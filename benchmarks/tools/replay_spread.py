"""How far a closed-loop serve cell's tokens/s will spread over seeds,
BEFORE any chip time: a replay of the dense plane's scheduler on the load
generator's own draws.

    python3 benchmarks/tools/replay_spread.py --workload <cell> \\
        --prefill-s 4096=0.15,8192=0.37,12288=0.66 --chunk-s 0.272

``--prefill-s``: seconds of one prefill launch a bucket (a row a launch:
buckets past ``llm._GROUP_POSITIONS``), ``--chunk-s``: seconds of one
chunk of ``--k`` decode steps; both from one traced run or
``tools/prefill_times.py``.  No device, no engine: an iteration is
``_admit_wave`` (every free slot takes the head of the backlog and pays
its bucket's prefill) then one chunk over the occupied slots; a row that
ends in a chunk is seen by the host while the next one runs, so its slot
sits that one out (``serve/llm.py`` ``_loop``).  Tokens are counted as
``loadgen.Log.tokens_in_window`` counts them.  The lengths are the
cell's kind's own draws (``--kind`` replays the cell under the other
generator).  It reproduced cell 8's six seeds each within 1.5% (PERF.md
section 6, PR 37).

Printed: the median, the deviation and the quartile spread over
``--seeds`` seeds, and of the sets of six (farthest run left out where
that narrows it, as the driver reads a set) the median spread and the
share under ``--admit``.
"""

from __future__ import annotations

import argparse
import os
import statistics
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks.kinds import serve_llm_even  # noqa: E402
from benchmarks.lib import loadgen, spec  # noqa: E402


def draws(traffic, seed, kind):
    """-> ``first(caller)``, ``later(caller)``: (prompt, output) tokens of
    a caller's first request (its part-way start applied) and of the next
    request the loop creates."""
    callers = int(traffic["arrivals"]["callers"])

    def scaled(n_out, scale):
        return max(1, int(round(n_out * scale)))

    if kind == "serve_llm_even":
        source = serve_llm_even.SharedSource(traffic, seed, vocab=2)
        firsts = []
        for scale in serve_llm_even.start_scales(seed, callers):
            _, p, o = source.lengths()
            firsts.append((p, scaled(o, scale)))
        return firsts.__getitem__, lambda caller: source.lengths()[1:]
    prompts = [loadgen.Lengths(traffic["prompt_tokens"],
                               loadgen._rng(seed, 5, c))
               for c in range(callers)]
    outputs = [loadgen.Lengths(traffic["output_tokens"],
                               loadgen._rng(seed, 6, c))
               for c in range(callers)]

    def later(c):
        return prompts[c].draw(), outputs[c].draw()

    def first(c):   # ``LoadGenerator._caller``'s uniform part-way start
        p, o = later(c)
        return p, scaled(o, float(
            loadgen._rng(seed, 4, c).uniform(0.05, 1.0)))
    return first, later


def replay(traffic, seed, kind, slots, prefill_s, chunk_s, seconds, k=16):
    """Tokens/s inside the window of one replayed run."""
    arrivals = traffic["arrivals"]
    callers = int(arrivals["callers"])
    buckets = sorted(prefill_s)
    first, later = draws(traffic, seed, kind)
    t_open = float(arrivals.get("lead_in_s", 0.0))
    t_close = t_open + seconds
    # backlog entries: (sent, caller, prompt, output), in arrival order
    backlog = [(0.0, c) + first(c) for c in range(callers)]
    rows = [None] * slots     # [caller, steps left, sent, first, tokens]
    ended = []                # slots whose last token the running chunk made
    records = []              # (sent, first, done, tokens)
    t = chunk_end = 0.0
    while backlog or any(rows):
        for s in range(slots):
            if rows[s] is None and backlog:
                sent, c, p, o = backlog.pop(0)
                t += prefill_s[next(b for b in buckets if p <= b)]
                rows[s] = [c, o - 1, sent, t, o]
        t += chunk_s
        for s in ended:       # seen while this chunk ran: replied, freed
            c, _, sent, first_t, o = rows[s]
            records.append((sent, first_t, chunk_end, o))
            rows[s] = None
            if chunk_end < t_close:   # the callers send nothing new after
                backlog.append((chunk_end, c) + later(c))
        ended = []
        for s, row in enumerate(rows):
            if row is not None and row[1] >= 0:
                row[1] -= k
                if row[1] <= 0:
                    row[1] = -1
                    ended.append(s)
        chunk_end = t         # when the host reads this chunk's tokens
    total = 0.0
    for sent, first_t, done, tokens in records:
        if sent >= t_close or done < t_open:
            continue
        total += t_open <= first_t < t_close
        if tokens > 1 and done > first_t:
            inside = min(done, t_close) - max(first_t, t_open)
            total += (tokens - 1) * max(0.0, inside) / (done - first_t)
    return total / seconds


def spread(values):
    """Quartile distance as a share of the median (the contract's)."""
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def set_spread(values):
    """As the driver reads a set: the run farthest from the median left
    out where that narrows it."""
    m = statistics.median(values)
    rest = sorted(values, key=lambda v: abs(v - m))[:-1]
    return min(spread(values), spread(rest))


def study(traffic, kind, slots, prefill_s, chunk_s, seconds, seeds, admit,
          k=16, first_seed=2147480000):
    rates = [replay(traffic, first_seed + 17 * i, kind, slots, prefill_s,
                    chunk_s, seconds, k) for i in range(seeds)]
    sets = [set_spread(rates[i:i + 6]) for i in range(0, seeds - 5, 6)]
    return {"kind": kind, "seeds": seeds,
            "median_tokens_per_s": statistics.median(rates),
            "deviation": statistics.pstdev(rates) / statistics.mean(rates),
            "spread": spread(rates),
            "set_of_six_spread_median": statistics.median(sets),
            "sets_admitted_share": sum(s < admit for s in sets) / len(sets)}


def main(argv) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--prefill-s", required=True,
                    help="bucket=seconds,... of one prefill launch")
    ap.add_argument("--chunk-s", type=float, required=True)
    ap.add_argument("--k", type=int, default=16)
    ap.add_argument("--kind", default=None)
    ap.add_argument("--seeds", type=int, default=120)
    ap.add_argument("--admit", type=float, default=0.05,
                    help="half the metric's bound")
    args = ap.parse_args(argv)
    cell = spec.Cell(args.workload)
    prefill_s = {int(b): float(s) for b, s in
                 (pair.split("=") for pair in args.prefill_s.split(","))}
    print(study(cell.traffic, args.kind or cell.workload["kind"],
                int(cell.workload["engine"]["max_slots"]), prefill_s,
                args.chunk_s, float(cell.benchmark["run_seconds"]),
                args.seeds, args.admit, args.k))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
