"""Find the knee of an open-loop serve cell: the highest arrival rate
the system sustains.  Run once when a cell is defined (and again by a
later benchmark PR after an optimisation has moved it); the cell's
traffic file then carries 0.8 x the knee as a number.

    python3 benchmarks/tools/knee_sweep.py --workload <cell> --seed 1 \\
        --seconds 25 --rates 6,8,10,12,14,16

One process, one engine start; each rate is one open-loop window of the
cell's own traffic with only ``rate_per_s`` replaced.  Prints one JSON
line per rate: requests due, share completed, TTFT p50/p90 of the first
and second half of the window, TPOT p90, how late the generator ran.
Sustained = >= 98% completed and the second half's TTFT p90 not above
the first's by more than the run-to-run spread.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

T_PROCESS = time.perf_counter()
BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(BENCH_DIR))

from benchmarks.kinds import serve_llm  # noqa: E402
from benchmarks.lib import loadgen, readers, runtime, spec  # noqa: E402
from benchmarks.lib.runtime import percentile  # noqa: E402


def main(argv) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=25.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    cell = spec.Cell(args.workload)
    runtime.place_caches()
    runtime.compile_watch()
    devices = runtime.claim_devices(cell.chips)
    out_dir = os.path.join(BENCH_DIR, "out", cell.name)
    os.makedirs(out_dir, exist_ok=True)
    ctx = runtime.Context(cell, args.seed, args.seconds, False, T_PROCESS,
                          devices, runtime.load_peaks(
                              devices[0].device_kind), out_dir)
    with serve_llm.Engine(ctx) as engine:
        print(json.dumps(engine.facts), flush=True)
        for i, rate in enumerate(float(r) for r in args.rates.split(",")):
            traffic = dict(cell.traffic)
            traffic["arrivals"] = {**traffic["arrivals"],
                                   "rate_per_s": rate}
            log = loadgen.LoadGenerator(
                traffic, args.seed + i, cell.config["vocab_size"],
                engine.send).run(args.seconds)
            measured = log.measured()
            mid = (log.t_open + log.t_close) / 2
            halves = [[r for r in measured if r.due < mid],
                      [r for r in measured if r.due >= mid]]
            obs = {"measured": measured}
            row = {
                "rate_per_s": rate, "due": len(measured),
                "completed_share": sum(r.ok for r in measured)
                / max(1, len(measured)),
                "output_tokens_per_s": sum(
                    r.got_tokens for r in measured) / log.seconds,
                "ttft_p50_ms": percentile(readers.ttft_ms(obs), 50),
                "ttft_p90_ms": percentile(readers.ttft_ms(obs), 90),
                "ttft_p90_ms_halves": [
                    percentile(readers.ttft_ms({"measured": h}), 90)
                    for h in halves],
                "tpot_p90_ms": percentile(readers.tpot_ms(obs), 90),
                "loadgen_lag_p99_ms": percentile(
                    readers.loadgen_lag_ms(obs), 99),
                "drained_by_s": max((r.done for r in measured),
                                    default=log.t_close) - log.t_close,
            }
            print(json.dumps(row), flush=True)
            if row["completed_share"] < 0.9:
                break   # far past the knee: the rest only queue
            time.sleep(2.0)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
