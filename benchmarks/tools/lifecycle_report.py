"""One traced run of a serve cell, then the program's own account of it
held against the load generator's and the device trace's:

    python3 benchmarks/tools/lifecycle_report.py --workload <cell> \\
        --seed 1 --seconds 40

Prints the run's result line (as ``run.py --trace 1`` would), then one
JSON line ``lifecycle`` with

- ``waits_vs_ttft_ms``: per request, serve.wait_boundary + wait_slot +
  wait_prefill against the ``ttft_ms`` of its reply (joined to the
  generator's log by prompt length, output length and send time): the largest
  gap, which the reply's rounding bounds at 0.005 ms;
- ``tokens``: output tokens of the requests wholly inside the window, by
  the program's spans and by the generator's log;
- ``ring``: events in the timeline ring and events it dropped;
- ``one_request``: a request of the traced slice laid against the device
  on the profiler's clock (``program_spans.profiler_minus_perf``): its
  stamps and every ``jit_prefill`` / ``jit_decode_k`` module that ran
  between its submission and its first token, in ms after submission.

Also written to ``benchmarks/out/<cell>/lifecycle.json``.
"""

from __future__ import annotations

import json
import os
import sys
import time

T_PROCESS = time.perf_counter()
BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(BENCH_DIR))

from benchmarks import run as bench_run  # noqa: E402
from benchmarks.lib import program_spans  # noqa: E402

JOIN_SLACK_S = 0.05


def join_to_log(requests, records):
    """(request, record) pairs.  The generator stamps ``sent`` just
    before the handle's span starts, so a request's record is the last
    one of its prompt and output lengths sent before that start (at most
    JOIN_SLACK_S before: the generator's own bookkeeping)."""
    by_lengths = {}
    for rec in sorted(records, key=lambda r: r.sent):
        by_lengths.setdefault((rec.prompt_tokens, rec.got_tokens),
                              []).append(rec)
    pairs = []
    for req in requests:
        if req.inbound_ms is None:
            continue
        started = req.t_submit - req.inbound_ms * 1e-3
        recs = by_lengths.get(
            (req.args["prompt_tokens"], req.args["output_tokens"]), [])
        before = [r for r in recs if r.sent <= started]
        if before and started - before[-1].sent < JOIN_SLACK_S:
            recs.remove(before[-1])
            pairs.append((req, before[-1]))
    return pairs


def one_request(got, trace, span, to_perf):
    """A request submitted inside the traced slice whose prefill group
    was the only one of its wave, against the device's modules."""
    offset = program_spans.profiler_minus_perf(trace, to_perf)
    if offset is None:
        return None
    launches = sorted(g["t_launch"] for g in got.groups)
    for req in got.requests:
        first = req.t_submit + sum(
            req.phase_ms.get(p, 0.0) for p in program_spans.PHASES[:3]) * 1e-3
        if "launch_ms" not in req.prefill or not (
                span[0] <= req.t_submit and first <= span[1]):
            continue
        seen = req.t_submit + req.phase_ms["serve.wait_boundary"] * 1e-3
        admitted = seen + req.phase_ms["serve.wait_slot"] * 1e-3
        launched = admitted + req.prefill["launch_ms"] * 1e-3
        if sum(abs(t - launched) < 0.05 for t in launches) != 1:
            continue

        def ms(t_perf):
            return round((t_perf - req.t_submit) * 1e3, 3)

        modules = [
            [name.split("(")[0], ms(s - offset), ms(e - offset)]
            for s, e, name in sorted(trace.devices[0].modules)
            if e - offset >= req.t_submit and s - offset <= first]
        return {"rid": req.args["rid"], "slot": req.args["slot"],
                "prompt_tokens": req.args["prompt_tokens"],
                "output_tokens": req.args["output_tokens"],
                "prefill": req.prefill, "profiler_minus_perf_s": offset,
                "stamps_ms": {"submit": 0.0, "seen": ms(seen),
                              "admitted": ms(admitted),
                              "prefill_launched": ms(launched),
                              "first_token": ms(first),
                              "done": ms(req.t_done)},
                "harvests": req.harvests,
                "inbound_ms": req.inbound_ms,
                "outbound_ms": req.outbound_ms,
                "device_modules_ms": modules}
    return None


def main(argv) -> int:
    from ray_tpu.observability import timeline

    result, obs = bench_run.measure(list(argv) + ["--trace", "1"],
                                    t_process=T_PROCESS)
    print(json.dumps(result), flush=True)
    got = program_spans.collect(obs)
    report = {"cell": obs["cell"].name,
              "ring": {"events": len(timeline.export_timeline()),
                       "dropped": timeline.dropped_events()}}
    if got is not None:
        log = obs["log"]
        pairs = join_to_log(got.requests, log.records)
        gaps = [abs(sum(req.phase_ms[p] for p in program_spans.PHASES[:3])
                    - rec.ttft_ms) for req, rec in pairs
                if "serve.decode" in req.phase_ms and rec.ok]
        inside = [r for r in got.requests if r.t_done <= log.t_close]
        by_log = [r for r in log.records if r.ok
                  and log.t_open <= r.sent and r.done <= log.t_close]
        report.update({
            "requests": len(got.requests), "joined": len(pairs),
            "waits_vs_ttft_ms": {"n": len(gaps),
                                 "max_gap": max(gaps) if gaps else None},
            "tokens": {
                "program_requests_inside": len(inside),
                "program_tokens_inside": sum(
                    r.args["output_tokens"] for r in inside),
                "generator_requests_inside": len(by_log),
                "generator_tokens_inside": sum(
                    r.got_tokens for r in by_log),
                "chunk_tokens_kept": sum(
                    c["tokens_kept"] for c in got.chunks),
                "chunks": len(got.chunks), "groups": len(got.groups)},
            "outcomes": sorted({r.args["outcome"] for r in got.requests}),
            "preemptions": sum(r.args["preemptions"]
                               for r in got.requests),
        })
        trace, span = obs.get("trace"), obs.get("trace_span")
        if trace is not None and trace.devices and span and span[0]:
            report["one_request"] = one_request(
                got, trace, span, timeline.perf_from_wall)
    print(json.dumps({"lifecycle": report}), flush=True)
    with open(os.path.join(BENCH_DIR, "out", obs["cell"].name,
                           "lifecycle.json"), "w") as f:
        json.dump(report, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
