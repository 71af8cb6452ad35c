"""One run of one cell:

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Finds the cell's files by name (``lib/spec.py``), refuses a machine
without the cell's chips, sets up, measures for ``--seconds`` and prints
ONE JSON object as the last line of stdout: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` and, traced, ``breakdown``.  With
``--trace 0`` the metrics are the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics.  Everything else goes to earlier
lines and to ``benchmarks/out/<cell>/``.

One process: it owns the chip(s) from start to end.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()   # "process start" of setup_s

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(BENCH_DIR))

from benchmarks.lib import runtime, spec  # noqa: E402

HOST_ANNOTATIONS = ("train.step", "serve.prefill", "serve.decode_chunk",
                    "serve.harvest_chunk")


def _say(**fields) -> None:
    print(json.dumps(fields, default=str), flush=True)


def measure(argv, allow_platforms=("tpu",), bench_dir=spec.BENCH_DIR,
            benchmark_json=None, t_process=None):
    """Run the cell and return the result object (not yet printed).
    ``allow_platforms`` is lifted by the CPU rehearsal tests only; there
    is no option for it on the command line."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)

    cell = spec.Cell(args.workload, bench_dir, benchmark_json)
    cache_dir = runtime.place_caches()
    watch = runtime.compile_watch()
    devices = runtime.claim_devices(cell.chips, allow_platforms)
    peaks = runtime.load_peaks(devices[0].device_kind, bench_dir)
    out_dir = os.path.join(bench_dir, "out", cell.name)
    os.makedirs(out_dir, exist_ok=True)
    ctx = runtime.Context(
        cell=cell, seed=args.seed, seconds=args.seconds,
        trace=bool(args.trace),
        t_process=T_PROCESS if t_process is None else t_process,
        devices=devices, peaks=peaks, out_dir=out_dir)
    _say(event="start", cell=cell.name, seed=args.seed,
         seconds=args.seconds, trace=args.trace, cache_dir=cache_dir,
         device_kind=devices[0].device_kind, chips=cell.chips)

    obs = cell.kind.run(ctx)
    obs.update(cell=cell, peaks=peaks, chips=cell.chips)

    group = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for entry, read in cell.readers(group):
        value = read(obs)
        if value is not None:
            metrics[entry["name"]] = {"value": value, "unit": entry["unit"]}
    checks = obs["checks"]
    memory = obs["memory"]
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": cell.chips,
              "memory_peak_bytes": max(memory["peak_in_use"],
                                       memory["peak_reserved"])}
    result = {"correct": all(checks.values()),
              "attempted": obs["attempted"], "failed": obs["failed"],
              "metrics": metrics, "device": device}
    trace = obs.get("trace")
    if trace is not None:
        from benchmarks.lib import trace_reduce

        with open(os.path.join(out_dir, "trace_describe.txt"), "w") as f:
            f.write(trace_reduce.describe(os.path.join(out_dir, "trace")))
        device["busy_s"] = trace.busy_s
        device["window_s"] = trace.window_s
        result["breakdown"] = {
            "device_ops": [[n, s] for n, s in trace.top_ops()],
            "idle_gaps": [[n, s] for n, s in
                          trace.idle_gaps(names=HOST_ANNOTATIONS)]}
    # what ``correct`` compared, each number beside its limit: the result
    # line's last key, and the run's last lines on standard error
    result["compared"] = obs["compared"]
    _say(event="facts", checks=checks, memory=memory,
         setup_s=obs["setup_s"], compile_events=watch.count,
         compile_s=watch.seconds, cache_hits=watch.cache_hits,
         cache_misses=watch.cache_misses,
         **{k: obs[k] for k in ("loss_gap", "losses", "reference_loss",
                                "grad_norm_gap", "grad_norm_first",
                                "reference_grad_norm", "grad_leaf_gaps",
                                "reference_s",
                                "logit_gap_max", "logit_gaps",
                                "weights_s", "engine_start_s",
                                "batch_sharding", "callers_left",
                                "window_compiles") if k in obs})
    return result, obs


def main(argv) -> int:
    try:
        result, _obs = measure(argv)
    except (runtime.BenchmarkRefused, spec.SpecError) as e:
        print(f"benchmarks/run.py: refused: {e}", file=sys.stderr,
              flush=True)
        return 2
    for name, (value, limit) in result["compared"].items():
        print(f"compared {name} {value!r} limit {limit!r}", file=sys.stderr)
    print(f"correct {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
