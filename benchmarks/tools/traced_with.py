"""One traced run of a cell, and beside its result line what further
readers of ``benchmarks/metrics/`` make of the same observations -- readers
that are written and not entered in ``BENCHMARK.json`` yet:

    python3 benchmarks/tools/traced_with.py --workload <cell> --seed <n> \\
        --seconds 40 --readers swa_train_attention_roofline,...

Prints the run's result line, then ``{"readers": {name: value | null}}``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.dirname(BENCH_DIR))

from benchmarks import run as bench_run  # noqa: E402
from benchmarks.lib import spec  # noqa: E402


def main(argv) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", required=True)
    ap.add_argument("--seconds", default="40")
    ap.add_argument("--readers", required=True)
    args = ap.parse_args(argv)
    result, obs = bench_run.measure(
        ["--workload", args.workload, "--seed", args.seed, "--seconds",
         args.seconds, "--trace", "1"])
    print(json.dumps(result), flush=True)
    print(json.dumps({"readers": {
        name: spec.load_module("metrics", name).read(obs)
        for name in args.readers.split(",")}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
