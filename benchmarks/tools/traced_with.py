"""One traced run of a cell, and beside its result line what OTHER reader
files make of the same observations: readers of ``benchmarks/metrics/`` that
the cell's entries do not name, or -- with ``--against <checkout>`` -- the
readers of another checkout's ``benchmarks/`` (the parent's, unpacked with
``git archive`` into a directory ``.gitignore`` lists), so that a PR which
changes how a reader finds its operations shows the same value from both on
ONE run:

    python3 benchmarks/tools/traced_with.py --workload <cell> --seed <n> \\
        --seconds 40 --against .chip_work/parent \\
        --readers ssm_state_update_roofline=nemotron_ssm_state_update_roofline,...

``new=old`` reads this tree's reader ``new`` beside the other checkout's
``old``; a bare name is the same name in both.  Prints the run's result
line, then ``{"readers": {name: value | null}}`` (and ``"against"``).
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


def _read_against(checkout: str, names, obs):
    """The other checkout's readers on ``obs``, with the cell as ITS files
    say it (its configuration's file, its ``lib/``): its own ``benchmarks``
    package takes this one's place in ``sys.modules`` (a reader file imports
    ``benchmarks.lib`` by name), after this tree's readers have read."""
    for name in [m for m in sys.modules if m.split(".")[0] == "benchmarks"]:
        del sys.modules[name]
    sys.path.insert(0, os.path.abspath(checkout))
    from benchmarks.lib import spec as other   # the other checkout's

    assert other.ROOT == os.path.abspath(checkout), other.ROOT
    theirs = {**obs, "cell": other.Cell(obs["cell"].name)}
    return {name: other.load_module("metrics", name).read(theirs)
            for name in names}


def main(argv) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", required=True)
    ap.add_argument("--seconds", default="40")
    ap.add_argument("--readers", required=True)
    ap.add_argument("--against", default=None)
    args = ap.parse_args(argv)
    pairs = []
    for name in args.readers.split(","):
        new, _, old = name.partition("=")
        pairs.append((new, old or new))
    result, obs = bench_run.measure(
        ["--workload", args.workload, "--seed", args.seed, "--seconds",
         args.seconds, "--trace", "1"])
    print(json.dumps(result), flush=True)
    out = {"readers": {new: spec.load_module("metrics", new).read(obs)
                       for new, _old in pairs}}
    if args.against:
        out["against"] = _read_against(args.against,
                                       [old for _new, old in pairs], obs)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
