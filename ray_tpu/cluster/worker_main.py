"""Worker-node process entry point.

``python -m ray_tpu.cluster.worker_main --head HOST:PORT [...]``

Boots a Runtime (with this node's resources), attaches it to the head,
and serves until the head connection drops or the parent dies
(reference: the raylet main loop, src/ray/raylet/main.cc — here the
node agent and the worker runtime share one process, which is the
right granularity for jax: one process == one jax client == one
multi-controller SPMD participant).
"""

from __future__ import annotations

import argparse
import json
import sys
import time


def main(argv=None) -> int:
    import os

    ap = argparse.ArgumentParser()
    ap.add_argument("--head", required=True)
    ap.add_argument("--num-cpus", type=float, default=None)
    ap.add_argument("--resources", type=str, default="")
    ap.add_argument("--name", type=str, default="")
    ap.add_argument("--labels", type=str, default="")
    ap.add_argument("--log-dir", type=str,
                    default=os.environ.get("RAY_TPU_LOG_DIR", ""))
    args = ap.parse_args(argv)

    import ray_tpu
    from ray_tpu.core.node import connect_to_cluster

    resources = json.loads(args.resources) if args.resources else None
    labels = json.loads(args.labels) if args.labels else None
    rt = connect_to_cluster(
        args.head, num_cpus=args.num_cpus, resources=resources,
        node_name=args.name, labels=labels)
    print(f"ray_tpu worker node {rt.node_id.hex()[:12]} "
          f"@ {rt.address} (head {args.head})", flush=True)
    # Structured log plane: task/actor prints on this node become
    # trace-stamped records in the shipped stream (observability/
    # logs.py) — `ray_tpu logs --trace <id>` sees worker stdout too.
    from ray_tpu.observability import logs as logs_mod

    logs_mod.capture_stdio()
    if args.log_dir:
        # Per-node log capture (reference: per-process files in the
        # session dir + log_monitor routing, _private/log_monitor.py):
        # task/actor prints on this node land in one tailable file,
        # registered in the head KV and served by the node's tail_log
        # RPC (CLI: `ray_tpu logs <node>`).
        os.makedirs(args.log_dir, exist_ok=True)
        log_path = os.path.join(
            args.log_dir, f"node-{rt.node_id.hex()[:12]}.log")
        f = open(log_path, "ab", buffering=0)
        sys.stdout.flush()
        sys.stderr.flush()
        os.dup2(f.fileno(), 1)
        os.dup2(f.fileno(), 2)
        # The existing sys.stdout wrapper now writes to the file but is
        # BLOCK-buffered against it (8 KB): without line buffering,
        # task prints sit invisible until the buffer fills and are lost
        # on crash.
        try:
            sys.stdout.reconfigure(line_buffering=True)
            sys.stderr.reconfigure(line_buffering=True)
        except Exception:
            pass
        rt.log_path = log_path
        # Bounded per-node STRUCTURED ring file alongside the raw
        # tail file: JSONL records survive the process (post-mortem
        # reads) without unbounded disk growth.
        logs_mod.configure_ring_file(os.path.join(
            args.log_dir, f"node-{rt.node_id.hex()[:12]}.jsonl"))

    # Flight recorder: rebase this node's record into the log dir when
    # no shared dir was pinned via env (keeps all of a node's forensics
    # together), then register base+pid in the head KV so the driver's
    # ProcessSupervisor can resolve a dead pid back to a node id and
    # ship the record into the incident bundle.
    from ray_tpu.observability import flightrec as flightrec_mod

    rec = flightrec_mod.current()
    if (rec is None or (args.log_dir
                        and not os.environ.get("RAY_TPU_FLIGHTREC_DIR"))):
        rec = flightrec_mod.install(args.log_dir or None)
    if rec is not None and rt.cluster is not None:
        try:
            rt.cluster.kv_put(
                rt.node_id.hex(),
                json.dumps({"base": rec.base, "pid": os.getpid()}),
                ns="flightrec")
        except Exception:
            pass

    try:
        head_gone_since = None
        while True:
            time.sleep(1.0)
            client = rt.cluster
            if client is None or client._stopped.is_set():
                return 0
            # Exit when the head is gone for good (connection lost and
            # not re-established within a grace window).
            if client.head._sock is None:
                head_gone_since = head_gone_since or time.monotonic()
                if time.monotonic() - head_gone_since > 5.0:
                    return 0
            else:
                head_gone_since = None
    except KeyboardInterrupt:
        return 0
    finally:
        ray_tpu.shutdown()


if __name__ == "__main__":
    sys.exit(main())
