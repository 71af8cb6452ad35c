"""Node bootstrap: head start, worker processes, cluster attach.

Reference analogues: python/ray/_private/node.py:1363
(start_head_processes), _private/services.py:1445/:1514 (spawning the
gcs_server / raylet binaries), and worker.py:2256 connect().

Process model: the *head* is a lightweight control-plane server
(ray_tpu.cluster.head.HeadServer) run either in-process (default, the
driver doubles as head node — matches ``ray.init()`` head mode) or as
its own subprocess.  *Worker nodes* are subprocesses running
``python -m ray_tpu.cluster.worker_main`` — each boots its own Runtime
+ NodeServer and registers with the head.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time
from typing import Dict, Optional

_head_server = None
_head_lock = threading.Lock()


def start_head(host: str = "127.0.0.1", port: int = 0,
               storage_path: Optional[str] = None) -> str:
    """Start an in-process head server; returns its address.
    ``storage_path`` enables GCS fault tolerance (tables persist and
    replay on restart at the same address)."""
    global _head_server
    from ..cluster.head import HeadServer

    with _head_lock:
        if _head_server is None:
            _head_server = HeadServer(host, port,  # raylint: disable=blocking-under-lock -- heads started here are never standbys, so the construction-time seed/dial path the analysis sees is unreachable; the lock guards the singleton
                                      storage_path=storage_path)
        return _head_server.address


def stop_head():
    global _head_server
    with _head_lock:
        if _head_server is not None:
            _head_server.shutdown()
            _head_server = None


def connect_to_cluster(address: str, *, namespace: str = "",
                       runtime_env: Optional[dict] = None,
                       num_cpus: Optional[float] = None,
                       num_tpus: Optional[float] = None,
                       resources: Optional[Dict[str, float]] = None,
                       node_name: str = "",
                       labels: Optional[Dict[str, str]] = None):
    """Boot a local Runtime and attach it to a running head
    (reference: ray.init(address=...) → connect(), worker.py:2256)."""
    from . import runtime as runtime_mod

    if address == "auto":
        address = os.environ.get("RAY_TPU_HEAD_ADDRESS", "")
        if not address:
            raise ConnectionError(
                'init(address="auto") needs RAY_TPU_HEAD_ADDRESS set')
    rt = runtime_mod.init_runtime(
        num_cpus=num_cpus, num_tpus=num_tpus, resources=resources,
        namespace=namespace, runtime_env=runtime_env)
    if rt.cluster is None:
        rt.attach_cluster(address, node_name=node_name, labels=labels)
    return rt


def start_worker_process(head_address: str, *,
                         num_cpus: Optional[float] = None,
                         resources: Optional[Dict[str, float]] = None,
                         node_name: str = "",
                         labels: Optional[Dict[str, str]] = None,
                         env: Optional[Dict[str, str]] = None,
                         force_cpu_platform: bool = True
                         ) -> subprocess.Popen:
    """Spawn a worker-node subprocess (reference: services.py:1514
    start_raylet — here the "raylet" and the worker runtime share one
    process).  ``force_cpu_platform`` keeps worker jax off the TPU so
    the driver retains chip ownership (one process per chip): it
    ASSIGNS ``JAX_PLATFORMS=cpu``, overriding whatever platform the
    parent's environment names."""
    cmd = [sys.executable, "-m", "ray_tpu.cluster.worker_main",
           "--head", head_address]
    if num_cpus is not None:
        cmd += ["--num-cpus", str(num_cpus)]
    if resources:
        cmd += ["--resources", json.dumps(resources)]
    if node_name:
        cmd += ["--name", node_name]
    if labels:
        cmd += ["--labels", json.dumps(labels)]
    child_env = dict(os.environ)
    if force_cpu_platform:
        child_env["JAX_PLATFORMS"] = "cpu"
    # Worker prints must reach the node log promptly (and survive a
    # crash) — see worker_main's log capture.
    child_env.setdefault("PYTHONUNBUFFERED", "1")
    child_env.update(env or {})
    return subprocess.Popen(cmd, env=child_env,
                            stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT)


def wait_for_nodes(head_address: str, count: int,
                   timeout: float = 30.0) -> None:
    """Block until ``count`` nodes are alive at the head."""
    from ..cluster.rpc import RpcClient

    client = RpcClient(head_address)
    deadline = time.monotonic() + timeout
    try:
        while time.monotonic() < deadline:
            nodes = client.call("list_nodes", {})
            if sum(1 for n in nodes if n["alive"]) >= count:
                return
            time.sleep(0.1)
        raise TimeoutError(
            f"cluster did not reach {count} nodes in {timeout}s")
    finally:
        client.close()
