"""Worker group: the actor gang that runs the train loop.

Reference: train/_internal/worker_group.py:102,193 — N actors placed by
a placement group; train/_internal/backend_executor.py:68 starts them
and installs the distributed backend (the torch path's process-group
bootstrap is train/torch/config.py:66 _setup_torch_process_group).

TPU-native backend setup: when the gang spans processes/hosts, rank 0
reserves a coordinator endpoint and every worker joins one global jax
runtime via ``jax.distributed.initialize`` — after which
``jax.devices()`` spans all hosts and the per-run ``MeshSpec`` builds
ONE multi-host mesh (multi-controller SPMD).  Colocated test gangs skip
the bootstrap and share the process-local mesh.
"""

from __future__ import annotations

import traceback
from typing import Any, Callable, Dict, List, Optional

import ray_tpu
from ray_tpu.parallel.mesh import MeshSpec, build_mesh
from ray_tpu.parallel.sharding import use_mesh

from .checkpoint import Checkpoint
from .session import TrainContext, _Session, _set_session


@ray_tpu.remote
class _ReportCollector:
    """Aggregates per-rank reports.  Rank 0's metrics drive the metric
    stream, but checkpoint dirs are kept from EVERY rank, keyed by
    (iteration, rank): with host-sharded (fsdp) state each rank holds a
    distinct shard, and the trainer merges all ranks' dirs for an
    iteration into one checkpoint (reference persists checkpoints
    reported by any worker)."""

    def __init__(self):
        self.reports: List[Dict[str, Any]] = []
        # {iteration: {rank: checkpoint_dir}}
        self.checkpoint_dirs: Dict[int, Dict[int, str]] = {}

    def report(self, rank: int, iteration: int, metrics: Dict[str, Any],
               checkpoint_dir: Optional[str]):
        if rank == 0:
            self.reports.append(
                {"iteration": iteration, **metrics})
        if checkpoint_dir is not None:
            self.checkpoint_dirs.setdefault(iteration, {})[rank] = (
                checkpoint_dir)
        return True

    def drain(self):
        out = (self.reports, self.checkpoint_dirs)
        self.reports = []
        self.checkpoint_dirs = {}
        return out

    def latest(self):
        return self.reports[-1] if self.reports else None


def process_identity():
    """(node_id, pid) of the current process — the driver compares its
    own against every worker's to decide gang colocation."""
    import os

    import ray_tpu as _rt

    try:
        node = _rt.get_runtime_context().get_node_id()
    except Exception:
        node = ""
    return (node, os.getpid())


_jax_distributed_state = {"initialized": False, "coordinator": None,
                          "rank": None}


@ray_tpu.remote
class _TrainWorker:
    def __init__(self, rank: int, world_size: int):
        self.rank = rank
        self.world_size = world_size
        self._collective_group = None

    def identity(self):
        return process_identity()

    def setup_collectives(self, group_name: str,
                          timeout: float = 60.0) -> bool:
        """Join the gang's DCN collective ring (ray_tpu.collectives):
        the gradient-sync/weight-distribution path for gangs without a
        shared jax runtime.  Collective: every worker must be called
        (rendezvous blocks until the ring closes)."""
        from ray_tpu.collectives.group import CollectiveGroup

        if self._collective_group is not None:
            self._collective_group.close()
        self._collective_group = CollectiveGroup(
            group_name, self.rank, self.world_size, timeout=timeout)
        return True

    def teardown_collectives(self) -> bool:
        if self._collective_group is not None:
            self._collective_group.close()
            self._collective_group = None
        return True

    def reserve_coordinator(self) -> str:
        """Rank 0: reserve a host:port for the jax coordination service
        (reference analogue: the TCP store master address in
        train/torch/config.py:66)."""
        import socket

        s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        s.bind(("", 0))
        port = s.getsockname()[1]
        s.close()
        rt = ray_tpu.get_runtime()
        host = "127.0.0.1"
        if rt.cluster is not None:
            host = rt.cluster.address.rsplit(":", 1)[0]
        return f"{host}:{port}"

    def setup_distributed(self, coordinator: str) -> bool:
        """Join the global jax runtime (jax.distributed.initialize).

        One call per OS process: actors run as threads inside their
        node's process, so a multi-host gang needs one worker per node
        (SPREAD placement).  jax backends must not have been touched in
        this process yet — which is why neither Runtime boot
        (detect_node_resources counts chips from device files) nor the
        device-telemetry sampler ever initialises one."""
        import jax

        st = _jax_distributed_state
        if st["initialized"]:
            if (st["coordinator"] == coordinator
                    and st["rank"] == self.rank):
                return True  # FailureConfig retry landed on the same node
            raise RuntimeError(
                f"jax.distributed already initialized in this process "
                f"(coordinator {st['coordinator']}, rank {st['rank']}); "
                f"a distributed gang needs one train worker per node — "
                f"use placement_strategy='SPREAD' or STRICT_SPREAD")
        import os

        if os.environ.get("JAX_PLATFORMS", "").startswith("cpu"):
            # Cross-process CPU collectives (virtual-device test mode).
            # Probing jax.default_backend() here would initialize the
            # backend and break initialize(), so gate on the env var.
            try:
                jax.config.update("jax_cpu_collectives_implementation",
                                  "gloo")
            except Exception:
                pass
        jax.distributed.initialize(
            coordinator_address=coordinator,
            num_processes=self.world_size,
            process_id=self.rank)
        st.update(initialized=True, coordinator=coordinator,
                  rank=self.rank)
        return True

    def run(self, loop_fn: Callable, loop_config: Optional[Dict[str, Any]],
            mesh_spec: Optional[MeshSpec], collector,
            experiment_name: str, storage_path: str,
            datasets, latest_checkpoint_path: Optional[str],
            colocated: bool = True):
        latest = (Checkpoint(latest_checkpoint_path)
                  if latest_checkpoint_path else None)
        mesh = None
        if mesh_spec is not None:
            import jax

            mesh = build_mesh(mesh_spec, jax.devices())
        ctx = TrainContext(
            rank=self.rank, world_size=self.world_size,
            mesh=mesh, experiment_name=experiment_name,
            storage_path=storage_path, datasets=datasets,
            latest_checkpoint=latest, colocated=colocated,
            collective_group=self._collective_group)
        _set_session(_Session(ctx, collector, latest))
        try:
            if mesh is not None:
                with use_mesh(mesh):
                    return self._invoke(loop_fn, loop_config)
            return self._invoke(loop_fn, loop_config)
        finally:
            _set_session(None)

    @staticmethod
    def _invoke(loop_fn, loop_config):
        import inspect

        sig = inspect.signature(loop_fn)
        if len(sig.parameters) == 0:
            return loop_fn()
        return loop_fn(loop_config or {})


class WorkerGroup:
    """Gang of `_TrainWorker` actors (reference: worker_group.py:102)."""

    def __init__(self, num_workers: int,
                 resources_per_worker: Dict[str, float],
                 placement_strategy: str = "PACK"):
        self.num_workers = num_workers
        self._pg = None
        bundles = [dict(resources_per_worker) for _ in range(num_workers)]
        if any(v > 0 for b in bundles for v in b.values()):
            from ray_tpu.util.placement_group import placement_group

            self._pg = placement_group(bundles,
                                       strategy=placement_strategy)
            self._pg.wait(timeout_seconds=30)
        self.workers = []
        for rank in range(num_workers):
            opts = {}
            if self._pg is not None:
                from ray_tpu.core.task_spec import (
                    PlacementGroupSchedulingStrategy)

                res = dict(resources_per_worker)
                opts = {
                    "scheduling_strategy": PlacementGroupSchedulingStrategy(
                        placement_group=self._pg,
                        placement_group_bundle_index=rank),
                    "num_cpus": res.pop("CPU", None),
                    "num_tpus": res.pop("TPU", None),
                    "resources": res or None,
                }
            self.workers.append(
                _TrainWorker.options(**opts).remote(rank, num_workers))

    def run_all(self, method: str, *args) -> List[Any]:
        refs = [getattr(w, method).remote(*args) for w in self.workers]
        return ray_tpu.get(refs)

    def run_all_async(self, method: str, *args):
        return [getattr(w, method).remote(*args) for w in self.workers]

    def setup_collectives(self, group_name: Optional[str] = None,
                          timeout: float = 60.0) -> str:
        """Form one DCN collective ring across the gang (all workers
        rendezvous concurrently); returns the group name."""
        import uuid

        name = group_name or f"__train__/{uuid.uuid4().hex[:12]}"
        ray_tpu.get([w.setup_collectives.remote(name, timeout)
                     for w in self.workers])
        self._has_collectives = True
        return name

    def shutdown(self):
        # Retract collective rendezvous keys before killing: the head
        # KV entries outlive the actors, so a kill-only shutdown would
        # leak one __collectives__/<group>/<rank> key per worker per
        # training run.  Best-effort and bounded — dead workers' keys
        # are the restart path's problem (fresh uuid per attempt).
        if getattr(self, "_has_collectives", False):
            try:
                ray_tpu.get([w.teardown_collectives.remote()
                             for w in self.workers], timeout=10.0)
            except Exception:
                pass
            self._has_collectives = False
        for w in self.workers:
            try:
                ray_tpu.kill(w)
            except Exception:
                pass
        if self._pg is not None:
            from ray_tpu.util.placement_group import remove_placement_group

            try:
                remove_placement_group(self._pg)
            except Exception:
                traceback.print_exc()
        self.workers = []
