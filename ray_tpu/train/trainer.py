"""JaxTrainer: the DataParallelTrainer equivalent.

Reference flow: BaseTrainer.fit (train/base_trainer.py:567) →
DataParallelTrainer loop (data_parallel_trainer.py:25) →
BackendExecutor.start (backend_executor.py:135) creates a WorkerGroup
and runs `train_loop_per_worker` on every worker; FailureConfig
restarts from the latest checkpoint (air/config.py:394).

TPU-native differences: the distributed backend is a jax device mesh
(`ScalingConfig.mesh`), not a torch process group, and parallelism
strategies (dp/fsdp/tp/pp/sp/ep) are mesh axes rather than wrapper
classes.
"""

from __future__ import annotations

import functools
import os
import tempfile
import threading
from typing import Any, Callable, Dict, Optional

from ..observability import device as _device
from ..observability import timeline as _timeline
from ..observability import tracing as _tracing
from .checkpoint import Checkpoint, CheckpointManager
from .config import (CheckpointConfig, FailureConfig, Result, RunConfig,
                     ScalingConfig)
from .worker_group import WorkerGroup, _ReportCollector


def _enter_loop(loop_fn: Callable, t_fit: float, trace_id: str, *config):
    """What a worker calls in the user's loop's place with tracing on
    (under the loop's own signature): the span ``train.worker_start``,
    ``fit()`` entered (the driver's clock) -> here (worker group up,
    mesh built, dataset shards handed out), then the loop.  The first
    step's compilation accounts for itself (``xla_trace`` /
    ``xla_lower`` / ``xla_compile``)."""
    _timeline.record_span(
        "train.worker_start", t_fit, _timeline.now(),
        pid=_timeline.process_pid(),
        tid=threading.current_thread().name,
        args={"trace_id": trace_id, "span_id": _tracing.new_span_id()})
    return loop_fn(*config)


class JaxTrainer:
    """Run ``train_loop_per_worker`` on a gang of workers over a jax
    mesh.  Inside the loop use ``ray_tpu.train.report`` /
    ``get_context`` / ``get_dataset_shard`` / ``get_checkpoint``.
    """

    def __init__(self,
                 train_loop_per_worker: Callable,
                 *,
                 train_loop_config: Optional[Dict[str, Any]] = None,
                 scaling_config: Optional[ScalingConfig] = None,
                 run_config: Optional[RunConfig] = None,
                 datasets: Optional[Dict[str, Any]] = None,
                 resume_from_checkpoint: Optional[Checkpoint] = None):
        self.train_loop_per_worker = train_loop_per_worker
        self.train_loop_config = train_loop_config or {}
        self.scaling_config = scaling_config or ScalingConfig()
        self.run_config = run_config or RunConfig()
        self.datasets = datasets or {}
        self.resume_from_checkpoint = resume_from_checkpoint

    # ------------------------------------------------------------------
    def fit(self) -> Result:
        import ray_tpu

        _device.install_compile_listener()
        loop = self.train_loop_per_worker
        if _tracing.enabled():
            loop = functools.update_wrapper(functools.partial(
                _enter_loop, loop, _timeline.now(),
                _tracing.for_submission()[0]), loop)
        if not ray_tpu.is_initialized():
            ray_tpu.init()

        name = self.run_config.name or "jax_trainer"
        storage = self.run_config.storage_path or os.path.join(
            tempfile.gettempdir(), "ray_tpu_results", name)
        ckpt_cfg: CheckpointConfig = self.run_config.checkpoint_config
        manager = CheckpointManager(
            storage,
            num_to_keep=ckpt_cfg.num_to_keep,
            score_attribute=ckpt_cfg.checkpoint_score_attribute,
            score_order=ckpt_cfg.checkpoint_score_order)

        failure: FailureConfig = self.run_config.failure_config
        max_failures = failure.max_failures
        attempts = 0
        latest_ckpt = self.resume_from_checkpoint
        last_error: Optional[BaseException] = None
        all_metrics: list = []

        while True:
            from .session import reset_dataset_shards

            reset_dataset_shards()
            collector = _ReportCollector.remote()
            coordinators: list = []
            group = WorkerGroup(
                self.scaling_config.num_workers,
                self.scaling_config.worker_resources(),
                self.scaling_config.placement_strategy)
            try:
                from .worker_group import process_identity

                mine = process_identity()
                idents = group.run_all("identity")
                colocated = all(ident == mine for ident in idents)
                if (not colocated and self.scaling_config.mesh is not None
                        and self.scaling_config.num_workers > 1):
                    # The gang spans processes/hosts: form ONE global
                    # jax runtime so the mesh covers every worker's
                    # devices (multi-controller SPMD; reference shape:
                    # _setup_torch_process_group, train/torch/config.py:66).
                    if len(set(idents)) != len(idents):
                        raise ValueError(
                            "distributed training needs one worker per "
                            "node process (actors share their node's "
                            "jax runtime) — got multiple workers on one "
                            "node; use placement_strategy='SPREAD'")
                    coordinator = ray_tpu.get(
                        group.workers[0].reserve_coordinator.remote())
                    group.run_all("setup_distributed", coordinator)
                elif (not colocated
                        and self.scaling_config.num_workers > 1):
                    # No shared jax runtime across the gang: gradient
                    # sync rides the DCN collective ring instead
                    # (session.allreduce_gradients → ring allreduce,
                    # docs/networking.md).  Fresh uuid-suffixed group
                    # name per attempt — a restarted gang must never
                    # rendezvous against a dead gang's stale endpoints.
                    group.setup_collectives()
                datasets = self.datasets
                if not colocated and datasets:
                    # Cross-process gang: host ONE shared execution per
                    # dataset in this (driver) process and hand workers
                    # a coordinator handle — each read task runs exactly
                    # once instead of once per worker
                    # (split_coordinator.py; reference output_splitter).
                    from .split_coordinator import make_split_coordinator

                    datasets = {}
                    for key, d in self.datasets.items():
                        if hasattr(d, "streaming_split"):
                            ref = make_split_coordinator(
                                d, self.scaling_config.num_workers)
                            coordinators.append(ref.actor)
                            datasets[key] = ref
                        else:
                            datasets[key] = d
                refs = group.run_all_async(
                    "run", loop,
                    self.train_loop_config, self.scaling_config.mesh,
                    collector, name, storage, datasets,
                    latest_ckpt.path if latest_ckpt else None,
                    colocated)
                ray_tpu.get(refs)
                latest_ckpt = self._drain(
                    collector, manager, all_metrics) or latest_ckpt
                last_error = None
                break
            except Exception as e:  # worker failure
                latest_ckpt = self._drain(
                    collector, manager, all_metrics) or latest_ckpt
                last_error = e
                attempts += 1
                if max_failures >= 0 and attempts > max_failures:
                    break
                if manager.latest_checkpoint() is not None:
                    latest_ckpt = manager.latest_checkpoint()
            finally:
                group.shutdown()
                for coord in coordinators:
                    try:
                        ray_tpu.kill(coord)
                    except Exception:
                        pass
                try:
                    ray_tpu.kill(collector)
                except Exception:
                    pass

        final_ckpt = manager.best_checkpoint() or latest_ckpt
        return self._finish(all_metrics, final_ckpt, last_error,
                            max_failures, attempts, storage, manager)

    @staticmethod
    def _drain(collector, manager: CheckpointManager,
               all_metrics: list) -> Optional[Checkpoint]:
        """Pull reports + per-rank checkpoint dirs off the collector.
        All ranks' dirs for one iteration merge into one checkpoint
        (rank shards carry distinct files under fsdp-sharded saves)."""
        import ray_tpu

        reports, ckpt_dirs = ray_tpu.get(collector.drain.remote())
        all_metrics.extend(reports)
        report_by_iter = {m.get("iteration"): m for m in reports}
        latest = None
        for it in sorted(ckpt_dirs):
            rank_dirs = ckpt_dirs[it]
            ordered = [rank_dirs[r] for r in sorted(rank_dirs)]
            metrics = report_by_iter.get(it, {"iteration": it})
            latest = manager.register(ordered, metrics)
        return latest

    @staticmethod
    def _finish(all_metrics, final_ckpt, last_error, max_failures,
                attempts, storage, manager) -> Result:
        try:
            import pandas as pd

            metrics_df = pd.DataFrame(all_metrics)
        except ImportError:  # pandas is optional everywhere else too
            metrics_df = None
        result = Result(
            metrics=all_metrics[-1] if all_metrics else {},
            checkpoint=final_ckpt,
            error=last_error,
            path=storage,
            metrics_dataframe=metrics_df)
        result._best_checkpoints = manager.list_checkpoints()
        if last_error is not None and max_failures >= 0:
            raise TrainingFailedError(
                f"training failed after {attempts} attempt(s)"
            ) from last_error
        return result


class TrainingFailedError(RuntimeError):
    """Reference: ray.train.base_trainer.TrainingFailedError."""
