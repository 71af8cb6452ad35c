"""Trainer configuration dataclasses.

Reference: ray.air config objects — ScalingConfig (air/config.py:102),
FailureConfig (:394), CheckpointConfig (:444), RunConfig (:593).  The
TPU-native ScalingConfig adds the mesh: workers are *hosts*, and the
per-run `MeshSpec` describes how their chips form parallelism axes
(replacing the reference's `use_gpu`/`resources_per_worker` GPU model).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

from ray_tpu.parallel.mesh import MeshSpec


@dataclasses.dataclass
class ScalingConfig:
    """How to scale training.

    num_workers: worker actors (one per TPU host on real pods).
    mesh: parallelism-axis layout over all chips of all workers; -1
    axes absorb remaining devices at runtime.
    resources_per_worker: scheduling resources per worker actor.
    """

    num_workers: int = 1
    mesh: Optional[MeshSpec] = None
    resources_per_worker: Optional[Dict[str, float]] = None
    placement_strategy: str = "PACK"

    def worker_resources(self) -> Dict[str, float]:
        if self.resources_per_worker is not None:
            return dict(self.resources_per_worker)
        return {"CPU": 1.0}


@dataclasses.dataclass
class FailureConfig:
    """max_failures: retries of a failed run (restarting workers from
    the latest checkpoint).  0 = fail fast; -1 = infinite."""

    max_failures: int = 0


@dataclasses.dataclass
class CheckpointConfig:
    num_to_keep: Optional[int] = None
    checkpoint_score_attribute: Optional[str] = None
    checkpoint_score_order: str = "max"

    def __post_init__(self):
        if self.checkpoint_score_order not in ("max", "min"):
            raise ValueError("checkpoint_score_order must be max|min")


@dataclasses.dataclass
class RunConfig:
    name: Optional[str] = None
    storage_path: Optional[str] = None
    failure_config: Optional[FailureConfig] = None
    checkpoint_config: Optional[CheckpointConfig] = None
    verbose: int = 0

    def __post_init__(self):
        if self.failure_config is None:
            self.failure_config = FailureConfig()
        if self.checkpoint_config is None:
            self.checkpoint_config = CheckpointConfig()


@dataclasses.dataclass
class Result:
    """Outcome of a training run (reference: ray.air Result)."""

    metrics: Dict[str, Any]
    checkpoint: Optional["Checkpoint"]  # noqa: F821 (train.checkpoint)
    error: Optional[BaseException]
    path: Optional[str] = None
    metrics_dataframe: Any = None

    @property
    def best_checkpoints(self):
        return getattr(self, "_best_checkpoints", [])
