"""Resource accounting.

Reference semantics: src/ray/common/scheduling/ — a node advertises a
total resource set ({"CPU": n, "TPU": m, custom...}); tasks demand
resources which are acquired at dispatch and released at completion.
TPU note: nodes can carry placement labels (see NodeLabel scheduling
in cluster/head.py); ICI-topology-aware labels are not auto-detected
yet — pass them explicitly at node start.
"""

from __future__ import annotations

import glob
import os
import threading
from typing import Dict, Optional


class ResourceSet:
    def __init__(self, total: Dict[str, float]):
        self._total = {k: float(v) for k, v in total.items() if v}
        self._available = dict(self._total)
        self._cond = threading.Condition()

    @property
    def total(self) -> Dict[str, float]:
        return dict(self._total)

    def available(self) -> Dict[str, float]:
        with self._cond:
            return dict(self._available)

    def can_ever_fit(self, demand: Dict[str, float]) -> bool:
        return all(self._total.get(k, 0.0) >= v for k, v in demand.items())

    def fits_now(self, demand: Dict[str, float]) -> bool:
        with self._cond:
            return all(self._available.get(k, 0.0) >= v - 1e-9
                       for k, v in demand.items())

    def try_acquire(self, demand: Dict[str, float]) -> bool:
        with self._cond:
            if all(self._available.get(k, 0.0) >= v - 1e-9
                   for k, v in demand.items()):
                for k, v in demand.items():
                    self._available[k] = self._available.get(k, 0.0) - v
                return True
            return False

    def acquire(self, demand: Dict[str, float],
                timeout: Optional[float] = None) -> bool:
        with self._cond:
            ok = self._cond.wait_for(
                lambda: all(self._available.get(k, 0.0) >= v - 1e-9
                            for k, v in demand.items()),
                timeout,
            )
            if not ok:
                return False
            for k, v in demand.items():
                self._available[k] = self._available.get(k, 0.0) - v
            return True

    def release(self, demand: Dict[str, float]):
        with self._cond:
            for k, v in demand.items():
                self._available[k] = min(
                    self._total.get(k, 0.0), self._available.get(k, 0.0) + v
                )
            self._cond.notify_all()

    def add_capacity(self, extra: Dict[str, float]):
        """Used by placement groups to mint bundle resources."""
        with self._cond:
            for k, v in extra.items():
                self._total[k] = self._total.get(k, 0.0) + v
                self._available[k] = self._available.get(k, 0.0) + v
            self._cond.notify_all()

    def remove_capacity(self, extra: Dict[str, float]):
        with self._cond:
            for k, v in extra.items():
                self._total[k] = max(0.0, self._total.get(k, 0.0) - v)
                self._available[k] = max(
                    0.0, self._available.get(k, 0.0) - v)
            self._cond.notify_all()


def detect_node_resources(num_cpus: Optional[float] = None,
                          num_tpus: Optional[float] = None,
                          resources: Optional[Dict[str, float]] = None
                          ) -> Dict[str, float]:
    """Auto-detect this host's resources."""
    total: Dict[str, float] = {}
    total["CPU"] = float(num_cpus if num_cpus is not None
                         else os.cpu_count() or 1)
    if num_tpus is None:
        num_tpus = float(_count_tpu_chips())
    if num_tpus:
        total["TPU"] = float(num_tpus)
    total["memory"] = float(_detect_memory_bytes())
    if resources:
        total.update({k: float(v) for k, v in resources.items()})
    return total


def _count_tpu_chips() -> int:
    """TPU chips this process may use, counted WITHOUT jax: from the
    device files the TPU driver exposes (``/dev/accel<N>``, or one
    numbered ``/dev/vfio`` group per chip — the same probe as the
    reference's _private/accelerators/tpu.py).  ``jax.devices()`` would
    answer too, but it initialises the backend: it takes the chip away
    from a child this process may be about to start (one process per
    chip), it breaks a later ``jax.distributed.initialize``, and its
    failure used to be swallowed into "0 chips".  A process held to the
    CPU (``JAX_PLATFORMS=cpu``) advertises none."""
    if os.environ.get("JAX_PLATFORMS", "") == "cpu":
        return 0
    return (len(glob.glob("/dev/accel[0-9]*"))
            or len(glob.glob("/dev/vfio/[0-9]*")))


def _detect_memory_bytes() -> int:
    try:
        with open("/proc/meminfo") as f:
            for line in f:
                if line.startswith("MemTotal:"):
                    return int(line.split()[1]) * 1024
    except OSError:
        pass
    return 8 * 1024**3
