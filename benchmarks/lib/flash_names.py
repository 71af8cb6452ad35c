"""Tell the three flash attention kernels apart in a device trace, by
name.  ``ray_tpu/ops/flash_attention.py`` gives each ``pallas_call`` a
``name=``; jax pushes it onto the name stack as the innermost scope, and
XLA names the custom-call instruction after that scope, so a v5e trace's
``XLA Ops`` event reads ``%flash_attention_dq.3 = f32[...] custom-call(
...), custom_call_target="tpu_custom_call"...`` (PERF.md section 3) — per
shard under a mesh too.  A program whose kernels carry no name matches
nothing here, and the readers return None.
"""

from __future__ import annotations

from typing import Optional

from . import readers

# kernel -> the instruction name XLA derives from the pallas_call's name
# (dotted suffixes make it unique within the module: ``.3``, ``.2.remat``)
KERNEL_OPS = {
    "fwd": r"^%flash_attention_fwd(\.\w+)* = ",
    "dq": r"^%flash_attention_dq(\.\w+)* = ",
    "dkdv": r"^%flash_attention_dkdv(\.\w+)* = ",
}


def kernel_seconds(trace, kernel: str) -> float:
    return trace.seconds_matching(KERNEL_OPS[kernel])


def time_share(kernel: str):
    """Device time of one kernel / device time of the steps, in %."""
    def read(obs) -> Optional[float]:
        trace = obs.get("trace")
        runs = trace.module_runs(readers.TRAIN_STEP_MODULE) if trace else []
        seconds = kernel_seconds(trace, kernel) if runs else 0.0
        if not seconds:
            return None
        return 100.0 * seconds / sum(e - s for s, e, _ in runs)
    return read
