"""The train step of a window / full decoder with experts in a device
trace, and the readers defined on it (``lib/afmoe_flops.py`` counts what
the step needs).  Nothing is found by an array's shape: the flash kernels
by the names ``lib/flash_names.py`` reads (the banded backward keeps them),
the grouped matmuls by ``lib/moe_names.py``'s ``%ragged-dot-none*``, the
rest by the program's scopes (``router``, ``expert_dispatch``,
``expert_ffn``, ``router_balance``) through ``scope_names.split``.  A
program without such kernels or scopes gives None.
"""

from __future__ import annotations

import re
from typing import Optional

from . import (afmoe_flops, flash_names, moe_names, readers, scope_names,
               ssm_names)


def train_mfu(obs) -> Optional[float]:
    """tokens/s a chip x ``afmoe_flops.train_flops_per_token`` / the chip's
    bf16 peak, in %: the whole step's share of the peak."""
    rate = readers.train_tokens_per_s_per_chip(obs)
    if rate is None:
        return None
    per_token = afmoe_flops.train_flops_per_token(obs["cell"].config,
                                                  obs["seq_len"])
    return 100.0 * rate * per_token / obs["peaks"]["bf16_flops_per_s"]


def _kernel_s_a_step(obs, seconds: float) -> Optional[float]:
    """Kernel seconds a step: their share of the steps' device time x a
    whole step's (a step the trace's edge cut miscounts neither)."""
    step_ms = readers.train_step_device_ms(obs)
    share = readers._share_of_steps(obs, seconds)
    if not seconds or step_ms is None or share is None:
        return None
    return share * 1e-2 * step_ms * 1e-3


def swa_train_attention_roofline(obs) -> Optional[float]:
    """Least time of the three flash kernels' work of one step INSIDE the
    masks (``afmoe_flops.flash_train_flops`` / bytes at the chip's peaks,
    the larger) / their measured seconds a step (forward + dq + dk/dv, by
    name), in %."""
    trace = obs.get("trace")
    if not trace:
        return None
    kernel_s = _kernel_s_a_step(obs, sum(
        flash_names.kernel_seconds(trace, k) for k in flash_names.KERNEL_OPS))
    if kernel_s is None:
        return None
    cfg, peaks = obs["cell"].config, obs["peaks"]
    least = max(
        afmoe_flops.flash_train_flops(cfg, obs["batch"], obs["seq_len"])
        / peaks["bf16_flops_per_s"],
        afmoe_flops.flash_train_bytes(cfg, obs["batch"], obs["seq_len"])
        / peaks["hbm_bytes_per_s"])
    return 100.0 * least / kernel_s


def held_rows_a_step(obs) -> Optional[float]:
    """Rows the held experts computed a step, all expert layers together:
    from the step's own ``expert_rows`` metric ((expert layers, experts):
    every expert's choices) where the run hands it over as
    ``obs["expert_rows"]`` -- ``kinds/train_lm.py`` hands over no step
    metric yet (PERF.md section 7), so today nothing.  The EXPECTED rows
    will not do: random weights route a chip's share of the rows +-45% off
    it from seed to seed."""
    rows = obs.get("expert_rows")
    if rows is None:
        return None
    share = obs["cell"].config.get("share") or {}
    first = share.get("experts_first", 0)
    held = share.get("experts_held", len(rows[0]))
    return float(sum(sum(layer[first:first + held]) for layer in rows))


def train_expert_matmul_roofline(obs) -> Optional[float]:
    """Least time of one step's grouped matmuls, forward and backward, over
    the rows the held experts computed (``held_rows_a_step``;
    ``afmoe_flops.expert_matmul_train_flops`` / ``_bytes`` at the chip's
    peaks, the larger) / the ``%ragged-dot-none*`` kernels' measured seconds
    a step -- the recomputed forward's among them, which the least time
    does not count -- in %."""
    trace, rows = obs.get("trace"), held_rows_a_step(obs)
    if not trace or not trace.devices or rows is None:
        return None
    kernel = re.compile(moe_names.GROUPED_MATMUL_OP)
    kernel_s = _kernel_s_a_step(obs, sum(
        end - start for start, end, name in ssm_names._leaves_inside(
            trace, readers.TRAIN_STEP_MODULE) if kernel.search(name)))
    if kernel_s is None:
        return None
    cfg, peaks = obs["cell"].config, obs["peaks"]
    least = max(
        afmoe_flops.expert_matmul_train_flops(cfg, rows)
        / peaks["bf16_flops_per_s"],
        afmoe_flops.expert_matmul_train_bytes(cfg, rows)
        / peaks["hbm_bytes_per_s"])
    return 100.0 * least / kernel_s


expert_ffn_time_share = scope_names.scopes_time_share(
    *moe_names.EXPERT_SCOPES, which="train")
routing_time_share = scope_names.scopes_time_share(
    *moe_names.ROUTING_SCOPES, which="train")
balance_update_time_share = scope_names.scopes_time_share(
    "router_balance", which="train")
