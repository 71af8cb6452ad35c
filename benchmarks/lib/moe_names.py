"""An expert layer's work in a device trace, and the readers of the four
``moe_*`` metrics -- every configuration's with experts, whatever its
family.

``ray_tpu/models/moe.py`` traces its layer under three scopes of the
program's vocabulary (``observability/device.py`` ``SCOPES``): ``router``
(the router's matmul, its scores and top-k), ``expert_dispatch`` (the sort
by expert, the counts and the grid's metadata made of them, the gather of
rows into expert order, the un-sort and the gate-weighted sum) and
``expert_ffn`` (the grouped matmuls and the activation between them).
The two time shares are the own device seconds of the ops under those
scopes over the device seconds of the ``jit_decode_k`` runs, through
``scope_names.split``: nothing is found by an array's shape, so a model
whose batch, expert count or width differs joins with a line in a list.

The experts themselves are ``jax.lax.ragged_dot``.  For a TPU XLA lowers
each to a Mosaic kernel, plus a small kernel that turns the group sizes
into the grid's metadata, and names both instructions itself, so a v5e
trace's ``XLA Ops`` events read ``%ragged-dot-none.2 = f32[960,2048]{...}
custom-call(...), custom_call_target="tpu_custom_call"...`` and
``%ragged-dot-metadata = (s32[513]...) custom-call(...)`` (PERF.md
section 3; ``flash_names.py`` is the precedent).  The roofline reads the
``%ragged-dot-none*`` kernels by that name.

What a step had to do comes from the program's spans (``serve.chunk``:
``expert_rows``, ``experts_touched``, ``expert_rows_max``); how wide an
expert is, which layers have experts and how many of them this chip
holds, from the configuration (``moe_flops.expert_width`` /
``expert_layers`` / ``experts_held``).  A program without experts traces
no such scope, kernel or span attribute, and the readers return None.
"""

from __future__ import annotations

import re
import statistics
from typing import Optional, Tuple

from . import moe_flops, program_spans, readers, scope_names, ssm_names

GROUPED_MATMUL_OP = r"^%ragged-dot-none(\.\w+)* = "
EXPERT_SCOPES = ("expert_ffn",)
ROUTING_SCOPES = ("router", "expert_dispatch")


def chunk_medians(obs) -> Optional[Tuple[float, float, float]]:
    """Medians over the window's ``serve.chunk`` spans of (expert rows a
    step, (layer, expert) pairs touched a step, busiest expert's rows /
    mean rows per expert the chip holds): the program's own count of what
    its grouped matmuls had to do.  None where the spans carry no expert
    load."""
    got = program_spans.collect(obs)
    chunks = [c for c in (got.chunks if got else [])
              if c.get("expert_rows")]
    if not chunks:
        return None
    cfg = obs["cell"].config
    pairs = moe_flops.expert_layers(cfg) * moe_flops.experts_held(cfg)
    return (statistics.median(c["expert_rows"] / c["k"] for c in chunks),
            statistics.median(c["experts_touched"] / c["k"]
                              for c in chunks),
            statistics.median(c["expert_rows_max"]
                              / (c["expert_rows"] / pairs)
                              for c in chunks))


# --------------------------------------------------------------- readers
expert_ffn_time_share = scope_names.scopes_time_share(*EXPERT_SCOPES)
routing_time_share = scope_names.scopes_time_share(*ROUTING_SCOPES)


def load_imbalance(obs) -> Optional[float]:
    medians = chunk_medians(obs)
    return None if medians is None else medians[2]


def expert_matmul_roofline(obs) -> Optional[float]:
    """Least time of a step's grouped matmuls (the touched experts'
    matrices at an expert's own width and the rows' activations: HBM
    bytes or FLOPs at peak) / the ``%ragged-dot-none*`` kernels' measured
    time a step."""
    step_ms, medians = readers.decode_step_device_ms(obs), chunk_medians(obs)
    if step_ms is None or medians is None:
        return None
    trace = obs["trace"]
    kernel = re.compile(GROUPED_MATMUL_OP)
    matmul_s = sum(end - start for start, end, name in
                   ssm_names._leaves_inside(trace, readers.DECODE_MODULE)
                   if kernel.search(name))
    if not matmul_s:
        return None
    # the kernels' share of the decode programs' time x the median whole
    # launch's step: a program cut by the trace's edge miscounts neither
    runs = trace.module_runs(readers.DECODE_MODULE)
    kernel_s = matmul_s / sum(e - s for s, e, _ in runs) * step_ms * 1e-3
    rows, touched, _imbalance = medians
    cfg, peaks = obs["cell"].config, obs["peaks"]
    least = max(
        moe_flops.expert_matmul_bytes(cfg, touched, rows)
        / peaks["hbm_bytes_per_s"],
        moe_flops.expert_matmul_flops(cfg, rows)
        / peaks["bf16_flops_per_s"])
    return 100.0 * least / kernel_s
