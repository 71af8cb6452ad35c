"""An expert layer's work in a device trace, and the readers of the
``moe_*`` metrics (a served configuration's) and the ``train_expert_*`` /
``train_routing_*`` ones (a trained one's) -- every configuration's with
experts, whatever its family.

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
``expert_layers`` / ``experts_held``, and what its file states under
``expert_shape``: an expert of two matrices, rows of a latent's width,
expert layers that are not all but the leading ones).  A program without
experts traces no such scope, kernel or span attribute, and the readers
return None.

A TRAIN step traces the same scopes and kernels inside ``jit_step``
(forward, recomputed forward and backward); what its grouped matmuls had to
do is the step's own ``expert_rows`` metric, which ``kinds/train_lm.py``
hands over as ``obs["expert_rows"]`` (expert layers x experts the router
scores, fetched after the window has closed).
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


def _grouped_matmul_seconds(trace, module: str) -> float:
    kernel = re.compile(GROUPED_MATMUL_OP)
    return sum(end - start for start, end, name in
               ssm_names._leaves_inside(trace, module)
               if kernel.search(name))


# --------------------------------------------------------------- readers
expert_ffn_time_share = scope_names.scopes_time_share(*EXPERT_SCOPES)
routing_time_share = scope_names.scopes_time_share(*ROUTING_SCOPES)
shared_expert_time_share = scope_names.scopes_time_share("shared_expert")
train_expert_ffn_time_share = scope_names.scopes_time_share(
    *EXPERT_SCOPES, which="train")
train_routing_time_share = scope_names.scopes_time_share(
    *ROUTING_SCOPES, which="train")


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
    matmul_s = _grouped_matmul_seconds(trace, readers.DECODE_MODULE)
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


def held_rows_a_step(obs) -> Optional[float]:
    """Rows the held experts computed a train step, all expert layers
    together: the step's own ``expert_rows`` metric ((expert layers,
    experts the router scores), every expert's choices) over the range the
    program holds (``program_fields.moe_held``; all where it holds all).
    None where the run hands none over.  The EXPECTED rows will not do:
    random weights route a chip's share of the rows +-45% off it from seed
    to seed (PERF.md section 6, PR 57)."""
    rows = obs.get("expert_rows")
    if rows is None:
        return None
    held = obs["cell"].config["program_fields"].get("moe_held")
    first, end = held if held else (0, len(rows[0]))
    return float(sum(sum(layer[first:end]) for layer in rows))


def train_expert_matmul_roofline(obs) -> Optional[float]:
    """Least time of one train step's grouped matmuls, forward and
    backward, over the rows the held experts computed
    (``held_rows_a_step``; ``moe_flops.expert_matmul_train_flops`` /
    ``_bytes`` at the chip's peaks, the larger) / the
    ``%ragged-dot-none*`` kernels' measured seconds a step -- the
    recomputed forward's among them, which the least time does not count
    -- in %."""
    trace, rows = obs.get("trace"), held_rows_a_step(obs)
    if not trace or not trace.devices or rows is None:
        return None
    kernel_s = readers.kernel_s_a_step(obs, _grouped_matmul_seconds(
        trace, readers.TRAIN_STEP_MODULE))
    if kernel_s is None:
        return None
    cfg, peaks = obs["cell"].config, obs["peaks"]
    least = max(
        moe_flops.expert_matmul_train_flops(cfg, rows)
        / peaks["bf16_flops_per_s"],
        moe_flops.expert_matmul_train_bytes(cfg, rows)
        / peaks["hbm_bytes_per_s"])
    return 100.0 * least / kernel_s
