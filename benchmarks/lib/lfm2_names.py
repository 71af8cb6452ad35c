"""The readers of the six ``lfm2_*`` metrics: a short-convolution + expert
model's decode step by the program's own scopes, and its two rooflines.

Nothing here is found by an op's shape: the time shares are the own
device seconds of the ops the program traced under its scopes
(``conv_proj`` / ``short_conv`` / ``conv_out`` of
``ray_tpu/models/shortconv.py``; ``attention``, which at head 64 is XLA's
``llama._cache_attend`` path with its staging copies; ``router`` /
``expert_dispatch`` / ``expert_ffn`` of ``models/moe.py``) over the device
seconds of the ``jit_decode_k`` runs, through ``scope_names.split``.  The
one kernel-level share reads the grouped matmuls by the name XLA gives
them (``%ragged-dot-none*``: ``lib/moe_names.py``).  What a step had to do
comes from the program's spans (``serve.chunk``: ``expert_rows``,
``experts_touched``, ``state_rows_updated``) and the generator's log.

A configuration without a ``conv`` in its ``layer_types`` is not looked
at; a program without such scopes or span attributes (the commit before
the model) matches nothing, and the readers return None.
"""

from __future__ import annotations

import re
import statistics
from typing import Optional, Tuple

from . import (lfm2_flops, moe_names, program_spans, readers, scope_names,
               ssm_names, swa_names)

CONV_SCOPES = ("conv_proj", "short_conv", "conv_out")
ROUTING_SCOPES = ("router", "expert_dispatch")


def _lfm2(obs) -> bool:
    return "conv" in obs["cell"].config.get("layer_types", ())


def chunk_medians(obs) -> Optional[Tuple[float, float, float]]:
    """Medians over the window's ``serve.chunk`` spans of (expert rows a
    step, (layer, expert) pairs touched a step, slots whose conv states a
    step advanced): the program's own count of what a step had to do."""
    got = program_spans.collect(obs) if _lfm2(obs) else None
    chunks = [c for c in (got.chunks if got else [])
              if c.get("expert_rows") and c.get("state_rows_updated")]
    if not chunks:
        return None
    return tuple(statistics.median(c[key] / c["k"] for c in chunks)
                 for key in ("expert_rows", "experts_touched",
                             "state_rows_updated"))


def scope_time_share(*scopes: str):
    """Own device seconds of the ops under ``scopes`` / device seconds of
    the decode programs, in %; None where the program's map knows no such
    scope."""
    def read(obs) -> Optional[float]:
        got = scope_names.split(obs, "decode") if _lfm2(obs) else None
        if not got:
            return None
        seconds = sum(s for (name, _phase), s in got.by.items()
                      if name in scopes)
        return 100.0 * seconds / got.module_s if seconds else None
    return read


def decode_step_roofline(obs) -> Optional[float]:
    """Least time of one decode step (every non-expert matmul weight once,
    three matrices of each (layer, expert) touched, the K/V in flight as
    far as each row is long, the conv states of the slots advanced read
    and written once: HBM bytes or the step's FLOPs at peak, the larger) /
    the median ``jit_decode_k`` step."""
    step_ms = readers.decode_step_device_ms(obs)
    if step_ms is None or not _lfm2(obs):
        return None
    lengths, medians = swa_names._traced_lengths(obs), chunk_medians(obs)
    if lengths is None or medians is None:
        return None
    rows, touched, advanced = medians
    cfg, peaks = obs["cell"].config, obs["peaks"]
    least = max(
        lfm2_flops.decode_step_bytes(cfg, touched, lengths, advanced)
        / peaks["hbm_bytes_per_s"],
        lfm2_flops.decode_step_flops(cfg, lengths, rows)
        / peaks["bf16_flops_per_s"])
    return 100.0 * least / (step_ms * 1e-3)


def expert_matmul_roofline(obs) -> Optional[float]:
    """Least time of a step's grouped matmuls (the touched experts'
    matrices and the rows' activations: HBM bytes or FLOPs at peak) / the
    ``%ragged-dot-none*`` kernels' measured time a step."""
    medians = chunk_medians(obs)
    step_ms = readers.decode_step_device_ms(obs)
    trace = obs.get("trace")
    if medians is None or step_ms is None or not trace or not trace.devices:
        return None
    kernel = re.compile(moe_names.GROUPED_MATMUL_OP)
    matmul_s = sum(end - start for start, end, name in
                   ssm_names._leaves_inside(trace, readers.DECODE_MODULE)
                   if kernel.search(name))
    runs = trace.module_runs(readers.DECODE_MODULE)
    if not matmul_s or not runs:
        return None
    # the kernels' share of the decode programs' time x the median step:
    # a program cut by the trace's edge miscounts neither
    kernel_s = matmul_s / sum(e - s for s, e, _ in runs) * step_ms * 1e-3
    rows, touched, _advanced = medians
    cfg, peaks = obs["cell"].config, obs["peaks"]
    least = max(
        lfm2_flops.expert_matmul_bytes(cfg, touched, rows)
        / peaks["hbm_bytes_per_s"],
        lfm2_flops.expert_matmul_flops(cfg, rows)
        / peaks["bf16_flops_per_s"])
    return 100.0 * least / kernel_s
