"""The readers of the ``dsa_*`` metrics: a model with a learned
sparse-attention indexer, by the program's own scopes, spans and one
kernel's name.

The time shares are the own device seconds of the ops the program traced
under its scopes (``indexer``: the index projections and scores;
``index_select``: the exact top-k, a mask; ``sparse_attention``: what is laid
out of the mask for the kernel, with the kernel that attends through it
under its own ``decode_attention``; ``router`` / ``expert_dispatch`` /
``expert_ffn`` of ``models/moe.py``) over the device seconds of the
``jit_decode_k`` or ``jit_prefill`` runs, through ``scope_names.split``.  What a step had to do comes from the program's
spans (``serve.chunk``: ``kv_positions_present`` / ``kv_positions_attended``,
``expert_rows``, ``experts_touched``; ``serve.prefill_group``) and the
generator's log.  Two kernels are read by name: the masked flash forward
of the prefill, ``%sparse_prefill_attention`` (``ops/flash_attention.py``
with ``keep``), whose result carries the bucket, and the decode step's
grouped matmuls (``%ragged-dot-none*``: ``lib/moe_names.py``).

A configuration without ``sa_config`` is not looked at; a program without
such scopes, span attributes or kernel (the commit before the model)
matches nothing, and the readers return None.
"""

from __future__ import annotations

import re
from typing import Optional

from . import (dsa_flops, moe_names, program_spans, readers, scope_names,
               ssm_names, swa_names)

SELECTION_SCOPES = ("indexer", "index_select")
ROUTING_SCOPES = ("router", "expert_dispatch")
ATTENTION_SCOPES = ("sparse_attention", "decode_attention")
PREFILL_ATTENTION_KERNEL = re.compile(
    r"^%sparse_prefill_attention(\.\w+)* = \(?\w+\[\d+,\d+,(\d+),\d+\]")


def _dsa(obs) -> bool:
    return "sa_config" in obs["cell"].config


def scope_time_share(*scopes: str, which: str = "decode"):
    """Own device seconds of the ops under ``scopes`` / device seconds of
    the decode (or prefill) programs, in %; None where the program's map
    knows no such scope."""
    def read(obs) -> Optional[float]:
        got = scope_names.split(obs, which) if _dsa(obs) else None
        if not got:
            return None
        seconds = sum(s for (name, _phase), s in got.by.items()
                      if name in scopes)
        return 100.0 * seconds / got.module_s if seconds else None
    return read


def selected_share(obs) -> Optional[float]:
    """serve.chunk: keys the live rows attend (``kv_positions_attended``:
    ``min(length, topk)`` a row, the scheduler's arithmetic over the rows'
    lengths and not a count made on the device) / keys they hold
    (``kv_positions_present``: each also an index key scored), over the
    window's chunks, in %: the traffic's sparsity."""
    got = program_spans.collect(obs) if _dsa(obs) else None
    chunks = [c for c in (got.chunks if got else [])
              if c.get("kv_positions_present")]
    if not chunks:
        return None
    return 100.0 * sum(c["kv_positions_attended"] for c in chunks) \
        / sum(c["kv_positions_present"] for c in chunks)


def decode_step_roofline(obs) -> Optional[float]:
    """Least time of one decode step (every non-expert matmul weight once,
    three matrices of each (layer, expert) touched, every index key a live
    row holds, K and V of the ``min(length, topk)`` rows it attends: HBM
    bytes or the step's FLOPs at peak, the larger) / the median
    ``jit_decode_k`` step."""
    step_ms = readers.decode_step_device_ms(obs)
    if step_ms is None or not _dsa(obs):
        return None
    lengths, medians = swa_names._traced_lengths(obs), \
        moe_names.chunk_medians(obs)
    if lengths is None or medians is None:
        return None
    rows, touched, _imbalance = medians
    cfg, peaks = obs["cell"].config, obs["peaks"]
    least = max(
        dsa_flops.decode_step_bytes(cfg, touched, lengths)
        / peaks["hbm_bytes_per_s"],
        dsa_flops.decode_step_flops(cfg, lengths, rows)
        / peaks["bf16_flops_per_s"])
    return 100.0 * least / (step_ms * 1e-3)


def expert_matmul_roofline(obs) -> Optional[float]:
    """Least time of a step's grouped matmuls (the touched experts'
    matrices at an expert's own width and the rows' activations: HBM bytes
    or FLOPs at peak) / the ``%ragged-dot-none*`` kernels' measured time a
    step."""
    medians = moe_names.chunk_medians(obs) if _dsa(obs) else None
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
    rows, touched, _imbalance = medians
    cfg, peaks = obs["cell"].config, obs["peaks"]
    least = max(
        dsa_flops.expert_matmul_bytes(cfg, touched, rows)
        / peaks["hbm_bytes_per_s"],
        dsa_flops.expert_matmul_flops(cfg, rows)
        / peaks["bf16_flops_per_s"])
    return 100.0 * least / kernel_s


def sparse_prefill_attention_roofline(obs) -> Optional[float]:
    """FLOPs inside the selection of the prompts prefilled (a traced
    kernel call counts as a layer's share of the mean prompt of its
    bucket's groups: query t attends ``min(t + 1, topk)`` keys) at the bf16
    peak / the measured time of the kernel's calls, which compute the
    causal tiles dense and masked."""
    trace = obs.get("trace")
    if not trace or not trace.devices or not _dsa(obs):
        return None
    hits = [(end - start, PREFILL_ATTENTION_KERNEL.search(name))
            for start, end, name in ssm_names._leaves_inside(
                trace, readers.PREFILL_MODULE)]
    hits = [(s, m) for s, m in hits if m]
    prompts = swa_names._mean_prompt_by_bucket(obs)
    if not hits:
        return None
    cfg = obs["cell"].config
    flops = 0.0
    for _seconds, match in hits:
        bucket = int(match.group(2))
        if bucket not in prompts:
            return None
        flops += dsa_flops.prefill_attention_flops(cfg, prompts[bucket]) \
            / cfg["num_hidden_layers"]
    return 100.0 * flops / obs["peaks"]["bf16_flops_per_s"] \
        / sum(s for s, _ in hits)
