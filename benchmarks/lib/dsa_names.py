"""The readers of the ``dsa_*`` metrics: a model with a learned
sparse-attention indexer, by the program's own scopes, spans and one
kernel's name.

The time shares are the own device seconds of the ops the program traced
under its scopes (``indexer``: the index projections and scores;
``index_select``: the exact top-k, a mask; ``sparse_attention``: what is laid
out of the mask for the kernel, with the kernel that attends through it
under its own ``decode_attention``) over the device seconds of the
``jit_decode_k`` or ``jit_prefill`` runs, through ``scope_names.split``.
What a step had to do comes from the program's spans (``serve.chunk``:
``kv_positions_present`` / ``kv_positions_attended``;
``serve.prefill_group``) and the generator's log.  One kernel is read by
name: the masked flash forward of the prefill,
``%sparse_prefill_attention`` (``ops/flash_attention.py`` with ``keep``),
whose result carries the bucket.  Its experts are ``lib/moe_names.py``'s,
its step's floor ``lib/dsa_flops.py``'s.

A configuration whose program is given no ``index_topk`` is not looked at;
a program without such scopes, span attributes or kernel (the commit before
the model) matches nothing, and the readers return None.
"""

from __future__ import annotations

import re
from typing import Optional

from . import (dsa_flops, program_spans, readers, scope_names, ssm_names,
               swa_names)

SELECTION_SCOPES = ("indexer", "index_select")
ATTENTION_SCOPES = ("sparse_attention", "decode_attention")
PREFILL_ATTENTION_KERNEL = re.compile(
    r"^%sparse_prefill_attention(\.\w+)* = \(?\w+\[\d+,\d+,(\d+),\d+\]")


def _dsa(obs) -> bool:
    """The program is given an ``index_topk``: under ``program_fields``,
    which is how every configuration here spells it, or as a published
    key of that name."""
    config = obs["cell"].config
    return bool(config.get("program_fields", {}).get("index_topk")
                or config.get("index_topk"))


def scope_time_share(*scopes: str, which: str = "decode"):
    """``scope_names.scopes_time_share`` for a configuration that
    selects."""
    return scope_names.scopes_time_share(*scopes, which=which, applies=_dsa)


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
