"""Tell a latent-attention model's work apart in a device trace, and the
readers of the seven ``mla_*`` metrics (its step's floor is
``lib/mla_flops.py``'s).

An event's name in a v5e trace is the instruction's whole text
(``lib/moe_names.py`` is the precedent), and a Pallas kernel's
instruction is named after its ``pallas_call(name=...)``:

- the decode step attends through ONE kernel a layer,
  ``mla_decode_attention`` (``ray_tpu/ops/mla_decode_attention.py``):
  ``%mla_decode_attention.3 = bf16[32,128,512] custom-call(...)``;
- a prefill past ``llama.FLASH_PREFILL_FROM`` positions attends, expanded,
  through ``flash_prefill_attention`` a GROUP of heads a call, whose
  result carries the heads and the bucket:
  ``%flash_prefill_attention.3 = bf16[1,32,8192,128] custom-call(...)``
  (no tuple: the latent model's call writes no softmax statistics).

The up-projections of the absorbed decode and the shared expert are
ordinary matmuls whose names say nothing: they are read by the program's
own scopes (``mla_absorb``, ``shared_expert``) through
``scope_names.split``.  What the kernels had to do comes from the
program's spans (``serve.chunk``: expert rows held and elsewhere, experts
touched; ``serve.prefill_group``: bucket and prompt tokens) and the
generator's log.  A configuration without ``kv_lora_rank`` is not looked
at; a program without such kernels, scopes or span attributes (the
commit before the model) matches nothing, and the readers return None.
"""

from __future__ import annotations

import re
import statistics
from typing import List, Optional, Tuple

from . import (mla_flops, program_spans, readers, scope_names, ssm_names,
               swa_names)

DECODE_ATTENTION_KERNEL = re.compile(r"^%mla_decode_attention(\.\w+)* = ")
PREFILL_ATTENTION_KERNEL = re.compile(
    r"^%flash_prefill_attention(\.\w+)* = \(?\w+\[\d+,(\d+),(\d+),\d+\]")


def _latent(obs) -> bool:
    return bool(obs["cell"].config.get("kv_lora_rank"))


def _kernel_seconds(obs, key: str, module: str, kernel
                    ) -> Optional[Tuple[float, float, List]]:
    """(seconds of the module's leaf ops whose name matches ``kernel``,
    seconds of the module, the matches), cached on the observations."""
    trace = obs.get("trace")
    if not trace or not trace.devices or not _latent(obs):
        return None
    if key not in obs:
        hits = [(end - start, kernel.search(name))
                for start, end, name in ssm_names._leaves_inside(
                    trace, module)]
        hits = [(s, m) for s, m in hits if m]
        total = sum(e - s for s, e, _ in trace.module_runs(module))
        obs[key] = (sum(s for s, _ in hits), total, hits) \
            if hits and total else None
    return obs[key]


def _decode_kernel(obs):
    return _kernel_seconds(obs, "mla_decode_attention_s",
                           readers.DECODE_MODULE, DECODE_ATTENTION_KERNEL)


def _prefill_kernel(obs):
    return _kernel_seconds(obs, "mla_prefill_attention_s",
                           readers.PREFILL_MODULE, PREFILL_ATTENTION_KERNEL)


def chunk_medians(obs) -> Optional[Tuple[float, float]]:
    """Medians over the window's ``serve.chunk`` spans of (held experts'
    rows a step, (layer, held expert) pairs touched a step): the
    program's own count of what its grouped matmuls had to do."""
    got = program_spans.collect(obs) if _latent(obs) else None
    chunks = [c for c in (got.chunks if got else [])
              if c.get("expert_rows") and "expert_rows_elsewhere" in c]
    if not chunks:
        return None
    return (statistics.median(c["expert_rows"] / c["k"] for c in chunks),
            statistics.median(c["experts_touched"] / c["k"]
                              for c in chunks))


# --------------------------------------------------------------- readers
def decode_attention_time_share(obs) -> Optional[float]:
    found = _decode_kernel(obs)
    return None if found is None else 100.0 * found[0] / found[1]


def decode_attention_bound(obs) -> Optional[str]:
    """Which floor is the larger for this chip: ``flops`` or ``bytes``
    (the kernel sits on the ridge: 242 FLOP a byte against the v5e's
    240)."""
    cfg, peaks = obs["cell"].config, obs["peaks"]
    flops = mla_flops.decode_attention_flops_per_position(cfg) \
        / peaks["bf16_flops_per_s"]
    nbytes = mla_flops.latent_bytes_per_position(cfg) \
        / peaks["hbm_bytes_per_s"]
    return "flops" if flops >= nbytes else "bytes"


def decode_attention_roofline(obs) -> Optional[float]:
    """Least time of a step's attention (every latent row a live slot
    attends, 1,152 bytes once and 278,528 FLOPs a layer: the LARGER of the
    two floors at peak; ``obs["mla_decode_attention_bound"]`` says which)
    / the measured time of the kernel a step."""
    found = _decode_kernel(obs)
    step_ms = readers.decode_step_device_ms(obs)
    lengths = swa_names._traced_lengths(obs) if found else None
    if found is None or step_ms is None or lengths is None:
        return None
    kernel_s = found[0] / found[1] * step_ms * 1e-3
    cfg, peaks = obs["cell"].config, obs["peaks"]
    obs["mla_decode_attention_bound"] = decode_attention_bound(obs)
    least = max(
        mla_flops.decode_attention_bytes(cfg, lengths)
        / peaks["hbm_bytes_per_s"],
        mla_flops.decode_attention_flops(cfg, lengths)
        / peaks["bf16_flops_per_s"])
    return 100.0 * least / kernel_s


def prefill_attention_time_share(obs) -> Optional[float]:
    found = _prefill_kernel(obs)
    return None if found is None else 100.0 * found[0] / found[1]


def prefill_attention_roofline(obs) -> Optional[float]:
    """FLOPs inside the causal mask at the TRUE widths (q/k 192, v 128)
    of the prompts prefilled (a traced kernel call counts as its heads'
    share of one layer's attention over the mean prompt of its bucket's
    groups) at the bf16 peak / the measured time of the kernel's calls.
    What Mosaic pads (192 to 256 lanes) and what a tile computes above
    the diagonal show as lost share."""
    found = _prefill_kernel(obs)
    if found is None:
        return None
    prompts = swa_names._mean_prompt_by_bucket(obs)
    cfg = obs["cell"].config
    flops = 0.0
    for _seconds, match in found[2]:
        heads, bucket = int(match.group(2)), int(match.group(3))
        if bucket not in prompts:
            return None
        flops += mla_flops.prefill_attention_flops(cfg, prompts[bucket],
                                                   heads)
    return 100.0 * flops / obs["peaks"]["bf16_flops_per_s"] / found[0]


def scope_time_share(scope: str, which: str = "decode"):
    """``scope_names.scopes_time_share`` for a configuration with latent
    attention."""
    return scope_names.scopes_time_share(scope, which=which,
                                         applies=_latent)


def held_rows_share(obs) -> Optional[float]:
    """serve.chunk: (token, expert) assignments that landed on the held
    experts / all the router made, over the window's chunks, in %."""
    got = program_spans.collect(obs) if _latent(obs) else None
    chunks = [c for c in (got.chunks if got else [])
              if "expert_rows_elsewhere" in c]
    total = sum(c["expert_rows"] + c["expert_rows_elsewhere"]
                for c in chunks)
    if not total:
        return None
    return 100.0 * sum(c["expert_rows"] for c in chunks) / total
