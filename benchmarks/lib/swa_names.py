"""Tell the attention of a model with window layers apart in a device
trace, and the readers of the five ``swa_*`` metrics (its step's floor is
``lib/swa_flops.py``'s).

An event's name in a v5e trace is the instruction's whole text
(``lib/moe_names.py`` is the precedent), and a Pallas kernel's
instruction is named after its ``pallas_call(name=...)``:

- the decode step attends through ONE kernel a layer, full pool or ring,
  ``decode_attention`` (``ray_tpu/ops/decode_attention.py``):
  ``%decode_attention.7 = bf16[32,32,128] custom-call(...)``;
- a prefill past ``llama.FLASH_PREFILL_FROM`` positions attends through
  ``flash_prefill_attention`` (``ray_tpu/ops/flash_attention.py``), one
  call a layer, whose result carries the bucket:
  ``%flash_prefill_attention.3 = (bf16[1,28,8192,128], f32[1,28,8192,1])
  custom-call(...)``.

What the kernels had to do comes from the program's spans (``serve.chunk``:
the keys the live rows hold by pool; ``serve.prefill_group``: bucket and
prompt tokens) and the generator's log.  A configuration without
``sliding_window_layout`` is not looked at, a program without such spans
or kernels matches nothing, and the readers return None.
"""

from __future__ import annotations

import re
import statistics
from typing import Dict, List, Optional, Tuple

from . import program_spans, readers, ssm_names, swa_flops

DECODE_ATTENTION_KERNEL = re.compile(r"^%decode_attention(\.\w+)* = ")
PREFILL_ATTENTION_KERNEL = re.compile(
    r"^%flash_prefill_attention(\.\w+)* = \(\w+\[\d+,\d+,(\d+),\d+\]")


def _windowed(obs) -> bool:
    return "sliding_window_layout" in obs["cell"].config


def _kernel_seconds(obs, key: str, module: str, kernel
                    ) -> Optional[Tuple[float, float, List]]:
    """(seconds of the module's leaf ops whose name matches ``kernel``,
    seconds of the module, the matches), cached on the observations."""
    trace = obs.get("trace")
    if not trace or not trace.devices or not _windowed(obs):
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


def lengths_in_flight(obs, t: float) -> List[float]:
    """Positions each sequence decoding at time ``t`` holds, from the
    generator's own log (``readers.context_in_flight``, a row at a
    time)."""
    out = []
    for r in obs["log"].records:
        if not (r.ok and r.got_tokens >= 2):
            continue
        first = r.sent + r.ttft_ms * 1e-3
        if first <= t < r.done:
            out.append(r.prompt_tokens + 1
                       + (t - first) / (r.done - first) * (r.got_tokens - 1))
    return out


def _traced_lengths(obs) -> Optional[List[float]]:
    span = obs.get("trace_span")
    if not span or span[0] is None:
        return None
    return lengths_in_flight(obs, (span[0] + span[1]) / 2) or None


# --------------------------------------------------------------- readers
def decode_attention_time_share(obs) -> Optional[float]:
    found = _kernel_seconds(obs, "swa_decode_attention_s",
                            readers.DECODE_MODULE, DECODE_ATTENTION_KERNEL)
    return None if found is None else 100.0 * found[0] / found[1]


def decode_attention_roofline(obs) -> Optional[float]:
    """Least time of a step's attention (every key a live row attends, K
    and V once, a ring layer's at ``min(length, window)``: HBM bytes or
    FLOPs at peak) / the measured time of the kernel a step."""
    found = _kernel_seconds(obs, "swa_decode_attention_s",
                            readers.DECODE_MODULE, DECODE_ATTENTION_KERNEL)
    step_ms = readers.decode_step_device_ms(obs)
    lengths = _traced_lengths(obs) if found else None
    if found is None or step_ms is None or lengths is None:
        return None
    kernel_s = found[0] / found[1] * step_ms * 1e-3
    cfg, peaks = obs["cell"].config, obs["peaks"]
    least = max(
        swa_flops.decode_attention_bytes(cfg, lengths)
        / peaks["hbm_bytes_per_s"],
        swa_flops.decode_attention_flops(cfg, lengths)
        / peaks["bf16_flops_per_s"])
    return 100.0 * least / kernel_s


def prefill_attention_time_share(obs) -> Optional[float]:
    found = _kernel_seconds(obs, "swa_prefill_attention_s",
                            readers.PREFILL_MODULE, PREFILL_ATTENTION_KERNEL)
    return None if found is None else 100.0 * found[0] / found[1]


def _mean_prompt_by_bucket(obs) -> Dict[int, float]:
    """Mean prompt tokens a row of the window's prefill groups, by
    bucket."""
    got = program_spans.collect(obs)
    by_bucket: Dict[int, List[float]] = {}
    for g in (got.groups if got else []):
        if g.get("rows"):
            by_bucket.setdefault(int(g["bucket"]), []).append(
                g["prompt_tokens"] / g["rows"])
    return {b: statistics.fmean(v) for b, v in by_bucket.items()}


def prefill_attention_roofline(obs) -> Optional[float]:
    """FLOPs inside the causal mask and the window layers' bands of the
    prompts prefilled (a traced kernel call counts as a layer's share of
    the mean prompt of its bucket's groups; a band of the mean is no more
    than the mean of the bands) at the bf16 peak / the measured time of
    the kernel's calls."""
    found = _kernel_seconds(obs, "swa_prefill_attention_s",
                            readers.PREFILL_MODULE, PREFILL_ATTENTION_KERNEL)
    if found is None:
        return None
    prompts = _mean_prompt_by_bucket(obs)
    cfg = obs["cell"].config
    layers = cfg["num_hidden_layers"]
    flops = 0.0
    for _seconds, match in found[2]:
        bucket = int(match.group(2))
        if bucket not in prompts:
            return None
        flops += swa_flops.prefill_attention_flops(cfg, prompts[bucket]) \
            / layers
    return 100.0 * flops / obs["peaks"]["bf16_flops_per_s"] / found[0]


def window_kv_read_share(obs) -> Optional[float]:
    """serve.chunk: keys a ring layer reads for the live rows
    (``kv_window_positions_attended``) / keys a full layer reads for the
    same rows (``kv_full_positions_attended``), over the window's
    chunks, in %."""
    got = program_spans.collect(obs)
    chunks = [c for c in (got.chunks if got else [])
              if c.get("kv_full_positions_attended")]
    if not chunks:
        return None
    return 100.0 * sum(c["kv_window_positions_attended"] for c in chunks) \
        / sum(c["kv_full_positions_attended"] for c in chunks)
