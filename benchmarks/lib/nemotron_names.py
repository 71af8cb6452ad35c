"""The readers of the ``latent_moe_*`` and ``nemotron_*`` metrics: a
Nemotron-H stack's own work in a device trace, told by the SCOPE the program
traced it under (``ray_tpu/observability/device.py`` ``SCOPES``;
``lib/scope_names.py`` joins the compiled instructions' scopes to the
trace's events) and, for the grouped matmuls, by the compiler's own name:

- ``latent_proj``: the two projections around an expert block's dispatch
  (4,096 -> 1,024 before the sort, 1,024 -> 4,096 after the combine);
- ``shared_expert``: the full-width two-matrix expert every token passes;
- ``expert_ffn``: the routed experts' grouped matmuls
  (``%ragged-dot-none*``, two an expert block: an expert has no gate) and
  the squared ReLU between them;
- ``ssm_state_update``: a decode step's update of every advancing slot's
  recurrent state (``ops/ssm_state_update.py``, the grouped kernel);
- ``ssm_scan``: the chunked scan of a prefill;
- ``decode_attention``: the ONE attention block's read of its pool, 2 K/V
  heads stored as rows, sixteen queries a head.

What a step had to do comes from the program's spans (``serve.chunk``:
``expert_rows``, ``experts_touched``, ``expert_rows_max``,
``state_rows_updated``); what an expert and a state are from the
configuration (``lib/nemotron_flops.py``).  ``lib/moe_names.py``'s two
counted readers are not joined: its expert is three full-width matrices and
its expert layers are every layer (``moe_flops.expert_params`` /
``expert_layers``).  A program without these scopes or attributes (another
configuration, an older commit) matches nothing and the readers return
None.
"""

from __future__ import annotations

import re
import statistics
from typing import Optional, Tuple

from . import (moe_names, nemotron_flops, program_spans, readers,
               scope_names, ssm_names)


def _applies(obs) -> bool:
    return "hybrid_override_pattern" in obs["cell"].config


def expert_load_a_step(obs) -> Optional[Tuple[float, float, float]]:
    """Medians over the window's ``serve.chunk`` spans of (expert rows a
    step, (block, expert) pairs touched a step, busiest expert's rows /
    mean rows per expert the chip holds over its E blocks).  None where the
    spans carry no expert load."""
    got = program_spans.collect(obs) if _applies(obs) else None
    chunks = [c for c in (got.chunks if got else [])
              if c.get("expert_rows")]
    if not chunks:
        return None
    cfg = obs["cell"].config
    pairs = nemotron_flops.block_counts(cfg)["E"] * cfg["n_routed_experts"]
    return (statistics.median(c["expert_rows"] / c["k"] for c in chunks),
            statistics.median(c["experts_touched"] / c["k"]
                              for c in chunks),
            statistics.median(c["expert_rows_max"]
                              / (c["expert_rows"] / pairs)
                              for c in chunks))


def _scope_seconds(obs, which: str, scope: str):
    """(own device seconds of ``scope``, device seconds of the module's
    runs) or None where the module did not run or keeps no map."""
    got = scope_names.split(obs, which) if _applies(obs) else None
    if not got or not got.module_s:
        return None
    return (sum(s for (name, _phase), s in got.by.items() if name == scope),
            got.module_s)


# --------------------------------------------------------------- readers
projection_time_share = scope_names.scopes_time_share(
    "latent_proj", applies=_applies)
shared_expert_time_share = scope_names.scopes_time_share(
    "shared_expert", applies=_applies)
state_update_time_share = scope_names.scopes_time_share(
    "ssm_state_update", applies=_applies)
prefill_scan_time_share = scope_names.scopes_time_share(
    "ssm_scan", which="prefill", applies=_applies)
decode_attention_time_share = scope_names.scopes_time_share(
    "decode_attention", "attention", applies=_applies)


def load_imbalance(obs) -> Optional[float]:
    load = expert_load_a_step(obs)
    return None if load is None else load[2]


def expert_matmul_roofline(obs) -> Optional[float]:
    """Least time of a step's grouped matmuls (the touched experts' two
    matrices in the latent and the rows' activations: HBM bytes or FLOPs at
    peak) / the ``%ragged-dot-none*`` kernels' measured time a step."""
    step_ms, load = readers.decode_step_device_ms(obs), \
        expert_load_a_step(obs)
    if step_ms is None or load is None:
        return None
    trace = obs["trace"]
    kernel = re.compile(moe_names.GROUPED_MATMUL_OP)
    matmul_s = sum(end - start for start, end, name in
                   ssm_names._leaves_inside(trace, readers.DECODE_MODULE)
                   if kernel.search(name))
    if not matmul_s:
        return None
    # the kernels' share of the decode programs' time x the median whole
    # launch's step: a program cut by the trace's edge miscounts neither
    runs = trace.module_runs(readers.DECODE_MODULE)
    kernel_s = matmul_s / sum(e - s for s, e, _ in runs) * step_ms * 1e-3
    rows, touched, _imbalance = load
    cfg, peaks = obs["cell"].config, obs["peaks"]
    least = max(
        nemotron_flops.expert_matmul_bytes(cfg, touched, rows)
        / peaks["hbm_bytes_per_s"],
        nemotron_flops.expert_matmul_flops(cfg, rows)
        / peaks["bf16_flops_per_s"])
    return 100.0 * least / kernel_s


def state_update_roofline(obs) -> Optional[float]:
    """Least time of a step's recurrent-state update (each advanced slot's
    state once in and once out a Mamba block: HBM bytes or FLOPs at peak)
    / the measured time a step of the ops under ``ssm_state_update``."""
    found = _scope_seconds(obs, "decode", "ssm_state_update")
    rows, step_ms = ssm_names.rows_a_step(obs), \
        readers.decode_step_device_ms(obs)
    if not found or not found[0] or rows is None or step_ms is None:
        return None
    update_s = found[0] / found[1] * step_ms * 1e-3
    cfg, peaks = obs["cell"].config, obs["peaks"]
    least = max(
        nemotron_flops.state_update_bytes(cfg, rows)
        / peaks["hbm_bytes_per_s"],
        nemotron_flops.state_update_flops(cfg, rows)
        / peaks["bf16_flops_per_s"])
    return 100.0 * least / update_s
