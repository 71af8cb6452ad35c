"""The readers of the ``kda_*`` metrics: a gated delta-rule layer's own
work in a device trace, told by the SCOPE the program traced it under
(``ray_tpu/observability/device.py`` ``SCOPES``; ``lib/scope_names.py``
joins the compiled instructions' scopes to the trace's events):

- ``kda_chunk``: the chunked rule of a prefill (the decayed products, the
  triangular solve, the scan over the chunks that carries the state);
- ``kda_state_update``: a decode step's update of every advancing slot's
  matrix state (``ops/kda_state_update.py``, or XLA's form by shape);
- ``kda_gates``: the q, k and v convolutions and their ``silu``, the l2
  norms, the decay and ``beta``, the head norm and the output gate, in
  both programs.

The in- and out-projections are ``ssm_proj`` / ``ssm_out``, as every
state-keeping mixer's (``batch.*_projection_time_share``).  What a step had
to move comes from the program's spans (``serve.chunk``:
``kda_slots_advanced``), what a state is from the configuration
(``kda_flops.state_update_bytes``).  A program without these scopes or
attributes (another configuration, an older commit) matches nothing and the
readers return None.
"""

from __future__ import annotations

import statistics
from typing import Optional

from . import kda_flops, program_spans, readers, scope_names


def _scope_seconds(obs, which: str, scope: str):
    """(own device seconds of ``scope``, device seconds of the module's
    runs) or None where the module did not run or keeps no map."""
    got = scope_names.split(obs, which)
    if not got or not got.module_s:
        return None
    return (sum(s for (name, _phase), s in got.by.items() if name == scope),
            got.module_s)


def slots_a_step(obs) -> Optional[float]:
    """Median over the window's ``serve.chunk`` spans of the slots a step
    advanced (``kda_slots_advanced`` / ``k``): the program's own count."""
    got = program_spans.collect(obs)
    chunks = [c for c in (got.chunks if got else [])
              if c.get("kda_slots_advanced")]
    if not chunks:
        return None
    return statistics.median(c["kda_slots_advanced"] / c["k"]
                             for c in chunks)


# --------------------------------------------------------------- readers
prefill_chunk_time_share = scope_names.scopes_time_share(
    "kda_chunk", which="prefill")
state_update_time_share = scope_names.scopes_time_share("kda_state_update")


def conv_gate_time_share(obs) -> Optional[float]:
    """Both programs' ``kda_gates`` seconds / both programs' seconds."""
    found = [f for f in (_scope_seconds(obs, which, "kda_gates")
                         for which in ("decode", "prefill")) if f]
    seconds = sum(f[0] for f in found)
    return 100.0 * seconds / sum(f[1] for f in found) if seconds else None


def state_update_roofline(obs) -> Optional[float]:
    """Least time of a step's delta-rule update (each advanced slot's
    state once in and once out a KDA layer: HBM bytes or FLOPs at peak,
    whatever implements the update) / the measured time a step of the ops
    under ``kda_state_update``."""
    found = _scope_seconds(obs, "decode", "kda_state_update")
    rows, step_ms = slots_a_step(obs), readers.decode_step_device_ms(obs)
    if not found or not found[0] or rows is None or step_ms is None:
        return None
    # the ops' share of the decode programs' time x the median step: a
    # program cut by the trace's edge miscounts neither
    update_s = found[0] / found[1] * step_ms * 1e-3
    cfg, peaks = obs["cell"].config, obs["peaks"]
    least = max(
        kda_flops.state_update_bytes(cfg, rows) / peaks["hbm_bytes_per_s"],
        kda_flops.state_update_flops(cfg, rows) / peaks["bf16_flops_per_s"])
    return 100.0 * least / update_s
