"""The readers of the ``power_*`` metrics: a power-retention layer's own
work in a device trace, told by the SCOPE the program traced it under
(``ray_tpu/observability/device.py`` ``SCOPES``; ``lib/scope_names.py``
joins the compiled instructions' scopes to the trace's events):

- ``power_chunk``: the chunked form of a prefill (the quadratic form
  inside a chunk, ``phi`` of a chunk's queries and keys, the state's read
  and update across chunks);
- ``power_state_update``: a decode step's update and read of every
  advancing slot's states (``ops/power_state_update.py``, or XLA's form by
  shape);
- ``power_gate``: the q/k head norms, RoPE and the gate, in both programs
  (no metric of its own: a row of ``scopes.json``).

The in- and out-projections are ``ssm_proj`` / ``ssm_out``, as every
state-keeping mixer's (``batch.*_projection_time_share``).  What a step had
to move comes from the program's spans (``serve.chunk``:
``power_slots_advanced``; ``serve.prefill_group``:
``power_chunk_positions``), what a state is from the configuration
(``power_flops``, at D = d (d + 1) / 2 whatever the program lays out).  A
program without these scopes or attributes (another configuration, an older
commit) matches nothing and the readers return None.
"""

from __future__ import annotations

import statistics
from typing import Optional

from . import power_flops, program_spans, readers, scope_names


def slots_a_step(obs) -> Optional[float]:
    """Median over the window's ``serve.chunk`` spans of the slots a step
    advanced (``power_slots_advanced`` / ``k``): the program's own count."""
    got = program_spans.collect(obs)
    chunks = [c for c in (got.chunks if got else [])
              if c.get("power_slots_advanced")]
    if not chunks:
        return None
    return statistics.median(c["power_slots_advanced"] / c["k"]
                             for c in chunks)


# --------------------------------------------------------------- readers
prefill_chunk_time_share = scope_names.scopes_time_share(
    "power_chunk", which="prefill")
state_update_time_share = scope_names.scopes_time_share("power_state_update")


def state_update_roofline(obs) -> Optional[float]:
    """Least time of a step's state update (each advanced slot's states
    once in and once out a layer: HBM bytes or FLOPs at peak, whatever
    implements the update) / the measured time a step of the ops under
    ``power_state_update``."""
    found = scope_names.scope_seconds(obs, "decode", "power_state_update")
    rows, step_ms = slots_a_step(obs), readers.decode_step_device_ms(obs)
    if not found or not found[0] or rows is None or step_ms is None:
        return None
    # the ops' share of the decode programs' time x the median step: a
    # program cut by the trace's edge miscounts neither
    update_s = found[0] / found[1] * step_ms * 1e-3
    cfg, peaks = obs["cell"].config, obs["peaks"]
    least = max(
        power_flops.state_update_bytes(cfg, rows) / peaks["hbm_bytes_per_s"],
        power_flops.state_update_flops(cfg, rows)
        / peaks["bf16_flops_per_s"])
    return 100.0 * least / update_s


def prefill_chunk_roofline(obs) -> Optional[float]:
    """Least time of the chunked form over the positions the traced prefill
    programs sent through it (FLOPs or HBM bytes at peak, the larger) / the
    measured time of the ops under ``power_chunk``.  The positions: the
    ``serve.prefill_group`` spans' ``power_chunk_positions``, each by the
    share of its launch -> harvest span that lies inside the traced span
    (a group cut by the trace's edge counts in part on both sides)."""
    found = scope_names.scope_seconds(obs, "prefill", "power_chunk")
    got, span = program_spans.collect(obs), obs.get("trace_span")
    if not found or not found[0] or not got or not span or span[0] is None:
        return None
    # a group by the share of its launch -> harvest span that the trace
    # holds (its program ran somewhere inside that span)
    positions = 0.0
    for g in got.groups:
        start, end = g["t_launch"], g["t_launch"] + g["dur_ms"] * 1e-3
        inside = min(end, span[1]) - max(start, span[0])
        if inside > 0 and end > start:
            positions += g.get("power_chunk_positions", 0) \
                * inside / (end - start)
    if not positions:
        return None
    cfg, peaks = obs["cell"].config, obs["peaks"]
    chunk = cfg["program_fields"]["power_chunk"]
    least = max(
        power_flops.chunk_flops(cfg, positions, chunk)
        / peaks["bf16_flops_per_s"],
        power_flops.chunk_bytes(cfg, positions, chunk)
        / peaks["hbm_bytes_per_s"])
    return 100.0 * least / found[0]


def state_bytes_share(obs) -> Optional[float]:
    """The states' share of a decode step's least bytes at the slots a step
    of the window advanced."""
    rows = slots_a_step(obs)
    if rows is None:
        return None
    return 100.0 * power_flops.state_bytes_share(obs["cell"].config, rows)
