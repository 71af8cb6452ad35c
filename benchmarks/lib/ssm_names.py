"""Tell a state-space layer's work apart in a device trace, and the
readers of the three ``ssm_*`` metrics (its step's floor is
``lib/ssm_flops.py``'s).

An event's name in a v5e trace is the instruction's whole text, result
and operands with their shapes (``lib/moe_names.py`` is the precedent):

- the decode step's recurrent-state update is ONE Pallas kernel a Mamba
  layer, ``pallas_call(name="ssm_state_update")`` in
  ``ray_tpu/ops/ssm_state_update.py``: its events read
  ``%ssm_state_update.100 = (bf16[36,120,128,4096], f32[120,1,4096])
  custom-call(...)`` (AOT, PR 30).  Where the program updates the state
  in XLA instead (a state Mosaic cannot tile), the ops are fusions whose
  names say nothing but whose ARRAYS do: inside a decode program only the
  update handles an array of ``[slots, state, heads x head_dim]``;
- inside a prefill program only the chunked scan
  (``ray_tpu/models/mamba2.py``, plain ``jax.numpy``) handles a
  ``[rows, chunks, heads, Q, Q]`` array (the per-head decay and mixing
  matrices of a chunk of Q positions), a ``[..., heads, head_dim,
  state]`` one (the chunk states) or a ``[..., state, heads x head_dim]``
  one (the rows' final states, scattered into the cache).

Where the configuration's file says that its program traces the two under
scopes of their own (``ssm_shape`` with ``"ops": "scopes"``,
``lib/ssm_flops.py``), they are the own seconds of the ops under
``ssm_state_update`` in the decode programs and under ``ssm_scan`` in the
prefill programs (``scope_names.split``), and no array's shape is looked at:
a mixer with groups, or one whose states lie otherwise, joins with that line
in its file.

A program without such layers matches nothing here, a configuration
without ``layer_types`` or ``ssm_shape`` is not looked at, and the readers
return None.
"""

from __future__ import annotations

import re
import statistics
from typing import List, Optional

from . import readers, scope_names, ssm_flops, trace_reduce


STATE_UPDATE_KERNEL = r"^%ssm_state_update(\.\w+)* = "


def state_update_op(slots: int, heads: int, head_dim: int,
                    state: int) -> str:
    return (STATE_UPDATE_KERNEL
            + rf"|\[(\d+,)?{slots},{state},{heads * head_dim}\]")


def prefill_scan_op(heads: int, head_dim: int, state: int) -> str:
    return (rf"\[(\d+,)*{heads},(\d+),\2\]"
            rf"|\[(\d+,)*{heads},{head_dim},{state}\]"
            rf"|\[(\d+,)*{state},{heads * head_dim}\]")


def _leaves_inside(trace, module: str) -> List[trace_reduce.Event]:
    """Leaf ops (no op nested inside) that ran inside a module."""
    runs = trace.module_runs(module)
    inside, i = [], 0
    for ev in trace_reduce._leaves(trace.devices[0].ops) if runs else []:
        while i < len(runs) and runs[i][1] <= ev[0]:
            i += 1
        if i < len(runs) and runs[i][0] <= ev[0]:
            inside.append(ev)
    return inside


def _seconds(obs, key: str, module: str, pattern) -> Optional[tuple]:
    """(seconds of the module's leaf ops that match ``pattern(heads,
    head_dim, state)``, seconds of the module), cached on the
    observations; None where there is nothing to read."""
    trace = obs.get("trace")
    shape = ssm_flops.shape(obs["cell"].config)
    if not trace or not trace.devices or shape is None:
        return None
    if key not in obs:
        op = re.compile(pattern(shape.heads, shape.head_dim, shape.state))
        matched = sum(end - start
                      for start, end, name in _leaves_inside(trace, module)
                      if op.search(name))
        total = sum(e - s for s, e, _ in trace.module_runs(module))
        obs[key] = (matched, total) if matched and total else None
    return obs[key]


def _by_scopes(obs) -> bool:
    stated = obs["cell"].config.get("ssm_shape") or {}
    return stated.get("ops") == "scopes"


def _update_seconds(obs):
    if _by_scopes(obs):
        return scope_names.scope_seconds(obs, "decode", "ssm_state_update")
    slots = obs["cell"].workload["engine"]["max_slots"]
    return _seconds(obs, "ssm_update_s", readers.DECODE_MODULE,
                    lambda *sizes: state_update_op(slots, *sizes))


def _scan_seconds(obs):
    if _by_scopes(obs):
        return scope_names.scope_seconds(obs, "prefill", "ssm_scan")
    return _seconds(obs, "ssm_scan_s", readers.PREFILL_MODULE,
                    prefill_scan_op)


def rows_a_step(obs) -> Optional[float]:
    """Median over the window's ``serve.chunk`` spans of the slots a step
    advanced (``state_rows_updated`` / ``k``): the program's own count.
    None where the spans carry none."""
    from . import program_spans

    got = program_spans.collect(obs)
    chunks = [c for c in (got.chunks if got else [])
              if c.get("state_rows_updated")]
    if not chunks:
        return None
    return statistics.median(c["state_rows_updated"] / c["k"]
                             for c in chunks)


# --------------------------------------------------------------- readers
def state_update_time_share(obs) -> Optional[float]:
    found = _update_seconds(obs)
    return None if found is None else 100.0 * found[0] / found[1]


def prefill_scan_time_share(obs) -> Optional[float]:
    found = _scan_seconds(obs)
    return None if found is None else 100.0 * found[0] / found[1]


def state_update_roofline(obs) -> Optional[float]:
    """Least time of a step's recurrent-state update (each advanced
    slot's state read once and written once, HBM bytes or FLOPs at peak)
    / the measured time of the update's ops a step."""
    found, rows = _update_seconds(obs), rows_a_step(obs)
    step_ms = readers.decode_step_device_ms(obs)
    if found is None or rows is None or step_ms is None:
        return None
    # the ops' share of the decode programs' time x the median step: a
    # program cut by the trace's edge miscounts neither
    update_s = found[0] / found[1] * step_ms * 1e-3
    cfg, peaks = obs["cell"].config, obs["peaks"]
    least = max(
        ssm_flops.state_update_bytes(cfg, rows) / peaks["hbm_bytes_per_s"],
        ssm_flops.state_update_flops(cfg, rows)
        / peaks["bf16_flops_per_s"])
    return 100.0 * least / update_s
