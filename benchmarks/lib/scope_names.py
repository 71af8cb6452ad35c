"""Device seconds by the program's own scopes, and the readers of the
``*_time_share`` metrics that are defined on them.

A v5e trace names a fused op after the fusion pass (``%fusion.362``); what
the program calls that work -- the ``jax.named_scope`` it was traced
under: ``ffn``, ``qkv_proj``, ``optimizer`` -- reaches only the compiled
instruction's ``op_name`` metadata, which the trace does not carry
(PERF.md section 3).  The program keeps the map itself
(``ray_tpu/observability/device.py``: ``register_program`` at warm-up and
at the first train step, ``program_scopes()`` afterwards): per module
name, instruction -> ``[scope, phase]``, keyed by the instruction's name,
opcode and result shape without layouts (``device.instruction_key``) --
what the compiled text and a trace event's name agree on.

The arithmetic: the own time (``trace_reduce.self_times``: an event's
duration less what is nested in it, so a ``while`` does not count its
body twice) of every ``XLA Ops`` event that starts inside a run of the
module (``jit_step``, ``jit_decode_k``, ``jit_prefill``), joined to the
map, summed by (scope, phase), over the device time of the module's runs
-- the denominator ``flash_names``, ``moe_names``, ``ssm_names`` and
``swa_names`` take, so a scope's share can be laid beside theirs.  An
event the map does not hold counts as ``unscoped``.

A program that keeps no map (an older commit; tracing off, when nothing
is registered) gives None, and the metric is left out.  The map is
fetched once a run, by the first reader that needs it: after the window
and after ``correct``, never on a path that is measured.
"""

from __future__ import annotations

import json
import os
import re
import time
from typing import Dict, List, NamedTuple, Optional, Tuple

from . import readers, trace_reduce

MODULES = {"train": readers.TRAIN_STEP_MODULE,
           "decode": readers.DECODE_MODULE,
           "prefill": readers.PREFILL_MODULE}
UNSCOPED = "unscoped"
# What a metric sums: scopes of the program's vocabulary
# (``device.SCOPES``) by the decision the metric is for.
FAMILIES = {
    "projection": ("qkv_proj", "attn_out", "ssm_proj", "ssm_out"),
    "ffn": ("ffn", "router", "expert_dispatch", "expert_ffn"),
    "head_sample": ("head", "sample"),
    "optimizer": ("optimizer",),
    "head_loss": ("head_loss",),
    "expert_dispatch": ("expert_dispatch",),
    "unscoped": (UNSCOPED,),
}


class Split(NamedTuple):
    by: Dict[Tuple[str, str], float]      # (scope, phase) -> own seconds
    module_s: float                       # device seconds of the runs
    # (event name, scope, phase, own seconds) of every distinct event
    events: List[Tuple[str, str, str, float]]


def events_inside(trace, runs) -> List[trace_reduce.Event]:
    """Chip 0's op events that start inside one of ``runs``."""
    ops = sorted(trace.devices[0].ops)
    inside, i = [], 0
    for ev in ops:
        while i < len(runs) and runs[i][1] <= ev[0]:
            i += 1
        if i < len(runs) and runs[i][0] <= ev[0]:
            inside.append(ev)
    return inside


def split_by_scope(trace, module_pattern: str,
                   scopes: Dict[str, Dict[str, List[str]]]):
    """The ``Split`` of the module's runs; None where the trace holds no
    run of the module or the map no module of that name."""
    from ray_tpu.observability.device import instruction_key   # the map's

    runs = sorted(trace.module_runs(module_pattern)) if trace.devices else []
    table: Dict[str, List[str]] = {}
    for module, rows in scopes.items():
        if re.search(module_pattern, module):
            table.update(rows)
    if not runs or not table:
        return None
    by: Dict[Tuple[str, str], float] = {}
    events = []
    for name, own in trace_reduce.self_times(
            events_inside(trace, runs)).items():
        scope, phase = table.get(instruction_key(name),
                                 (UNSCOPED, "forward"))
        by[scope, phase] = by.get((scope, phase), 0.0) + own
        events.append((name, scope, phase, own))
    return Split(by, sum(e - s for s, e, _ in runs), events)


# --------------------------------------------------------------- readers
def scope_map(obs) -> Optional[Dict[str, Dict[str, List[str]]]]:
    """The program's map, fetched once a run (``program_scopes()`` lowers
    each registered program through the compile cache): what it took and
    the chip's memory around it go to ``obs["scope_map_cost"]``."""
    if "scope_map" in obs:
        return obs["scope_map"]
    obs["scope_map"] = None
    from ray_tpu.observability import device

    fetch = getattr(device, "program_scopes", None)
    if fetch is None:
        return None

    def in_use():
        import jax

        stats = jax.local_devices()[0].memory_stats() or {}
        return int(stats.get("bytes_in_use", 0))

    before, t0 = in_use(), time.perf_counter()
    scopes = fetch()
    obs["scope_map_cost"] = {
        "seconds": time.perf_counter() - t0,
        "programs": len(device.registered_programs()),
        "instructions": sum(len(rows) for rows in scopes.values()),
        "hbm_in_use_before": before, "hbm_in_use_after": in_use()}
    obs["scope_map"] = scopes or None
    return obs["scope_map"]


def split(obs, which: str):
    """``split_by_scope`` of the run's trace for the train step
    (``which="train"``), the decode or the prefill programs, once a run;
    each is also written to ``benchmarks/out/<cell>/scopes.json`` with
    its Mosaic kernels and its largest unscoped ops by name, for a
    person."""
    cached = f"scope_split.{which}"
    if cached in obs:
        return obs[cached]
    obs[cached] = None
    trace = obs.get("trace")
    if not trace or not trace.devices \
            or not trace.module_runs(MODULES[which]):
        return None
    scopes = scope_map(obs)
    if scopes:
        obs[cached] = split_by_scope(trace, MODULES[which], scopes)
        _write_report(obs)
    return obs[cached]


def _write_report(obs) -> None:
    cell = obs["cell"]
    report = {"cost": obs.get("scope_map_cost")}
    for which in MODULES:
        got = obs.get(f"scope_split.{which}")
        if not got:
            continue
        largest = sorted(got.events, key=lambda ev: -ev[3])

        def rows(keep, top):
            return [[scope, phase, trace_reduce.short_name(name), s]
                    for name, scope, phase, s in largest
                    if keep(name, scope)][:top]

        report[which] = {
            "module_s": got.module_s,
            "own_s": sum(got.by.values()),
            "by_scope_phase": sorted(
                ([scope, phase, s] for (scope, phase), s in got.by.items()),
                key=lambda row: -row[2]),
            "largest_ops": rows(lambda name, scope: True, 25),
            "kernels": rows(
                lambda name, scope: trace_reduce.MOSAIC_CALL in name, 25),
            "unscoped_ops": rows(
                lambda name, scope: scope == UNSCOPED, 15)}
    out_dir = os.path.join(cell.bench_dir, "out", cell.name)
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "scopes.json"), "w") as f:
        json.dump(report, f, indent=1)


def scope_seconds(obs, which: str, *scopes: str) -> Optional[tuple]:
    """(own device seconds of the ops the program traced under ``scopes``,
    device seconds of the module's runs); None where none of them ran or
    the program keeps no map."""
    got = split(obs, which)
    if not got or not got.module_s:
        return None
    seconds = sum(s for (name, _phase), s in got.by.items()
                  if name in scopes)
    return (seconds, got.module_s) if seconds else None


def scopes_time_share(*scopes: str, which: str = "decode", applies=None):
    """``scope_seconds`` of the decode (or prefill, or train) programs as a
    share, in %; None also where ``applies(obs)`` says that the
    configuration has no such layer."""
    def read(obs) -> Optional[float]:
        found = scope_seconds(obs, which, *scopes) \
            if applies is None or applies(obs) else None
        return None if found is None else 100.0 * found[0] / found[1]
    return read


def time_share(which: str, family: Optional[str] = None,
               phase: Optional[str] = None):
    """Own device seconds of a family of scopes (every scope where
    ``family`` is None) in one phase (every phase where None) / device
    seconds of the module's runs, in %."""
    def read(obs) -> Optional[float]:
        got = split(obs, which)
        if not got:
            return None
        seconds = sum(
            s for (scope, ph), s in got.by.items()
            if (family is None or scope in FAMILIES[family])
            and (phase is None or ph == phase))
        return 100.0 * seconds / got.module_s
    return read
