"""Tell an expert layer's work apart in a device trace, by name, and the
readers of the five ``moe_*`` metrics.

``ray_tpu/models/moe.py`` computes its experts with
``jax.lax.ragged_dot``.  For a TPU XLA lowers each to a Mosaic kernel,
plus a small kernel that turns the group sizes into the grid's metadata,
and names both instructions itself, so a v5e trace's ``XLA Ops`` events
read ``%ragged-dot-none.2 = f32[960,2048]{...} custom-call(...),
custom_call_target="tpu_custom_call"...`` and ``%ragged-dot-metadata =
(s32[513]...) custom-call(...)`` (PERF.md section 3; ``flash_names.py``
is the precedent).  The routing around them — router matmul, softmax,
top-k, the sort by expert, counts, the gather of rows into expert order,
the un-sort and the gate-weighted sum — is ordinary HLO whose names say
nothing (``%fusion.174``, ``%sort.32``), but whose ARRAYS do: an event's
name is the instruction's whole text, result and operands with their
shapes, and within a decode program only routing handles an array of
``slots x top_k`` rows (``s32[960]``, ``bf16[960,2048]``) or of shape
``[slots, experts]`` / ``[slots, top_k]``.  The one other op with such
rows is the activation between the grouped matmuls (``[slots x top_k,
expert width]``), which is the experts' own and counted apart.

A program without experts matches nothing here, and the readers return
None.
"""

from __future__ import annotations

import re
import statistics
from typing import Dict, List, Optional, Tuple

from . import readers, trace_reduce

GROUPED_MATMUL_OP = r"^%ragged-dot-none(\.\w+)* = "
GROUP_METADATA_OP = r"^%ragged-dot-metadata(\.\w+)* = "


def routing_op(slots: int, top_k: int, experts: int):
    """Pattern of a decode program's routing ops (see the module's
    head); the grouped matmuls are to be excluded first."""
    rows = slots * top_k
    return (rf"\[{rows}[,\]]|\[{slots},{experts}\]|\[{slots},{top_k}\]|"
            + GROUP_METADATA_OP)


def _decode_leaves(trace) -> List[trace_reduce.Event]:
    """Leaf ops (no op nested inside) that ran inside a decode module."""
    runs = trace.module_runs(readers.DECODE_MODULE)
    inside, i = [], 0
    for ev in trace_reduce._leaves(trace.devices[0].ops) if runs else []:
        while i < len(runs) and runs[i][1] <= ev[0]:
            i += 1
        if i < len(runs) and runs[i][0] <= ev[0]:
            inside.append(ev)
    return inside


def expert_layer_split(trace, slots: int, top_k: int, experts: int,
                       width: int) -> Optional[Dict[str, float]]:
    """Seconds of the decode modules spent in the expert layers:
    ``matmul`` (the grouped-matmul kernels), ``activation`` (silu x up
    between them) and ``routing`` (the rest).  None where the trace holds
    no grouped matmul."""
    matmul = re.compile(GROUPED_MATMUL_OP)
    routing = re.compile(routing_op(slots, top_k, experts))
    activation = re.compile(rf"\[{slots * top_k},{width}\]")
    out = {"matmul": 0.0, "activation": 0.0, "routing": 0.0}
    for start, end, name in _decode_leaves(trace):
        if matmul.search(name):
            out["matmul"] += end - start
        elif activation.search(name):
            out["activation"] += end - start
        elif routing.search(name):
            out["routing"] += end - start
    return out if out["matmul"] else None


def chunk_medians(obs) -> Optional[Tuple[float, float, float]]:
    """Medians over the window's ``serve.chunk`` spans of (expert rows a
    step, (layer, expert) pairs touched a step, busiest expert's rows /
    mean rows per expert): the program's own count of what its grouped
    matmuls had to do.  None where the spans carry no expert load."""
    from . import program_spans

    cfg = obs["cell"].config
    got = program_spans.collect(obs) if "num_experts" in cfg else None
    chunks = [c for c in (got.chunks if got else [])
              if c.get("expert_rows")]
    if not chunks:
        return None
    pairs = cfg["num_hidden_layers"] * cfg["num_experts"]
    return (statistics.median(c["expert_rows"] / c["k"] for c in chunks),
            statistics.median(c["experts_touched"] / c["k"]
                              for c in chunks),
            statistics.median(c["expert_rows_max"]
                              / (c["expert_rows"] / pairs)
                              for c in chunks))


# --------------------------------------------------------------- readers
def _split(obs) -> Optional[Dict[str, float]]:
    trace = obs.get("trace")
    cfg = obs["cell"].config
    if not trace or not trace.devices or "num_experts" not in cfg:
        return None
    if "moe_split" not in obs:
        obs["moe_split"] = expert_layer_split(
            trace, obs["cell"].workload["engine"]["max_slots"],
            cfg["num_experts_per_tok"], cfg["num_experts"],
            cfg["intermediate_size"])
    return obs["moe_split"]


def time_share(part: str):
    """``matmul`` or ``routing`` seconds / seconds of the decode
    programs, in %."""
    def read(obs) -> Optional[float]:
        split = _split(obs)
        if not split or not split[part]:
            return None
        runs = obs["trace"].module_runs(readers.DECODE_MODULE)
        return 100.0 * split[part] / sum(e - s for s, e, _ in runs)
    return read


def load_imbalance(obs) -> Optional[float]:
    medians = chunk_medians(obs)
    return None if medians is None else medians[2]


def decode_step_roofline(obs) -> Optional[float]:
    """Least time of one decode step at the batch in flight at the middle
    of the traced span and the experts its steps touched (HBM bytes or
    FLOPs at peak, whichever is larger) / the measured time of a step."""
    from . import moe_flops

    step_ms = readers.decode_step_device_ms(obs)
    span = obs.get("trace_span")
    medians = chunk_medians(obs)
    if step_ms is None or medians is None or not span or span[0] is None:
        return None
    sequences, positions = readers.context_in_flight(
        obs, (span[0] + span[1]) / 2)
    if not sequences:
        return None
    rows, touched, _ = medians
    cfg, peaks = obs["cell"].config, obs["peaks"]
    least = max(
        moe_flops.decode_step_bytes(cfg, touched, positions)
        / peaks["hbm_bytes_per_s"],
        moe_flops.decode_step_flops(cfg, sequences, positions, rows)
        / peaks["bf16_flops_per_s"])
    return 100.0 * least / (step_ms * 1e-3)


def expert_matmul_roofline(obs) -> Optional[float]:
    """Least time of a step's grouped matmuls (the touched experts'
    matrices and the rows' activations) / their measured time a step."""
    from . import moe_flops

    split, medians = _split(obs), chunk_medians(obs)
    step_ms = readers.decode_step_device_ms(obs)
    if not split or medians is None or step_ms is None:
        return None
    # the kernels' share of the decode programs' time x the median step:
    # a program cut by the trace's edge miscounts neither
    runs = obs["trace"].module_runs(readers.DECODE_MODULE)
    kernel_s = split["matmul"] / sum(e - s for s, e, _ in runs) \
        * step_ms * 1e-3
    rows, touched, _ = medians
    cfg, peaks = obs["cell"].config, obs["peaks"]
    least = max(
        moe_flops.expert_matmul_bytes(cfg, touched, rows)
        / peaks["hbm_bytes_per_s"],
        moe_flops.expert_matmul_flops(cfg, rows)
        / peaks["bf16_flops_per_s"])
    return 100.0 * least / kernel_s
