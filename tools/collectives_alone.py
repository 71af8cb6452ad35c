"""A train cell's collectives, op by op: which run alone on the chip.

    chiprun --chips 4 -- python3 -m tools.collectives_alone
    chiprun --chips 4 -- python3 -m tools.collectives_alone internlm2-1.8b.train-fsdp4 --steps 4

Builds the cell's train step under its mesh (``benchmarks/workloads/
<cell>.json``: configuration, batch, mesh, optimizer) on weights drawn on
the device, traces ``--steps`` warmed steps and prints for chip 0, in
milliseconds a step: the step; every collective op by name with the time
it is in flight and the part of that during which no other op runs (what
``collective_time_share`` and ``collective_exposed_share`` add up, one
line an op, but the permutes of a weight gradient's exchange by hand,
``llama.scattered_grad_matmul``'s, which are one line an exchange: the
head's, and the layers' by the scope they are traced under, told from
XLA's own permutes by the ``op_name`` the compiled step gives them); the
layers' exchange as a whole; and the ``%all-reduce-scatter`` fusions,
XLA's fused reduce-scatters, whose trace events have the opcode
``fusion``: the benchmark's readers do not count them as collectives, and
they run on the ops line with nothing beside them (PERF.md section 7).  A
time is the chip's or it is none: anywhere but on a TPU the tool exits
before it builds anything.
"""

import argparse
import re
import shutil
import tempfile
from typing import Dict, List, Mapping, Optional, Sequence, Tuple

from benchmarks.lib import trace_reduce as tr
from ray_tpu.observability.device import scope_of

FUSED_REDUCE_SCATTER = "calls=%all-reduce-scatter"
LAYERS_EXCHANGE = "layers' gradient exchange"
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def exchanges(hlo_text: str) -> Dict[str, str]:
    """``{event name, short: exchange}`` of a compiled step's text: the
    collective-permutes (starts and dones) that ``ppermute`` under a
    ``shard_map`` in a backward made, named ``head's gradient exchange``
    or ``layers' gradient exchange, <scope>`` by their ``op_name``
    (``jit(step)/transpose(jvp(layer_scan))/while/body/closed_call/
    checkpoint/ffn/shard_map/ppermute``)."""
    found = {}
    for line in hlo_text.splitlines():
        made = _OP_NAME.search(line)
        if not (made and "transpose(jvp(" in made.group(1)
                and made.group(1).endswith("/shard_map/ppermute")
                and tr.is_collective(line.strip())):
            continue
        found[tr.short_name(line.strip(), 96)] = (
            f"{LAYERS_EXCHANGE}, {scope_of(made.group(1))[0]}"
            if "(layer_scan)" in made.group(1)
            else "head's gradient exchange")
    return found


def by_op(ops: Sequence[tr.Event], async_ops: Sequence[tr.Event] = (),
          rows_of: Optional[Mapping[str, str]] = None
          ) -> Tuple[List[Tuple[str, float, float, int]],
                     List[Tuple[str, float, int]]]:
    """``(collectives, fused)``: per collective op ``(name, seconds in
    flight, seconds of those with no other op running, events)``, most
    alone first, and per fused reduce-scatter ``(name, seconds,
    events)``.  An op's events on the two lines (an async start and its
    flight) count once, as the union of their intervals; the ops that
    ``rows_of`` names (``exchanges``) are one row under the name it gives
    them."""
    rows_of = rows_of or {}
    other = tr.union((s, e) for s, e, n in tr._leaves(ops)
                     if not tr.is_collective(n))
    flights: Dict[str, List[tr.Interval]] = {}
    for s, e, n in list(ops) + list(async_ops):
        if tr.is_collective(n):
            name = tr.short_name(n, 96)
            flights.setdefault(rows_of.get(name, name), []).append((s, e))
    rows = {}
    for name, spans in flights.items():
        spans = tr.union(spans)
        rows[name] = (tr.total(spans), tr.total(tr.subtract(spans, other)),
                      len(spans))
    fused: Dict[str, List[float]] = {}
    for s, e, n in ops:
        if FUSED_REDUCE_SCATTER in n:
            row = fused.setdefault(tr.short_name(n, 96), [0.0, 0])
            row[0] += e - s
            row[1] += 1
    return (sorted(((n, *r) for n, r in rows.items()),
                   key=lambda r: -r[2]),
            sorted(((n, *r) for n, r in fused.items()), key=lambda r: -r[1]))


def report(trace: tr.Trace, rows_of: Optional[Mapping[str, str]] = None,
           pattern: str = r"jit_step") -> str:
    rows_of = rows_of or {}
    device = trace.devices[0]
    runs = sorted(e - s for s, e, _ in trace.module_runs(pattern))
    steps = max(len(runs), 1)
    collectives, fused = by_op(device.ops, device.async_ops, rows_of)
    whole = {op: LAYERS_EXCHANGE for op, row in rows_of.items()
             if row.startswith(LAYERS_EXCHANGE)}
    layers = [row for row in by_op(device.ops, device.async_ops, whole)[0]
              if row[0] == LAYERS_EXCHANGE]
    in_flight, alone = trace.collective_seconds()
    ms = 1e3 / steps
    out = [f"{len(runs)} steps, median {runs[len(runs) // 2] * 1e3:.2f} ms"
           if runs else "no step found",
           f"collectives: {in_flight * ms:.2f} ms a step in flight, "
           f"{alone * ms:.2f} alone",
           "   alone  in flight  a step  op"]
    out += [f"{a * ms:8.3f} {t * ms:10.3f} {c / steps:7.1f}  {name}"
            for name, t, a, c in collectives + layers]
    out.append(f"fused reduce-scatters (opcode fusion, not counted above): "
               f"{sum(t for _, t, _ in fused) * ms:.2f} ms a step")
    out += [f"{t * ms:8.3f} {'':10} {c / steps:7.1f}  {name}"
            for name, t, c in fused]
    return "\n".join(out)


def main():
    import jax
    import jax.numpy as jnp

    from benchmarks.lib import program, spec
    from ray_tpu.models import llama
    from ray_tpu.parallel import MeshSpec, use_mesh
    from ray_tpu.parallel.sharding import logical_sharding

    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("cell", nargs="?",
                        default="internlm2-1.8b.train-fsdp4")
    parser.add_argument("--steps", type=int, default=4)
    args = parser.parse_args()
    if jax.default_backend() != "tpu":
        raise SystemExit("collectives_alone times the compiled step: "
                         "tpu only")

    cell = spec.Cell(args.cell)
    trainer = cell.workload["trainer"]
    cfg = program.llama_config(cell.config)
    fused = bool(trainer["fused_optimizer"])
    devices = jax.devices()[:cell.chips]
    with use_mesh(MeshSpec(**(trainer["mesh"] or {})).build(devices)):
        state = llama.init_train_state(jax.random.key(0), cfg, fused=fused)
        step = llama.make_train_step(cfg, fused=fused)
        tokens = jax.random.randint(
            jax.random.key(1),
            (cell.traffic["batch"], cell.traffic["seq_len"]), 0,
            cfg.vocab_size, jnp.int32)
        if len(devices) > 1:
            tokens = jax.device_put(tokens, logical_sharding(("batch", None)))
        batch = {"tokens": tokens}
        rows_of = exchanges(step.lower(state, batch).compile().as_text())
        for _ in range(3):                                  # compile, warm
            state, metrics = step(state, batch)
        jax.block_until_ready(metrics)
        trace_dir = tempfile.mkdtemp(prefix="collectives_alone_")
        try:
            jax.profiler.start_trace(trace_dir)
            for _ in range(args.steps):
                state, metrics = step(state, batch)
            jax.block_until_ready(metrics)
            jax.profiler.stop_trace()
            trace = tr.read(trace_dir)
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
    print(f"{args.cell} on {len(devices)} x {devices[0].device_kind}",
          flush=True)
    print(report(trace, rows_of), flush=True)


if __name__ == "__main__":
    main()
