"""Kind ``train_lm``: a language model trained through the entry point a
user calls — ``JaxTrainer(...).fit()`` with one worker that owns the
cell's chips, ``init_train_state`` / ``make_train_step`` under the
cell's mesh, batches from a ``ray_tpu.data`` pipeline through
``get_dataset_shard().iter_batches(device_put=True)``.  The loop reads
the loss (and so waits for the device) every ``sync_every_steps`` steps,
as a user who logs does.

Cell file: ``trainer`` {mesh: null | {axis: n}, fused_optimizer,
prefetch_batches, sync_every_steps, warmup_steps}.  Traffic:
``generator: "token_batches"``.

``correct``: the plain float32 reference computes the loss of the first
batch and its gradient from the same parameters, forward and backward
over every sequence.  The first step's own ``loss`` and ``grad_norm``
must agree within LOSS_TOL and GRAD_NORM_TOL; and because a norm hardly
moves when a small term is wrong (section 6 of PERF.md: all of dq
dropped is 1%), the gradient of the program's ``loss_fn`` under the
cell's configuration — the function the step differentiates, with the
same kernels — must agree with the reference's kind of parameter by kind
within GRAD_LEAF_TOL.  All of it runs after the window on the state made
again from the seed, so neither its seconds nor its memory are counted
as the program's.  Also: train state
float32, loss finite and moving, buffers donated, no leaf resharded by
the step, no compilation inside the window.
"""

from __future__ import annotations

import json
import math
import os
import time
from typing import Any, Dict

import numpy as np

from benchmarks.lib import loadgen, program, runtime

# The step computes its forward in bfloat16 (8 mantissa bits) and the
# reference in float32.  Over ~16k random tokens the rounding of the
# logits averages out in the mean cross-entropy: the largest |difference|
# measured on the v5e is 1.6e-4 over 36 runs (PERF.md section 6), and the
# tolerance is twelve times that.  A dropped residual, norm or RoPE term moves the loss
# at initialisation by > 0.05; bfloat16 master weights are caught by the
# dtype check.
LOSS_TOL = 0.002
# The step's gradient comes from bfloat16 matmuls and the flash kernels'
# backward (dq, dk/dv), the reference's from float32: rounding that is
# independent from element to element adds to the norm only in the
# second order.  Relative |difference|: the largest measured on the v5e
# is 4.5e-4 (PERF.md section 6), the tolerance eleven times that; all of
# dq dropped moves the norm by 1.1%, dk 2.8%, dv 56%.
GRAD_NORM_TOL = 0.005
# |loss_fn's gradient - the reference's| / |the reference's| for the
# worst kind of parameter: rounding leaves 0.7-2.8% (measured, wq and wk
# the worst), a kernel that drops or misplaces a term leaves its kind of
# parameter near 100%.
GRAD_LEAF_TOL = 0.1


def _loop(config: Dict[str, Any]) -> None:
    """``train_loop_per_worker``.  Everything observed goes back through
    ``train.report`` as plain data."""
    import jax

    from ray_tpu import train
    from ray_tpu.models import llama

    cell_config, trainer = config["config"], config["trainer"]
    reference = config["reference"]
    watch = runtime.compile_watch()
    cfg = program.llama_config(cell_config)
    fused = bool(trainer["fused_optimizer"])
    batch_size, every = config["batch"], int(trainer["sync_every_steps"])

    state = llama.init_train_state(jax.random.key(config["seed"]), cfg,
                                   fused=fused)
    jax.block_until_ready(state)
    step = llama.make_train_step(cfg, fused=fused)
    shard = train.get_dataset_shard("train")

    waits = {"s": 0.0}

    def batches():
        while True:   # epochs over the same shard
            it = shard.iter_batches(
                batch_size=batch_size, drop_last=True,
                prefetch_batches=int(trainer["prefetch_batches"]),
                device_put=True)
            while True:
                t0 = time.perf_counter()
                try:
                    batch = next(it)
                except StopIteration:
                    break
                waits["s"] += time.perf_counter() - t0
                yield batch

    feed = batches()
    batch = next(feed)
    first_tokens = np.asarray(batch["tokens"])
    token_sharding = batch["tokens"].sharding
    obs: Dict[str, Any] = {
        "batch_sharding": str(token_sharding),
        "state_dtypes": sorted({str(x.dtype)
                                for x in jax.tree.leaves(state["params"])}),
    }

    # First step: compiles (or fetches), then the checks on live state.
    old_leaves = jax.tree.leaves(state)
    old_shardings = [leaf.sharding for leaf in old_leaves]
    state, metrics = step(state, batch)
    losses = [float(metrics["loss"])]
    obs["grad_norm_first"] = float(metrics["grad_norm"])
    obs["donation_honoured"] = all(x.is_deleted() for x in old_leaves)
    obs["leaves_resharded"] = [
        jax.tree_util.keystr(path) for (path, leaf), was in zip(
            jax.tree_util.tree_leaves_with_path(state), old_shardings)
        if not leaf.sharding.is_equivalent_to(was, leaf.ndim)]
    del old_leaves, old_shardings
    for _ in range(int(trainer["warmup_steps"])):
        state, metrics = step(state, next(feed))
    losses.append(float(metrics["loss"]))

    # ------------------------------------------------------ the window
    tracer = runtime.Tracer(config["trace"], config["out_dir"])
    compiles_open = watch.count
    program_open = runtime.program_counters()
    waits["s"] = 0.0
    jax.block_until_ready(state)
    t_open = t_mark = time.perf_counter()
    # The steps' own ``expert_rows`` (a model with experts: what its grouped
    # matmuls had to do), kept as they lie on the device and fetched after
    # the window: the traced group's, else the last step's.
    groups, expert_rows = [], []
    while True:
        # Group after group with no gap between them, except around the
        # one traced group: starting and stopping the profiler is not
        # the program's time.
        traced = tracer.enabled and len(groups) == 1
        if traced:
            tracer.start()
            t_mark = time.perf_counter()
        for _ in range(every):
            state, metrics = step(state, next(feed))
            if traced:
                expert_rows.append(metrics.get("expert_rows"))
        loss = float(metrics["loss"])          # the user's log line: waits
        t_end = time.perf_counter()
        groups.append({"steps": every, "t_start": t_mark, "t_end": t_end,
                       "loss": loss, "traced": traced})
        t_mark = t_end
        if traced:
            tracer.stop()
            t_mark = time.perf_counter()
        train.report({"step": len(groups) * every, "loss": loss})
        if time.perf_counter() - t_open >= config["seconds"] and \
                not (tracer.enabled and len(groups) < 2):
            break
    t_close = time.perf_counter()
    expert_rows = [np.asarray(rows) for rows in
                   expert_rows or [metrics.get("expert_rows")]
                   if rows is not None]
    obs.update({
        # (expert layers, experts the router scores): rows a step, the mean
        # over the steps kept; None for a model without experts
        "expert_rows": np.mean(expert_rows, axis=0).tolist()
        if expert_rows else None,
        "t_open": t_open, "t_close": t_close,
        "groups": groups, "losses_warmup": losses,
        "input_wait_s": waits["s"],
        "window_compiles": watch.count - compiles_open,
        "program_window_compiles":
            runtime.program_counters()["xla_compiles"]
            - program_open["xla_compiles"],
        "memory": runtime.memory_peaks(jax.local_devices()),
    })

    # ------------------------------------ the reference, after the window
    del state, batch, metrics
    t0 = time.perf_counter()
    params = llama.init_train_state(jax.random.key(config["seed"]), cfg,
                                    fused=fused)["params"]

    def place(rows):   # as the pipeline delivered them
        return jax.device_put(rows, token_sharding)

    ours = jax.jit(
        lambda p, b: jax.grad(llama.loss_fn)(p, b, cfg),
        # each gradient sharded as its parameter, not gathered whole
        out_shardings=jax.tree.map(lambda x: x.sharding, params))(
        params, {"tokens": place(first_tokens)})
    obs["reference_loss"], theirs = reference.loss_and_grads(
        params, first_tokens, cell_config,
        # one group of rows across the chips that share a batch
        rows_at_a_time=len(token_sharding.device_set), place=place)
    obs["reference_grad_norm"] = reference.global_norm(theirs)
    obs["grad_leaf_gaps"] = reference.gradient_gaps(ours, theirs)
    obs["reference_s"] = time.perf_counter() - t0
    train.report({"step": -1, "obs": obs})


def run(ctx: runtime.Context) -> Dict[str, Any]:
    import ray_tpu
    from ray_tpu import data as rd
    from ray_tpu.parallel import MeshSpec
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    cell = ctx.cell
    traffic, trainer = cell.traffic, cell.workload["trainer"]
    batch, seq = traffic["batch"], traffic["seq_len"]
    rows = loadgen.token_batches(traffic, ctx.seed,
                                 cell.config["vocab_size"])
    dataset = rd.from_blocks([{"tokens": rows[i:i + batch]}
                              for i in range(0, len(rows), batch)])
    mesh = MeshSpec(**trainer["mesh"]) if trainer.get("mesh") else None
    job = JaxTrainer(
        _loop,
        train_loop_config=dict(
            config=cell.config, trainer=trainer, reference=cell.reference,
            batch=batch, seed=ctx.seed, seconds=ctx.seconds,
            trace=ctx.trace, out_dir=ctx.out_dir),
        scaling_config=ScalingConfig(num_workers=1, mesh=mesh),
        run_config=RunConfig(
            name=cell.name,
            storage_path=os.path.join(ctx.out_dir, "train_results")),
        datasets={"train": dataset})
    t_fit = time.perf_counter()
    try:
        result = job.fit()
    finally:
        ray_tpu.shutdown()
    obs = result.metrics.get("obs")
    if obs is None:
        raise RuntimeError(f"the train loop did not finish: "
                           f"{result.metrics} {getattr(result, 'error', '')}")

    losses = obs["losses_warmup"] + [g["loss"] for g in obs["groups"]]
    loss_gap = abs(losses[0] - obs["reference_loss"])
    grad_norm_gap = abs(obs["grad_norm_first"] / obs["reference_grad_norm"]
                        - 1.0)
    checks = {
        "first loss equals the reference's": loss_gap <= LOSS_TOL,
        "first grad norm equals the reference's":
            grad_norm_gap <= GRAD_NORM_TOL,
        "loss_fn's gradient equals the reference's, kind by kind":
            max(obs["grad_leaf_gaps"].values()) <= GRAD_LEAF_TOL,
        "train state is float32": obs["state_dtypes"] == ["float32"],
        "losses finite": all(math.isfinite(x) for x in losses),
        "loss moves between reads": all(
            a != b for a, b in zip(losses, losses[1:])),
        "old state donated": bool(obs["donation_honoured"]),
        "no leaf resharded by the step": not obs["leaves_resharded"],
        "no compilation in the window": obs["window_compiles"] == 0,
    }
    steps = sum(g["steps"] for g in obs["groups"])
    with open(os.path.join(ctx.out_dir, "groups.json"), "w") as f:
        json.dump(obs["groups"], f)
    return {
        "kind": "train_lm", "checks": checks,
        # each number ``correct`` compares, beside its limit
        "compared": {
            "loss_gap": [loss_gap, LOSS_TOL],
            "grad_norm_gap": [grad_norm_gap, GRAD_NORM_TOL],
            "grad_leaf_gap_max": [max(obs["grad_leaf_gaps"].values()),
                                  GRAD_LEAF_TOL],
            "window_compiles": [obs["window_compiles"], 0]},
        "attempted": steps,
        "failed": 0 if checks["losses finite"] else steps,
        "setup_s": obs["t_open"] - ctx.t_process,
        "t_fit": t_fit, "tokens_per_step": batch * seq, "seq_len": seq,
        "batch": batch, "losses": losses, "loss_gap": loss_gap,
        "grad_norm_gap": grad_norm_gap,
        "trace": runtime.read_trace(ctx),
        "window_compiles": obs["window_compiles"],
        **{k: obs[k] for k in (
            "groups", "t_open", "t_close", "input_wait_s", "memory",
            "expert_rows", "reference_loss", "reference_grad_norm",
            "grad_leaf_gaps", "reference_s", "batch_sharding",
            "grad_norm_first", "program_window_compiles")},
    }
