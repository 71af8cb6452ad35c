"""Per-stage Llama functions for CROSS-PROCESS pipeline parallelism.

The in-jit GPipe schedule (parallel/pipeline.py) runs all stages in one
XLA program on one mesh — the right shape *within* an ICI domain.  A
multi-slice pod needs the other half: each slice runs its own jitted
stage program and activations cross DCN between processes
(train/cross_pipeline.py).  This module supplies the stage-local math:

- ``stage_slice(params, stage, n)`` — the stage's parameter subtree
  (embedding on stage 0, L/n layer block each, norm+head on the last).
- ``make_stage_fwd / make_stage_fwd_loss`` — jittable stage programs.
- Backward is activation recomputation at stage granularity: the stage
  re-runs its forward under ``jax.vjp`` at backward time, so only the
  stage *input* is kept per in-flight microbatch (GPipe memory M×input,
  not M×activations).

Reference: Ray ships no pipeline-training schedule; its intended
substrate is compiled-graph channels + overlap schedules
(python/ray/dag/dag_node_operation.py:506-539).  SURVEY §5.8: DCN =
cross-slice pipelines over channels.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp

from .llama import (LlamaConfig, embed_sharded, head_loss_logits,
                    next_token_loss, train_layers)

PyTree = Any


def check_pipeline_config(config: LlamaConfig, n_stages: int):
    if n_stages < 2:
        raise ValueError("cross-process pipeline needs >= 2 stages")
    if not config.one_stage_stack:
        raise NotImplementedError(
            "a pipeline stage runs one stack of one kind of the layers "
            "llama.forward trains (LlamaConfig.one_stage_stack): this "
            "config is served only, or trained whole")
    if config.n_layers % n_stages:
        raise ValueError(
            f"{config.n_layers} layers not divisible by {n_stages} stages")
    if config.tie_embeddings:
        raise ValueError(
            "tie_embeddings shares one parameter between stage 0 "
            "(embedding) and the last stage (head); untie for "
            "cross-process pipeline")
    if config.moe_experts > 0:
        raise NotImplementedError(
            "MoE layers in cross-process pipeline stages: route the "
            "aux loss through the activation protocol first")
    if config.attention_impl == "ring":
        raise NotImplementedError(
            "ring attention needs a seq mesh axis inside the stage "
            "program; compose it via the stage mesh_spec instead")


def stage_slice(params: PyTree, stage: int, n_stages: int) -> PyTree:
    """The parameter subtree stage ``stage`` owns.

    Slicing a fully-initialized tree keeps init numerics identical to
    the single-process model (parity tests depend on it).  At 8B+ scale
    initialize per-stage instead: build the full tree under
    ``jax.eval_shape`` and materialize only this slice.
    """
    L = jax.tree.leaves(params["layers"])[0].shape[0]
    per = L // n_stages
    lo, hi = stage * per, (stage + 1) * per
    out: Dict[str, Any] = {
        "layers": jax.tree.map(lambda a: a[lo:hi], params["layers"])}
    if stage == 0:
        out["embed_tokens"] = params["embed_tokens"]
    if stage == n_stages - 1:
        out["final_norm"] = params["final_norm"]
        out["lm_head"] = params["lm_head"]
    return out


def _run_layers(x: jax.Array, layers: PyTree, config: LlamaConfig):
    """The stage's stacked layers over ``x`` (B, S, E): ``forward``'s walk
    over a model of the stage's own layers."""
    n = jax.tree.leaves(layers)[0].shape[0]
    return train_layers(
        x, {"layers": layers}, dataclasses.replace(config, n_layers=n),
        jnp.broadcast_to(jnp.arange(x.shape[1], dtype=jnp.int32),
                         x.shape[:2]))[0]


def make_stage_fwd(config: LlamaConfig, first: bool) -> Callable:
    """``fwd(stage_params, inp) -> h_out``; inp is tokens (B, S) int32
    on stage 0, hidden states (B, S, E) downstream."""

    def fwd(sl: PyTree, inp: jax.Array) -> jax.Array:
        x = (embed_sharded(sl, inp, config) if first
             else inp.astype(config.dtype))
        return _run_layers(x, sl["layers"], config)

    return fwd


def make_stage_fwd_loss(config: LlamaConfig) -> Callable:
    """Last stage: ``fwd_loss(stage_params, h_in, tokens) -> loss``.

    Mirrors llama.loss_fn's full-length-forward-then-slice convention
    (llama.py loss_fn) so pipeline loss == single-process loss.
    """
    c = config

    def fwd_loss(sl: PyTree, h_in: jax.Array,
                 tokens: jax.Array) -> jax.Array:
        x = _run_layers(h_in.astype(c.dtype), sl["layers"], c)
        return next_token_loss(head_loss_logits(x, sl, c)[:, :-1],
                               {"tokens": tokens}, c)

    return fwd_loss
