"""Llama-family decoder LM, TPU-native.

Design (idiomatic jax/XLA, not a torch translation):

- **Functional**: params are a plain pytree; ``forward(params, tokens)``
  is pure and jit/pjit-friendly.
- **Scan over layers**: per-layer weights are stacked on a leading
  ``layers`` dim and ONE block (``layer_block``) runs under
  ``jax.lax.scan`` (``walk_layers``) — one trace, O(1) compile time in
  depth, and the ``layers`` dim is the natural pipeline-parallel shard axis.
- **Logical shardings**: every weight/activation dim carries a logical
  axis name resolved by :mod:`ray_tpu.parallel.sharding`; the same model
  runs DP/FSDP/TP/SP by swapping rule tables.
- **bf16 compute, f32 params/optimizer**: matmuls hit the MXU in
  bfloat16; the master copy and adam moments stay float32.
- **Pluggable attention**: ``config.attention_impl`` selects plain
  einsum attention, the Pallas flash kernel, or ring attention
  (sequence-parallel) — all causal, all identical numerics up to
  blocking.

Parity note: the reference trains models only through wrappers around
torch (train/torch/train_loop_utils.py:162); there is no reference
model to port, so shapes follow the public Llama-2/3 architecture.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ray_tpu.parallel.sharding import (axes_entry, current_rules,
                                       partitioning_mesh,
                                       with_logical_constraint)

PyTree = Any
LAYER_KINDS = ("attention", "mamba", "window", "conv", "mamba1", "gmu",
               "cross", "kda", "power")
# The kinds that keep a state a slot (``state_mixer``): a model has one.
STATE_KINDS = ("mamba", "mamba1", "conv", "kda", "power")
# Leaves that stay float32 whatever type the weights are served in.
FLOAT32_LEAVES = ("router_bias",)
# The deviation a router's selection bias is drawn with: of the order of
# the distance between a token's 4th and 5th sigmoid scores of 32 under
# this initialiser (median 0.019, mean 0.026 at hidden 2,048), so that the
# bias changes the choice for a measurable share of tokens (35% a layer
# there).  A zero bias would leave the mechanism idle.
ROUTER_BIAS_STD = 0.02
# The kinds whose mixer is attention: they share ``ATTENTION_LEAVES``,
# stacked over all of them in their order.  A ``cross`` layer has a query
# and an output projection alone and attends the rows of the K/V layer
# before it (``LlamaConfig.kv_layer``): it holds no rows of its own.
ATTENDING_KINDS = ("attention", "window", "cross")
# The deviation a bias (a LayerNorm's, an attention projection's) is drawn
# with: a zero bias would leave the term idle under the comparison with
# the reference.  And that of a differential attention's lambda vectors.
BIAS_STD = 0.02
LAMBDA_STD = 0.1
# What a sandwich norm's weight starts at (``sandwich_norm``).  The norm
# erases the scale of the branch's output projection, so the (2 L)^-1/2 a
# residual-scaled initialiser gives that projection (0.32 at 5 layers, 0.125
# at 32) has to sit in the norm's own weight; and the embedding is then NOT
# drawn down by its multiplier, so that tokens enter at unit rms beside the
# branches.  At 1 / 1 every branch enters at 45 x the embedding at hidden
# 2,048, every position's stream is nearly the same vector and a sigmoid
# router sends most tokens to the same few experts: one chip's share of the
# rows then swings by +-45% from seed to seed at the first step (at toy
# width 545-1,431 rows of an expected 1,024, with this 760-1,109; on the
# v5e 30.1k-36.9k of 32.8k with it).  What the share does AFTER the first
# step is the learning rate's: ``lr_warmup_steps`` (PERF.md section 6, PR
# 57).
POST_NORM_INIT = 0.3


def _runs(kinds, cuts=()):
    """A list of layer kinds cut into runs of whole periods: ``[(first
    index, one period's kinds, periods)]``.  From the front, the period
    that repeats (twice or more) over the most layers, the shorter of two
    that cover the same; a stretch in which nothing repeats is one period
    of its own.  LFM2's 22 expert layers (A C C C) x 4, (A C C) x 2 are
    two runs; their first 12 one.  No run crosses one of ``cuts`` (a
    decoder-hybrid-decoder's K/V layer is a run of its own: its prefill
    stops there)."""
    kinds = tuple(kinds)
    cut = next((c for c in sorted(cuts) if 0 < c < len(kinds)), None)
    if cut is not None:
        return _runs(kinds[:cut]) + [
            (cut + at, period, m)
            for at, period, m in _runs(kinds[cut:],
                                       [c - cut for c in cuts])]

    out, at = [], 0
    while at < len(kinds):
        rest = kinds[at:]
        best = (len(rest), 1)                   # nothing repeats
        covered = 0
        for p in range(1, len(rest) // 2 + 1):
            m = 1
            while rest[m * p:(m + 1) * p] == rest[:p]:
                m += 1
            if m > 1 and m * p > covered:
                best, covered = (p, m), m * p
        p, m = best
        out.append((at, rest[:p], m))
        at += p * m
    return out


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    head_dim: int = 128
    intermediate_size: int = 14336
    max_seq_len: int = 8192
    rope_theta: float = 500000.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    # "dot" (einsum), "flash" (Pallas kernel), "ring" (sequence-parallel
    # ring attention over the "seq" mesh axis).
    attention_impl: str = "dot"
    remat: bool = True
    # Rematerialization policy for the per-layer checkpoint wrapper:
    # "full" recomputes everything in backward (min memory, ~2N extra
    # flops/token); "dots" saves matmul/einsum outputs with no batch
    # dims (XLA's dots_with_no_batch_dims_saveable — but it saves the
    # F32 dot results, ~830 MB/layer at bench shapes: OOM on one v5e);
    # "attn" saves only the flash kernel's residuals (q/k/v/o bf16 +
    # lane-dense f32 lse, ~129 MB/layer) so backward skips re-running the
    # attention forward while still rematerializing the FFN — the best
    # measured time/memory point on v5e; ignored when remat=False.
    remat_policy: str = "full"
    # Tie input embedding and LM head (small models).
    tie_embeddings: bool = False
    # lax.scan unroll factor for the layer stack: >1 lets XLA fuse
    # across adjacent layers (fewer loop-carried DUS/sequencing
    # overheads) at the cost of compile time.
    scan_unroll: int = 1
    # >0 enables REAL pipeline parallelism when the active mesh has a
    # pipe axis of size >1: the layer stack runs as a GPipe microbatch
    # schedule over pipe stages (parallel/pipeline.py) instead of one
    # scan.  Value = number of microbatches.
    pipeline_microbatches: int = 0
    # >0 replaces every layer's dense FFN with this many experts of
    # width intermediate_size (models/moe.py), moe_top_k a token.  Every
    # token reaches all of its experts (dropless grouped matmuls); only
    # under a mesh with expert > 1 does training dispatch densely with a
    # capacity (moe_capacity_factor), which drops.  The Switch aux loss
    # is added to the training loss scaled by moe_aux_weight.
    moe_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    moe_aux_weight: float = 0.01
    # The chosen gates renormalised to sum to one (Switch, Mixtral) or
    # the softmax's probabilities as they are (OLMoE: norm_topk_prob
    # false).
    moe_norm_topk: bool = True
    # What the router reads: "ffn", the rows the experts multiply (the
    # stream after attention, normed), or "layer", the layer's INPUT,
    # before the attention norm and un-normed (SmallThinker).  And the
    # experts' activation: "silu" or "relu" on the gate of a gated expert
    # (``act(x W_gate) * (x W_up)``), or "relu2": an expert of TWO matrices,
    # ``relu(x W_up)^2 W_down``, no gate (Nemotron-H; the shared expert too).
    moe_router_input: str = "ffn"
    moe_activation: str = "silu"
    # How the router scores: "softmax" over all experts, or "sigmoid" an
    # expert.  With moe_router_bias the top-k are those of score + a bias
    # an expert (leaf ``router_bias``, float32) and the gates are the
    # chosen SCORES, without it (LFM2).
    moe_router_score: str = "softmax"
    moe_router_bias: bool = False
    # Training balances that bias without an auxiliary loss (DeepSeek-V3,
    # arXiv:2412.19437 section 2.1.2): after each step and an expert layer,
    # ``b += d - mean(d)``, ``d = moe_balance_rate x sign(mean(c) - c)``, c
    # the step's tokens an expert was chosen by (``balance_router_bias``).
    # The bias takes no gradient, no weight decay and no Adam moments.
    moe_balance_rate: float = 0.001
    # The train step's learning rate rises linearly over this many steps
    # (step n runs at ``learning_rate x min(1, n / lr_warmup_steps)``, n
    # from 1); 0: the rate from the first step, as every plain decoder is
    # stepped.  That balance presumes it: at the whole rate Adam's first
    # updates are the gradient's SIGN, every row of a router moves by the
    # rate along what the tokens' streams have in common, a step shifts an
    # expert's logit for all tokens alike by ~0.5 at hidden 2,048 against
    # the bias's 0.001, and every token chooses the same experts within
    # twenty steps (seen on the v5e: a held share's rows swing between 0.3
    # and 2.8 x their expectation, PERF.md section 6, PR 57).
    lr_warmup_steps: int = 0
    # RMSNorm on q and on k, each over its WHOLE projection, before the
    # split into heads and before RoPE (OLMoE); or over each HEAD's
    # head_dim, one weight of head_dim shared by the heads, before RoPE
    # (qk_head_norm: LFM2).
    qk_norm: bool = False
    qk_head_norm: bool = False
    # ONE PERIOD of the layer stack, a kind per layer: "attention",
    # "mamba" (a Mamba-2 mixer, models/mamba2.py, in place of attention;
    # a layer keeps its FFN unless ``block_pattern`` says it has none) or
    # "window" (attention over the last ``window_size`` keys, the query's
    # own among them; served only).
    # n_layers is a whole number of periods and the layer scans run a
    # period an iteration.  () is a period of one attention layer: the
    # plain decoder.
    layer_pattern: Tuple[str, ...] = ()
    window_size: int = 0
    # A kind for EVERY layer, as a published ``layer_types`` lists them
    # ("full_attention" reads as "attention"), where the stack is not whole
    # periods of one pattern: ``parts`` cuts the list into runs of whole
    # periods, each walked as a stack of its own.  "conv": a gated short
    # convolution of ``conv_taps`` taps in place of attention
    # (models/shortconv.py; served only).  Leading dense layers
    # (first_dense_layers) are then the list's first entries, of whatever
    # kind it says.
    layer_types: Tuple[str, ...] = ()
    conv_taps: int = 3
    # A stack whose blocks hold ONE sub-layer each, ``x + f(norm(x))``, as
    # a published ``hybrid_override_pattern`` spells them (Nemotron-H): "M"
    # a Mamba-2 mixer, "*" attention, "E" the expert feed-forward part.  A
    # mixer and the "E" right after it are one LAYER here (``attn_norm``
    # the mixer's pre-norm, ``mlp_norm`` the feed-forward part's); a mixer
    # that no "E" follows is a layer WITHOUT a feed-forward half.
    # ``layer_types`` is derived from it and n_layers counts those layers
    # ("MEMEMEM*EME": 11 blocks, 6 layers, the fourth without an FFN).
    # ``parts`` walks each FFN-less layer as a stack of its own, whose
    # config says ``no_ffn``; on anything but such a part it is refused.
    block_pattern: str = ""
    no_ffn: bool = False
    # Rotary position embedding on q and k (False: NoPE, Granite 4), and
    # the kinds of attending layer that go without it where the others
    # rotate (SmallThinker: the global layers are NoPE, the window
    # layers rotate).
    rope: bool = True
    nope_kinds: Tuple[str, ...] = ()
    # Softmax scale in place of head_dim ** -0.5, and the Granite
    # multipliers: on the embedding, on both residual branches, and the
    # divisor of the logits.
    attention_multiplier: Optional[float] = None
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    # The Mamba-2 layers' sizes (ssm_groups groups of ssm_state dimensions:
    # head j reads the B and C of group j // (ssm_heads / ssm_groups), and
    # the gated norm runs over a group's ssm_heads * ssm_head_dim /
    # ssm_groups channels), the chunk their prefill scans by, and the type
    # their recurrent state is STORED in by the serving cache (the
    # recurrence's arithmetic is float32 either way).
    ssm_heads: int = 0
    ssm_head_dim: int = 64
    ssm_state: int = 128
    ssm_groups: int = 1
    ssm_conv: int = 4
    ssm_chunk: int = 256
    ssm_state_dtype: Any = jnp.float32
    # The type the SERVING programs carry the residual stream in (None:
    # ``dtype``).  Every branch is added to it, so its rounding is the
    # largest single source of a deep model's distance from a float32
    # reference (measured for granite-4.0-h-micro: PERF.md section 6,
    # PR 30); matmul operands are ``dtype`` either way.
    stream_dtype: Any = None
    # LATENT attention (DeepSeek-V2's MLA; served only, dense plane), on
    # with kv_lora_rank > 0.  Queries go down to q_lora_rank, are normed
    # and come up to n_heads x (qk_nope_head_dim + qk_rope_head_dim); keys
    # and values go down to ONE row of kv_lora_rank (normed) +
    # qk_rope_head_dim (roped, shared by all heads) a token, which is all
    # the serving cache keeps, and come up to qk_nope_head_dim and
    # v_head_dim a head.  head_dim is then the q/k head, nope + rope; RoPE
    # turns the rope parts only, their pairs taken interleaved (2i, 2i+1).
    kv_lora_rank: int = 0
    q_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # A published ``rope_scaling`` block of type "yarn" (factor,
    # original_max_position_embeddings, beta_fast, beta_slow, mscale,
    # mscale_all_dim): ``rope_frequencies``.  None: plain RoPE.
    rope_scaling: Any = None
    # Layers with a dense FFN of width intermediate_size BEFORE the
    # expert layers (DeepSeek's first_k_dense_replace): their weights are
    # ``params["dense_layers"]``, stacked apart, and every walk runs them
    # as a prologue outside the period scan (``parts``).
    first_dense_layers: int = 0
    # An expert's width where it is not intermediate_size (0: it is), and
    # the width of the shared expert every token passes beside its routed
    # ones (n_shared_experts x the expert width, as ONE SwiGLU; 0: none).
    moe_intermediate_size: int = 0
    moe_shared_size: int = 0
    # LATENT experts (Nemotron 3's LatentMoE), on with moe_latent_size > 0:
    # the routed experts read and write rows of that width, ``W_lat_out
    # sum_e g_e E_e(W_lat_in h)``, the two projections (leaves ``w_lat_in``
    # hidden -> latent, ``w_lat_out`` latent -> hidden) around the dispatch
    # and the combine, so that the sorted rows are latent-wide.  The router
    # and the shared expert read ``h`` at full width.
    moe_latent_size: int = 0
    # Group-limited routing: the experts are moe_groups groups of
    # consecutive ones, a group scores its best expert, only the
    # moe_top_groups best groups' experts can be chosen (0: no groups).
    # The chosen gates are multiplied by moe_routed_scale.
    moe_groups: int = 0
    moe_top_groups: int = 0
    moe_routed_scale: float = 1.0
    # One chip's SHARE of the experts: ``(first, count)`` of the
    # moe_experts are held here (their matrices are ``[L, count, ...]``),
    # the router scores all moe_experts, and the layer adds what its own
    # experts give for the tokens routed to them; what the experts
    # elsewhere would add is left out.  (): every expert is here.
    moe_held: Tuple[int, ...] = ()
    # Positions a dropless dispatch takes at once (0: all of them): a
    # longer prompt's expert FFN runs a chunk at a time, so that its
    # sorted rows and their un-sorted copy (positions x top-k x hidden
    # each) are a chunk's and not the prompt's.
    moe_dispatch_chunk: int = 0
    # A learned sparse-attention INDEXER in front of every attention layer
    # (models/indexer.py; served only, dense plane), on with index_topk >
    # 0: index_heads index queries of index_head_dim and ONE index key of
    # that width a token, which the serving cache keeps beside K and V; a
    # query attends the index_topk keys its index scores rank highest.
    index_heads: int = 0
    index_head_dim: int = 0
    index_topk: int = 0
    # A DECODER-HYBRID-DECODER (SambaY, arXiv:2507.06607; served only,
    # dense plane), spelled by ``layer_types``: "mamba1" (a Mamba-1 mixer,
    # models/mamba1.py: ``ssm_inner`` channels of ``ssm_state`` state
    # dimensions each, ``dt`` of rank ``ssm_dt_rank``; ssm_conv, ssm_chunk
    # and ssm_state_dtype as for Mamba-2), "gmu" (a gated memory unit: no
    # state, no token mixing; it gates the scan output of the nearest
    # Mamba-1 layer before it, which rides the walk's carry) and "cross" (a
    # query and an output projection alone: it attends the rows of the K/V
    # layer, the last "attention" layer before the first cross layer, and
    # keeps none).  From the K/V layer on, only gmu and cross layers
    # follow, so a prefill runs those layers at each row's last position
    # alone (``layer_walk``).
    ssm_inner: int = 0
    ssm_dt_rank: int = 0
    # KDA layers (kind "kda", models/kda.py; served only, dense plane): a
    # gated delta-rule linear attention of kda_heads heads of kda_head_dim,
    # whose state is a (kda_head_dim, kda_head_dim) matrix a head (stored
    # as ssm_state_dtype), behind a causal conv of kda_conv taps; its decay
    # and output gates are low-rank, of rank kda_gate_rank; the prefill
    # runs the rule kda_chunk positions at a time.
    kda_heads: int = 0
    kda_head_dim: int = 128
    kda_conv: int = 4
    kda_gate_rank: int = 128
    kda_chunk: int = 64
    # POWER-RETENTION layers (kind "power", models/power_retention.py;
    # served only, dense plane): a squared-product linear attention of
    # n_heads query heads in groups over n_kv_heads key/value heads of
    # head_dim, q and k normed a head and rotated (``rope``) on the way in;
    # a key/value head's state is the symmetric square of its keys against
    # its values, head_dim (head_dim + 1) / 2 rows of head_dim, and a
    # normaliser of as many (stored as ssm_state_dtype), decayed by one
    # gate a head and position, log sigmoid of a linear map (no bias: a
    # checkpoint's gate is its weights alone); the prefill runs the
    # recurrence power_chunk positions at a time.  power_gate_shift, for
    # RANDOM weights alone, shifts the gate's pre-activation by a constant
    # a head, its two ends and evenly between (power_retention.init_params
    # says what of a trained gate it stands for).  A model of these layers
    # alone holds no K/V.
    power_chunk: int = 256
    power_gate_shift: Optional[Tuple[float, float]] = None
    power_eps: float = 1e-6
    # An output GATE on every attending layer: what the output projection
    # reads is multiplied by ``sigmoid(W_gate h)``, h the layer's normed
    # input, a value an attention output (leaf ``w_attn_gate``; served
    # only).
    attn_gate: bool = False
    # DIFFERENTIAL attention (arXiv:2410.05258) in every attending layer:
    # query and key heads come in pairs, a pair of value heads is one value
    # of 2 x head_dim, two softmaxes are subtracted and the difference is
    # normed (``diff_combine``).  K and V are then kept two heads a row
    # (``kv_row_heads`` rows of ``kv_row_dim``), queries padded with zeros
    # to that width on the side of their own key, so that every attention
    # here computes a pair's two softmaxes as grouped-query heads.
    diff_attention: bool = False
    # LayerNorm (mean removed, a weight and a bias) at every norm in place
    # of RMSNorm, and biases on the attention projections.
    layer_norm: bool = False
    attn_bias: bool = False
    # SANDWICH norms (Trinity / AFMoE, Gemma 2): an RMSNorm on each
    # branch's OUTPUT as well, before it is added to the stream -- ``x +
    # post_attn_norm(attn W_o)``, ``x + post_mlp_norm(ffn)`` (leaves beside
    # ``attn_norm`` / ``mlp_norm``, a weight of hidden_size a layer each).
    sandwich_norm: bool = False
    # A part's first layer's index among all (``parts`` sets it): what a
    # differential layer's lambda_init is a function of.
    layer_offset: int = 0

    def __post_init__(self):
        # a configuration file's lists and type names, made hashable
        object.__setattr__(self, "layer_pattern", tuple(self.layer_pattern))
        object.__setattr__(self, "layer_types", tuple(
            "attention" if kind == "full_attention" else kind
            for kind in self.layer_types))
        object.__setattr__(self, "nope_kinds", tuple(self.nope_kinds))
        object.__setattr__(self, "moe_held", tuple(self.moe_held))
        if self.power_gate_shift is not None:
            object.__setattr__(self, "power_gate_shift",
                               tuple(self.power_gate_shift))
        if self.block_pattern:
            kinds, _ = self._blocks_to_layers()
            if self.layer_pattern or self.layer_types not in ((), kinds):
                raise ValueError("block_pattern stands in place of "
                                 "layer_pattern and layer_types")
            object.__setattr__(self, "layer_types", kinds)
            if "E" in self.block_pattern and self.moe_experts < 1:
                raise ValueError("an 'E' block is an expert feed-forward "
                                 "part: it needs moe_experts")
        if self.no_ffn and (self.block_pattern or self.layer_types
                            or self.first_dense_layers or self.moe_experts):
            raise ValueError("no_ffn is what parts() says of a block_pattern"
                             "'s mixer-only layer, a plain stack without "
                             "experts: spell the model as a block_pattern")
        if isinstance(self.rope_scaling, dict):
            object.__setattr__(self, "rope_scaling",
                               tuple(sorted(self.rope_scaling.items())))
        for name in ("dtype", "stream_dtype", "ssm_state_dtype"):
            if isinstance(getattr(self, name), str):
                object.__setattr__(self, name,
                                   jnp.dtype(getattr(self, name)).type)
        kinds = set(self.layer_pattern) | set(self.layer_types)
        if kinds - set(LAYER_KINDS):
            raise ValueError(
                f"layer_pattern: unknown kinds {kinds - set(LAYER_KINDS)} "
                f"(choose from {LAYER_KINDS})")
        if self.layer_types:
            if self.layer_pattern or len(self.layer_types) != self.n_layers:
                raise ValueError(
                    f"layer_types names each of the n_layers="
                    f"{self.n_layers} layers (it has "
                    f"{len(self.layer_types)}) and stands in place of "
                    f"layer_pattern")
        elif (self.n_layers - self.first_dense_layers) % len(self.period):
            raise ValueError(
                f"n_layers={self.n_layers} is not a whole number of "
                f"periods of {len(self.period)} layers")
        self._check_latent_and_share()
        if "mamba" in kinds and (
                self.ssm_heads < 1 or self.ssm_groups < 1
                or self.ssm_heads % self.ssm_groups):
            raise ValueError("a mamba layer needs ssm_heads, a whole number "
                             "of them an ssm_groups group")
        if "conv" in kinds and self.conv_taps < 2:
            raise ValueError("a conv layer needs conv_taps of 2 or more")
        if "window" in kinds and self.window_size < 1:
            raise ValueError("a window layer needs window_size")
        if "mamba1" in kinds and min(self.ssm_inner, self.ssm_dt_rank) < 1:
            raise ValueError("a mamba1 layer needs ssm_inner and "
                             "ssm_dt_rank")
        if "kda" in kinds and (
                min(self.kda_heads, self.kda_gate_rank) < 1
                or self.kda_conv < 2 or self.kda_chunk % 8):
            raise ValueError("a kda layer needs kda_heads, kda_gate_rank, "
                             "kda_conv of 2 or more and a kda_chunk of "
                             "whole sublane tiles")
        if "power" in kinds and (
                self.head_dim % 2 or self.power_chunk % 8
                or len(self.power_gate_shift or (0, 0)) != 2
                or self.n_heads % self.n_kv_heads or self.kv_lora_rank
                or self.index_topk or self.block_pattern):
            raise ValueError(
                "a power layer is built at degree 2 (the symmetric square "
                "of a key of an even head_dim), with a power_chunk of whole "
                "sublane tiles, no power_gate_shift or its two ends, whole "
                "groups of query heads, and neither a latent cache, an "
                "indexer nor a block_pattern beside it")
        if len(kinds & set(STATE_KINDS)) > 1:
            raise ValueError(
                "mamba, mamba1, conv, kda and power layers keep their states "
                "under the same leaves of the serving cache (ssm, conv): they "
                "do not mix, one kind of state-keeping layer a model")
        if "window" in kinds and kinds & {"mamba", "conv", "kda", "power"}:
            raise ValueError(
                "window rings beside a recurrent or conv state are built "
                "and held to a reference for Mamba-1 layers alone (a "
                "decoder-hybrid-decoder): window and mamba, conv, kda or "
                "power layers do not mix")
        self._check_cross_decoder(kinds)
        if self.qk_norm and self.qk_head_norm:
            raise ValueError("qk_norm is over the whole projection, "
                             "qk_head_norm over a head: choose one")
        if self.sandwich_norm and (
                self.layer_norm or kinds - {"attention", "window"}):
            raise ValueError("sandwich_norm is built as RMSNorm on the "
                             "branches of attention and window layers")
        if self.moe_router_score not in ("softmax", "sigmoid"):
            raise ValueError(f"moe_router_score {self.moe_router_score!r}")
        if self.moe_groups and (self.moe_router_score != "softmax"
                                or self.moe_router_bias):
            raise ValueError("group-limited routing is built for a softmax "
                             "router without a selection bias")
        if set(self.nope_kinds) - set(ATTENDING_KINDS):
            raise ValueError(f"nope_kinds: choose from {ATTENDING_KINDS}")
        if self.moe_router_input not in ("ffn", "layer"):
            raise ValueError(f"moe_router_input {self.moe_router_input!r}")
        if self.moe_activation not in ("silu", "relu", "relu2"):
            raise ValueError(f"moe_activation {self.moe_activation!r}")

    def _blocks_to_layers(self):
        """``block_pattern`` as ``(a kind a layer, the indices of the layers
        without a feed-forward half)``."""
        kinds, bare = [], []
        mixers = {"M": "mamba", "*": "attention"}
        blocks = self.block_pattern
        for i, b in enumerate(blocks):
            if b in mixers:
                if blocks[i + 1:i + 2] != "E":
                    bare.append(len(kinds))
                kinds.append(mixers[b])
            elif b != "E" or i == 0 or blocks[i - 1] == "E":
                raise ValueError(
                    f"block_pattern {blocks!r}: a block is 'M', '*' or an "
                    f"'E' right after a mixer (a dense '-' block and an "
                    f"expert block after another are not built)")
        return tuple(kinds), tuple(bare)

    def _check_cross_decoder(self, kinds):
        """Refuse a cross or gmu layer with nothing before it to read, and
        what no prefill here computes of a decoder-hybrid-decoder."""
        if self.diff_attention and (
                self.kv_lora_rank or self.index_topk or self.qk_norm
                or self.qk_head_norm or self.rope or self.n_kv_heads % 2
                or self.n_heads % (2 * self.n_kv_heads)):
            raise ValueError(
                "differential attention is built for plain NoPE heads in "
                "pairs (rope=False, an even n_kv_heads, n_heads a multiple "
                "of 2 x n_kv_heads), without q/k norms, latent attention "
                "or an indexer")
        if (self.attn_bias or "cross" in kinds) and not self.diff_attention:
            raise ValueError("biased attention projections and cross "
                             "layers are built for differential attention "
                             "alone (diff_attention)")
        if not kinds & {"gmu", "cross"}:
            return
        if not self.layer_types:
            # (a part's own config: the whole model's list was checked)
            return
        types, kv = self.layer_types, self.kv_layer
        if kv is None:
            raise ValueError(
                "a cross layer attends the rows of an attention layer "
                "before it, and a gmu layer belongs to such a cross-"
                "decoder: there is no attention layer before a cross layer")
        if "gmu" in types and ("mamba1" not in types[:kv]
                               or "gmu" in types[:kv]):
            raise ValueError("a gmu layer gates the scan output of a "
                             "mamba1 layer of the self-decoder, before the "
                             "K/V layer: there is none, or the gmu layer "
                             "lies before it too")
        tail = set(types[kv + 1:]) - {"gmu", "cross"}
        if tail:
            raise ValueError(
                f"after the K/V layer only gmu and cross layers follow: a "
                f"prefill runs them at each row's last position alone, so "
                f"{sorted(tail)} there would miss every other position")
        if self.first_dense_layers or self.moe_experts:
            raise ValueError("a decoder-hybrid-decoder with experts or "
                             "leading dense layers is not built")

    def _check_latent_and_share(self):
        """Refuse what no program here computes of latent attention,
        leading dense layers and an expert share."""
        patterned = bool(self.layer_types) \
            or self.period != ("attention",)
        if self.kv_lora_rank:
            if patterned:
                raise ValueError(
                    "latent attention keeps one latent row a token and "
                    "layer: no serving cache holds it beside window rings "
                    "or recurrent states (layer_pattern must be empty)")
            if min(self.q_lora_rank, self.qk_nope_head_dim,
                   self.qk_rope_head_dim, self.v_head_dim) < 1:
                raise ValueError(
                    "latent attention needs q_lora_rank, qk_nope_head_dim, "
                    "qk_rope_head_dim and v_head_dim (a model without "
                    "query compression is not built)")
            if self.head_dim != self.qk_nope_head_dim + self.qk_rope_head_dim:
                raise ValueError(
                    f"head_dim={self.head_dim} is not qk_nope_head_dim + "
                    f"qk_rope_head_dim")
            if (self.qk_norm or self.qk_head_norm or not self.rope
                    or self.nope_kinds):
                raise ValueError("latent attention has its own norms and "
                                 "always rotates its rope part")
        if self.index_topk:
            if patterned or self.kv_lora_rank or self.first_dense_layers:
                raise ValueError(
                    "an indexer keeps one index key a token and layer "
                    "beside K and V: no serving cache holds that pool "
                    "beside window rings, recurrent states or a latent "
                    "row (one stack of attention layers only)")
            if min(self.index_heads, self.index_head_dim) < 1:
                raise ValueError("an indexer needs index_heads and "
                                 "index_head_dim")
        if self.rope_scaling is not None:
            kind = dict(self.rope_scaling).get("type")
            if kind != "yarn":
                raise ValueError(f"rope_scaling type {kind!r}: only "
                                 f"'yarn' is built")
        if self.first_dense_layers:
            if (patterned and not self.layer_types) or self.moe_experts == 0:
                raise ValueError(
                    "first_dense_layers is a prologue before a stack of "
                    "expert layers of one kind, or the first entries of "
                    "layer_types")
            if not 0 < self.first_dense_layers < self.n_layers:
                raise ValueError("first_dense_layers must leave a layer")
        if self.moe_groups:
            if (self.moe_experts % self.moe_groups
                    or not 0 < self.moe_top_groups <= self.moe_groups):
                raise ValueError(
                    f"moe_groups={self.moe_groups} must divide "
                    f"moe_experts={self.moe_experts} and hold "
                    f"moe_top_groups={self.moe_top_groups}")
        if self.moe_held:
            first, count = self.moe_held
            if not (0 <= first and 0 < count
                    and first + count <= self.moe_experts):
                raise ValueError(
                    f"moe_held={self.moe_held} is not (first, count) "
                    f"within moe_experts={self.moe_experts}")

    @property
    def period(self) -> Tuple[str, ...]:
        """The kinds of one period of layers."""
        if self.layer_types:
            raise ValueError("a config with layer_types has a period a "
                             "part (parts())")
        return self.layer_pattern or ("attention",)

    @property
    def period_len(self) -> int:
        return len(self.period)

    def layers_of(self, kind: str) -> int:
        """How many of the n_layers are of ``kind``."""
        if self.layer_types:
            return self.layer_types.count(kind)
        return (self.n_layers // self.period_len) * self.period.count(kind)

    def layers_before(self, layer: int, kind: str) -> int:
        """How many of the layers before ``layer`` keep their cache rows
        or state where a layer of ``kind`` does: where a part's first
        layer of that kind lies in the cache's stack."""
        if not self.layer_types:
            # one kind of layer behind leading dense ones of the same kind
            return layer
        return self.layer_types[:layer].count(kind)

    def parts(self):
        """The layer stacks a walk runs in turn, ``(config of the part,
        its key in params, its first layer's index among all)``: the
        leading dense layers, if any, as a dense model of that many layers
        (``params["dense_layers"]``), then the scanned stack
        (``params["layers"]``).  A part's config is a plain one, whole
        periods of one ``layer_pattern``: the code that walks a stack
        never asks which part it is in.  A config with ``layer_types`` is
        cut into as many parts as its list has runs of whole periods
        (``_runs``): further dense parts are ``dense_layers_1`` ..., further
        expert parts ``layers_1`` ... ."""
        k = self.first_dense_layers
        if not k and not self.layer_types:
            return [(self, "layers", 0)]
        # (leading dense layers without a list: a stack of attention layers)
        kinds = self.layer_types or ("attention",) * self.n_layers
        dense = dict(moe_experts=0, moe_shared_size=0, moe_groups=0,
                     moe_top_groups=0, moe_held=(), moe_router_bias=False,
                     moe_latent_size=0)
        out = []
        # a layer without a feed-forward half is a stack of its own
        bare = self._blocks_to_layers()[1] if self.block_pattern else ()
        for key, first, stack, fields in (("dense_layers", 0, kinds[:k], dense),
                                          ("layers", k, kinds[k:], {})):
            cuts = () if self.kv_layer is None else tuple(
                self.kv_layer - first + i for i in (0, 1))
            cuts += tuple(b - first + i for b in bare for i in (0, 1))
            for i, (start, pattern, periods) in enumerate(
                    _runs(stack, cuts)):
                if self.diff_attention:
                    fields = {**fields, "layer_offset": first + start}
                if bare:
                    alone = first + start in bare
                    fields = {**(dense if alone else {}), "no_ffn": alone}
                out.append((dataclasses.replace(
                    self, n_layers=len(pattern) * periods, layer_types=(),
                    layer_pattern=() if pattern == ("attention",)
                    else pattern, first_dense_layers=0, block_pattern="",
                    **fields),
                    key if i == 0 else f"{key}_{i}", first + start))
        return out

    @property
    def kv_layer(self) -> Optional[int]:
        """The index of a decoder-hybrid-decoder's K/V layer: the last
        attention layer before the first cross layer, whose rows the
        cross layers attend.  None for every other model (and for a part's
        own config: the walks ask the whole model's)."""
        if "cross" not in self.layer_types:
            return None
        before = self.layer_types[:self.layer_types.index("cross")]
        return next((i for i in reversed(range(len(before)))
                     if before[i] == "attention"), None)

    @property
    def kv_heads_a_row(self) -> int:
        """Kv heads a stored row holds: 2 under differential attention
        (a pair's keys side by side, throughout) and for an even number of
        heads of HALF a 128-lane row (64), which a serving pool keeps two
        a row (``llama_serve.init_cache``: the same bytes in the same
        order as by position, in rows the decode kernel reads); 1 else."""
        return 2 if self.diff_attention or (
            self.head_dim == 64 and self.n_kv_heads % 2 == 0) else 1

    @property
    def kv_as_rows(self) -> bool:
        """Whether a per-slot serving pool keeps K and V as the ROWS the
        decode kernel reads, ``(La, B, positions * kv_row_heads,
        kv_row_dim)``, and not by position and head: heads of 64 two a row,
        and whole-lane heads too few to fill a sublane tile (2 of 128:
        stored ``(..., S, 2, 128)`` the chip pads the 2 to a tile of 16, 8 x
        the bytes, and Mosaic cannot read them) beside a state or another
        pool.  A plain decoder's pool stays by position, which is also what
        ``forward_with_cache`` and the paged planes' gathered blocks are."""
        return self.kv_heads_a_row > 1 or (
            self.head_dim % 128 == 0 and self.n_kv_heads % 8 != 0
            and not self.one_kv_stack)

    @property
    def kv_row_heads(self) -> int:
        """K (or V) rows a position keeps a layer, as stored and attended:
        the kv heads, or the PAIRS of them (``kv_heads_a_row``)."""
        return self.n_kv_heads // self.kv_heads_a_row

    @property
    def kv_row_dim(self) -> int:
        """Their width: the head, or a pair's two heads side by side."""
        return self.kv_heads_a_row * self.head_dim

    @property
    def expert_width(self) -> int:
        return self.moe_intermediate_size or self.intermediate_size

    @property
    def held_experts(self) -> Tuple[int, int]:
        """``(first, count)`` of the experts whose matrices are here."""
        return self.moe_held or (0, self.moe_experts)

    @property
    def rope_dim(self) -> int:
        """The width RoPE turns: the head, or a latent head's rope part."""
        return self.qk_rope_head_dim or self.head_dim

    @property
    def latent_row(self) -> int:
        """Values a token and layer keeps in a latent cache, as STORED:
        kv_lora_rank + qk_rope_head_dim (576 for DeepSeek-V2) padded with
        zeros to whole lanes (640).  The chip lays a 576-wide minor
        dimension out as 640 whatever the leaf says (Mosaic reads the pool
        as ``...x640``, and refuses a 576-wide slice of it), so the leaf
        says what it occupies."""
        return -(-(self.kv_lora_rank + self.qk_rope_head_dim) // 128) * 128

    @property
    def o_dim(self) -> int:
        """What the output projection reads: every head's values."""
        return self.n_heads * (self.v_head_dim or self.head_dim)

    def attending_layers(self) -> int:
        """How many of the n_layers attend (hold ``ATTENTION_LEAVES``)."""
        return sum(self.layers_of(kind) for kind in ATTENDING_KINDS)

    def ropes(self, kind: str) -> bool:
        """Whether an attending layer of ``kind`` rotates q and k."""
        return self.rope and kind not in self.nope_kinds

    @property
    def one_kv_stack(self) -> bool:
        """Every layer keeps K and V rows by position, in one stack, and
        attends all of them (what ``forward_with_cache`` holds): no other
        kind, list, latent cache or indexer."""
        return not (self.layer_types or self.kv_lora_rank or self.index_topk
                    or self.diff_attention
                    or any(self.layers_of(kind) for kind in LAYER_KINDS
                           if kind != "attention"))

    @property
    def plain_decoder(self) -> bool:
        """What ``llama.forward`` TRAINS; any other config is served only.
        ``forward`` goes through the walk that serves them
        (``walk_layers``), which computes most of the refused terms too:
        they are refused because no test and no cell holds their backward,
        not for want of a code path (ROADMAP Queue 2).  Trained: layers of
        softmax attention, full or over a window, in one pattern or a
        ``layer_types`` list with leading dense layers; RoPE, NoPE or one
        a kind; q/k norms; an output gate; sandwich norms; an embedding
        multiplier; SwiGLU or routed experts (softmax or sigmoid scores, a
        selection bias, a shared expert, a held share)."""
        return not (
            self.kv_lora_rank or self.index_topk or self.diff_attention
            or any(self.layers_of(kind) for kind in LAYER_KINDS
                   if kind not in ("attention", "window"))
            or self.attention_multiplier is not None
            or self.logits_scaling != 1.0
            or self.moe_router_input != "ffn"
            or self.rope_scaling is not None
            or self.layer_norm or self.attn_bias)

    @property
    def one_stage_stack(self) -> bool:
        """What a PIPELINE stage runs (``llama_pipeline``): a trained
        config whose layers are one stack of one kind, which a stage
        slices by layer."""
        return self.plain_decoder and self.one_kv_stack \
            and not self.first_dense_layers

    @property
    def attn_scale(self) -> float:
        if self.attention_multiplier is not None:
            return self.attention_multiplier
        if self.rope_scaling is not None:
            # DeepSeek's YaRN: the scores carry mscale(all dims) squared
            scaling = dict(self.rope_scaling)
            return self.head_dim ** -0.5 * yarn_mscale(
                scaling["factor"], scaling.get("mscale_all_dim", 0)) ** 2
        return self.head_dim ** -0.5

    @property
    def q_dim(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.n_kv_heads * self.head_dim

    @classmethod
    def debug(cls, **kw) -> "LlamaConfig":
        """Tiny config for tests/CI (runs on CPU in <1s)."""
        base = dict(vocab_size=256, hidden_size=64, n_layers=2, n_heads=4,
                    n_kv_heads=2, head_dim=16, intermediate_size=128,
                    max_seq_len=128, rope_theta=10000.0, remat=False,
                    tie_embeddings=True)
        base.update(kw)
        return cls(**base)

    @classmethod
    def moe_debug(cls, **kw) -> "LlamaConfig":
        """Tiny MoE config (expert-parallel dryruns/tests on CPU)."""
        base = dict(moe_experts=4, moe_top_k=2)
        base.update(kw)
        return cls.debug(**base)

    @classmethod
    def hybrid_debug(cls, **kw) -> "LlamaConfig":
        """Tiny hybrid of Granite 4.0-H's shape (tests, ``chip_smoke``):
        two periods of (mamba, mamba, attention), NoPE, the four Granite
        multipliers, prefill chunks of 8."""
        base = dict(n_layers=6, layer_pattern=("mamba", "mamba",
                                               "attention"),
                    rope=False, attention_multiplier=1.0 / 16,
                    embedding_multiplier=12.0, residual_multiplier=0.22,
                    logits_scaling=8.0, ssm_heads=4, ssm_head_dim=16,
                    ssm_state=16, ssm_chunk=8)
        base.update(kw)
        return cls.debug(**base)

    @classmethod
    def llama_moe_1b(cls, **kw) -> "LlamaConfig":
        """Switch-style MoE bench model: 8 experts over the 440M dense
        trunk (~1.6B total params, ~440M active/token)."""
        base = dict(vocab_size=32000, hidden_size=1024, n_layers=24,
                    n_heads=8, n_kv_heads=8, head_dim=128,
                    intermediate_size=4096, max_seq_len=2048,
                    rope_theta=10000.0, tie_embeddings=True,
                    attention_impl="flash", moe_experts=8, moe_top_k=2)
        base.update(kw)
        return cls(**base)

    @classmethod
    def llama_125m(cls, **kw) -> "LlamaConfig":
        base = dict(vocab_size=32000, hidden_size=768, n_layers=12,
                    n_heads=6, n_kv_heads=6, head_dim=128,
                    intermediate_size=2048, max_seq_len=2048,
                    rope_theta=10000.0, tie_embeddings=True)
        base.update(kw)
        return cls(**base)

    @classmethod
    def llama_440m(cls, **kw) -> "LlamaConfig":
        """Single-chip bench model: largest config that trains with
        f32 adam state in 16 GB HBM (measured on v5e).

        head_dim is 128, NOT the GPU-lineage 64: every (…, head_dim)
        tensor tiles the TPU's (8,128) layout exactly (64 pads 2x in
        HBM) and QK^T runs the MXU at full systolic depth.  Measured
        v5e, identical param count: 32.7k tok/s @ 43.4% MFU vs 24.6k @
        32.6% with 16 heads x 64.  remat_policy='attn' saves the flash
        kernel's residuals so backward never re-runs attention."""
        base = dict(vocab_size=32000, hidden_size=1024, n_layers=24,
                    n_heads=8, n_kv_heads=8, head_dim=128,
                    intermediate_size=4096, max_seq_len=2048,
                    rope_theta=10000.0, tie_embeddings=True,
                    attention_impl="flash", remat_policy="attn")
        base.update(kw)
        return cls(**base)

    @classmethod
    def llama2_7b(cls, **kw) -> "LlamaConfig":
        base = dict(vocab_size=32000, hidden_size=4096, n_layers=32,
                    n_heads=32, n_kv_heads=32, head_dim=128,
                    intermediate_size=11008, max_seq_len=4096,
                    rope_theta=10000.0)
        base.update(kw)
        return cls(**base)

    @classmethod
    def llama3_8b(cls, **kw) -> "LlamaConfig":
        base = dict(vocab_size=128256, hidden_size=4096, n_layers=32,
                    n_heads=32, n_kv_heads=8, head_dim=128,
                    intermediate_size=14336, max_seq_len=8192,
                    rope_theta=500000.0)
        base.update(kw)
        return cls(**base)


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------

def param_logical_axes(config: LlamaConfig) -> Dict[str, Any]:
    """Pytree (matching init_params) of per-dim logical axis names."""
    if config.first_dense_layers or config.layer_types:
        parts = {key: param_logical_axes(part)
                 for part, key, _ in config.parts()}
        return {**parts["layers"],
                **{key: axes["layers"] for key, axes in parts.items()}}
    if config.no_ffn:
        ffn_axes = {}
    elif config.moe_experts > 0:
        ffn_axes = {
            "router": ("layers", None, "expert"),
            "w_gate": ("layers", "expert", "embed", "mlp"),
            "w_up": ("layers", "expert", "embed", "mlp"),
            "w_down": ("layers", "expert", "mlp", "embed"),
        }
        if config.moe_latent_size:
            ffn_axes.update(w_lat_in=("layers", "embed", None),
                            w_lat_out=("layers", None, "embed"))
    else:
        ffn_axes = {
            "w_gate": ("layers", "embed", "mlp"),
            "w_up": ("layers", "embed", "mlp"),
            "w_down": ("layers", "mlp", "embed"),
        }
    axes = {
        "embed_tokens": ("vocab", "embed"),
        "layers": {
            "attn_norm": ("layers", None),
            "wq": ("layers", "embed", "heads"),
            "wk": ("layers", "embed", "kv_heads"),
            "wv": ("layers", "embed", "kv_heads"),
            "wo": ("layers", "heads", "embed"),
            "mlp_norm": ("layers", None),
            **ffn_axes,
        },
        "final_norm": (None,),
    }
    if config.no_ffn:
        del axes["layers"]["mlp_norm"]
    if config.qk_norm or config.qk_head_norm:
        axes["layers"]["q_norm"] = ("layers", None)
        axes["layers"]["k_norm"] = ("layers", None)
    if config.sandwich_norm:
        axes["layers"]["post_attn_norm"] = ("layers", None)
        axes["layers"]["post_mlp_norm"] = ("layers", None)
    if config.moe_router_bias:
        axes["layers"]["router_bias"] = ("layers", "expert")
    if config.moe_shared_size:
        axes["layers"].update(
            ws_gate=("layers", "embed", "mlp"),
            ws_up=("layers", "embed", "mlp"),
            ws_down=("layers", "mlp", "embed"))
    if config.moe_experts > 0 and not expert_config(config).gated:
        for name in ("w_gate", "ws_gate"):
            axes["layers"].pop(name, None)
    if config.kv_lora_rank:
        for name in ("wq", "wk", "wv"):
            del axes["layers"][name]
        axes["layers"].update(
            wq_a=("layers", "embed", None), q_a_norm=("layers", None),
            wq_b=("layers", None, "heads"),
            wkv_a=("layers", "embed", None), kv_a_norm=("layers", None),
            wk_b=("layers", "heads", None, None),
            wv_b=("layers", "heads", None, None))
    if config.index_topk:
        from ray_tpu.models import indexer

        axes["layers"].update(indexer.param_axes(config))
    if config.layers_of("mamba"):
        from ray_tpu.models import mamba2

        axes["layers"].update(mamba2.param_axes(config))
    if config.layers_of("conv"):
        from ray_tpu.models import shortconv

        axes["layers"].update(shortconv.param_axes(config))
    if config.layers_of("mamba1"):
        from ray_tpu.models import mamba1

        axes["layers"].update(mamba1.param_axes(config))
    if config.layers_of("kda"):
        from ray_tpu.models import kda

        axes["layers"].update(kda.param_axes(config))
    if config.layers_of("power"):
        from ray_tpu.models import power_retention

        axes["layers"].update(power_retention.param_axes(config))
    if config.attn_gate:
        axes["layers"]["w_attn_gate"] = ("layers", "embed", "heads")
    if config.layers_of("gmu"):
        axes["layers"].update(gmu_in=("layers", "embed", "mlp"),
                              gmu_out=("layers", "mlp", "embed"))
    if config.attn_bias:
        axes["layers"].update(bq=("layers", "heads"),
                              bk=("layers", "kv_heads"),
                              bv=("layers", "kv_heads"), bo=("layers", None))
    if config.diff_attention:
        axes["layers"].update({name: ("layers", None)
                               for name in DIFF_LEAVES})
    if config.layer_norm:
        axes["layers"].update(attn_norm_bias=("layers", None),
                              mlp_norm_bias=("layers", None))
        axes["final_norm_bias"] = (None,)
    for name in _absent_attention_leaves(config):
        axes["layers"].pop(name, None)
    if not config.tie_embeddings:
        axes["lm_head"] = ("embed", "vocab")
    return axes


def _absent_attention_leaves(config: LlamaConfig):
    """The ``ATTENTION_LEAVES`` a plain stack does not hold: all of them
    where no layer attends (a scan slices every leaf of its stack), the
    key and value projections where every attending layer is a cross
    layer."""
    if not config.attending_layers():
        return ATTENTION_LEAVES
    if config.layers_of("cross") == config.attending_layers():
        return ("wk", "wv", "bk", "bv")
    return ()


def init_dense(key, shape, fan_in, dtype=jnp.float32):
    """Truncated-normal fan-in-scaled initializer shared across model
    families (llama, moe)."""
    scale = fan_in ** -0.5
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape,
                                        jnp.float32) * scale).astype(dtype)


def init_params(rng: jax.Array, config: LlamaConfig,
                dtype: Any = jnp.float32) -> PyTree:
    """Initialize the stacked-layer param pytree (truncated-normal,
    fan-in scaled; norms at 1).  A leaf that every layer has (the two
    norms, the FFN) is stacked over all n_layers; one that only a kind
    of layer has (``ATTENTION_LEAVES``, a Mamba mixer's ``ssm_*``) over
    the layers of that kind, in their order."""
    c = config
    keys = jax.random.split(rng, 8)

    def dense(key, shape, fan_in):
        return init_dense(key, shape, fan_in, dtype)

    if c.first_dense_layers or c.layer_types:
        # Each part is drawn as the plain model its config is: ``layers``
        # (with the embedding, the final norm and the head) from the key
        # itself, every other part from a key of its own.
        parts = {key: part for part, key, _ in c.parts()}
        params = init_params(rng, parts.pop("layers"), dtype)
        for i, (key, part) in enumerate(parts.items()):
            params[key] = init_params(
                jax.random.fold_in(rng, 97 if key == "dense_layers"
                                   else 970 + i), part, dtype)["layers"]
        return params
    L, La = c.n_layers, c.attending_layers()
    if c.no_ffn:
        ffn = {}
    elif c.moe_experts > 0:
        # The router scores every expert; the matrices are those of the
        # experts held here (all of them unless ``moe_held``), as wide as
        # the rows they read: the stream's, or the latent's.
        E, (_, Eh), F = c.moe_experts, c.held_experts, c.expert_width
        Din = c.moe_latent_size or c.hidden_size
        ffn = {
            "router": dense(keys[5], (L, c.hidden_size, E), c.hidden_size),
            "w_up": dense(jax.random.fold_in(keys[6], 1),
                          (L, Eh, Din, F), Din),
            "w_down": dense(keys[7], (L, Eh, F, Din), F),
        }
        if expert_config(c).gated:
            ffn["w_gate"] = dense(keys[6], (L, Eh, Din, F), Din)
        if c.moe_latent_size:
            kl = jax.random.split(jax.random.fold_in(rng, 76), 2)
            ffn.update(
                w_lat_in=dense(kl[0], (L, c.hidden_size, Din),
                               c.hidden_size),
                w_lat_out=dense(kl[1], (L, Din, c.hidden_size), Din))
        if c.moe_shared_size:
            Fs, ks = c.moe_shared_size, jax.random.split(
                jax.random.fold_in(rng, 96), 3)
            ffn.update(
                ws_up=dense(ks[1], (L, c.hidden_size, Fs), c.hidden_size),
                ws_down=dense(ks[2], (L, Fs, c.hidden_size), Fs))
            if expert_config(c).gated:
                ffn["ws_gate"] = dense(ks[0], (L, c.hidden_size, Fs),
                                       c.hidden_size)
    else:
        ffn = {
            "w_gate": dense(keys[5], (L, c.hidden_size, c.intermediate_size),
                            c.hidden_size),
            "w_up": dense(keys[6], (L, c.hidden_size, c.intermediate_size),
                          c.hidden_size),
            "w_down": dense(keys[7], (L, c.intermediate_size, c.hidden_size),
                            c.intermediate_size),
        }
    embed_tokens = dense(keys[0], (c.vocab_size, c.hidden_size),
                         c.hidden_size)
    if c.embedding_multiplier != 1.0 and not c.sandwich_norm:
        # (sandwich norms: ``POST_NORM_INIT``.)
        # Drawn so that the embedding the LAYERS see (x the multiplier)
        # is the one every other configuration starts from.  Drawn at
        # that scale itself and tied to the head, a row's own logit is
        # multiplier x |row|^2 ahead of the rest and the model repeats
        # its input whatever its layers or its states compute (measured
        # at granite-4.0-h-micro's widths: PERF.md section 6, PR 30).
        embed_tokens = (embed_tokens.astype(jnp.float32)
                        / c.embedding_multiplier).astype(dtype)
    params = {
        "embed_tokens": embed_tokens,
        "layers": {
            "attn_norm": jnp.ones((L, c.hidden_size), dtype),
            "wq": dense(keys[1], (La, c.hidden_size, c.q_dim),
                        c.hidden_size),
            "wk": dense(keys[2], (La, c.hidden_size, c.kv_dim),
                        c.hidden_size),
            "wv": dense(keys[3], (La, c.hidden_size, c.kv_dim),
                        c.hidden_size),
            "wo": dense(keys[4], (La, c.q_dim, c.hidden_size), c.q_dim),
            "mlp_norm": jnp.ones((L, c.hidden_size), dtype),
            **ffn,
        },
        "final_norm": jnp.ones((c.hidden_size,), dtype),
    }
    if c.no_ffn:
        del params["layers"]["mlp_norm"]
    if c.qk_norm:
        params["layers"]["q_norm"] = jnp.ones((La, c.q_dim), dtype)
        params["layers"]["k_norm"] = jnp.ones((La, c.kv_dim), dtype)
    if c.qk_head_norm:
        params["layers"]["q_norm"] = jnp.ones((La, c.head_dim), dtype)
        params["layers"]["k_norm"] = jnp.ones((La, c.head_dim), dtype)
    if c.sandwich_norm:
        params["layers"]["post_attn_norm"] = jnp.full(
            (L, c.hidden_size), POST_NORM_INIT, dtype)
        params["layers"]["post_mlp_norm"] = jnp.full(
            (L, c.hidden_size), POST_NORM_INIT, dtype)
    if c.moe_experts > 0 and c.moe_router_bias:
        params["layers"]["router_bias"] = ROUTER_BIAS_STD * jax.random.normal(
            jax.random.fold_in(rng, 94), (L, c.moe_experts), jnp.float32)
    if c.kv_lora_rank:
        for name in ("wq", "wk", "wv", "wo"):
            del params["layers"][name]
        params["layers"].update(_init_latent_attention(
            jax.random.fold_in(rng, 95), c, La, dtype, dense))
    if c.index_topk:
        from ray_tpu.models import indexer

        params["layers"].update(indexer.init_params(
            jax.random.fold_in(rng, 92), c, La, dense))
    if c.layers_of("mamba"):
        from ray_tpu.models import mamba2

        params["layers"].update(mamba2.init_params(
            jax.random.fold_in(rng, 98), c, c.layers_of("mamba"), dtype,
            dense))
    if c.layers_of("conv"):
        from ray_tpu.models import shortconv

        params["layers"].update(shortconv.init_params(
            jax.random.fold_in(rng, 93), c, c.layers_of("conv"), dense))
    if c.layers_of("mamba1"):
        from ray_tpu.models import mamba1

        params["layers"].update(mamba1.init_params(
            jax.random.fold_in(rng, 91), c, c.layers_of("mamba1"), dtype,
            dense))
    if c.layers_of("kda"):
        from ray_tpu.models import kda

        params["layers"].update(kda.init_params(
            jax.random.fold_in(rng, 78), c, c.layers_of("kda"), dtype,
            dense))
    if c.layers_of("power"):
        from ray_tpu.models import power_retention

        params["layers"].update(power_retention.init_params(
            jax.random.fold_in(rng, 75), c, c.layers_of("power"), dtype,
            dense))
    if c.attn_gate:
        params["layers"]["w_attn_gate"] = dense(
            jax.random.fold_in(rng, 77), (La, c.hidden_size, c.o_dim),
            c.hidden_size)
    if c.layers_of("gmu"):
        Lg, kg = c.layers_of("gmu"), jax.random.split(
            jax.random.fold_in(rng, 90), 2)
        params["layers"].update(
            gmu_in=dense(kg[0], (Lg, c.hidden_size, c.ssm_inner),
                         c.hidden_size),
            gmu_out=dense(kg[1], (Lg, c.ssm_inner, c.hidden_size),
                          c.ssm_inner))

    def drawn(key, shape, std):
        return (std * jax.random.normal(jax.random.fold_in(rng, key), shape,
                                        jnp.float32)).astype(dtype)

    if c.attn_bias:
        params["layers"].update(
            bq=drawn(80, (La, c.q_dim), BIAS_STD),
            bk=drawn(81, (La, c.kv_dim), BIAS_STD),
            bv=drawn(82, (La, c.kv_dim), BIAS_STD),
            bo=drawn(83, (La, c.hidden_size), BIAS_STD))
    if c.diff_attention:
        params["layers"].update({
            name: drawn(84 + i, (La, c.head_dim), LAMBDA_STD)
            for i, name in enumerate(DIFF_LEAVES[:4])})
        params["layers"]["sub_norm"] = jnp.ones((La, 2 * c.head_dim), dtype)
    if c.layer_norm:
        params["layers"].update(
            attn_norm_bias=drawn(88, (L, c.hidden_size), BIAS_STD),
            mlp_norm_bias=drawn(89, (L, c.hidden_size), BIAS_STD))
        params["final_norm_bias"] = drawn(79, (c.hidden_size,), BIAS_STD)
    for name in _absent_attention_leaves(c):
        params["layers"].pop(name, None)
    if not c.tie_embeddings:
        params["lm_head"] = dense(
            jax.random.fold_in(rng, 99), (c.hidden_size, c.vocab_size),
            c.hidden_size)
    return params


def _init_latent_attention(rng, c: LlamaConfig, La: int, dtype, dense):
    """A latent attention's leaves over ``La`` layers (DeepSeek-V2's
    names beside ours): ``wq_a`` W_DQ, ``q_a_norm``, ``wq_b`` W_UQ (a
    head's columns: nope then rope), ``wkv_a`` W_DKV (columns: the
    kv_lora_rank compressed, then the rope part), ``kv_a_norm``, and W_UKV
    as the two matrices the absorbed decode multiplies by, a head apart:
    ``wk_b`` (H, nope, rank), a head's W_UK transposed, and ``wv_b`` (H,
    rank, v), its W_UV; ``wo`` reads H x v_head_dim."""
    ks = jax.random.split(rng, 6)
    D, H, R, Rq = c.hidden_size, c.n_heads, c.kv_lora_rank, c.q_lora_rank
    return {
        "wq_a": dense(ks[0], (La, D, Rq), D),
        "q_a_norm": jnp.ones((La, Rq), dtype),
        "wq_b": dense(ks[1], (La, Rq, H * c.head_dim), Rq),
        "wkv_a": dense(ks[2], (La, D, R + c.qk_rope_head_dim), D),
        "kv_a_norm": jnp.ones((La, R), dtype),
        "wk_b": dense(ks[3], (La, H, c.qk_nope_head_dim, R), R),
        "wv_b": dense(ks[4], (La, H, R, c.v_head_dim), R),
        "wo": dense(ks[5], (La, c.o_dim, D), c.o_dim),
    }


def param_count(params: PyTree) -> int:
    return sum(x.size for x in jax.tree.leaves(params))


# ---------------------------------------------------------------------------
# Building blocks
# ---------------------------------------------------------------------------

REMAT_POLICIES = ("full", "dots", "dots_saveable", "attn", "attn_ffn")


def _remat_policy(config: LlamaConfig):
    """Checkpoint policy for the per-layer remat wrapper (see
    LlamaConfig.remat_policy).  "attn_ffn" additionally saves the
    FFN activation ``silu(gate)*up`` next to the flash residuals —
    backward skips recomputing the two up-projection matmuls at
    +intermediate_size bf16/token of residual memory (the next sweep
    point past "attn" when HBM headroom allows)."""
    if config.remat_policy not in REMAT_POLICIES:
        raise ValueError(
            f"unknown remat_policy {config.remat_policy!r} "
            f"(choose from {REMAT_POLICIES})")
    if config.remat_policy in ("attn", "attn_ffn"):
        from ray_tpu.ops.flash_attention import FLASH_RESIDUAL_NAMES

        names = FLASH_RESIDUAL_NAMES
        if config.remat_policy == "attn_ffn":
            names = names + ("ffn_act",)
        return jax.checkpoint_policies.save_only_these_names(*names)
    return {
        "full": jax.checkpoint_policies.nothing_saveable,
        "dots": jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
        "dots_saveable": jax.checkpoint_policies.dots_saveable,
    }[config.remat_policy]


def matmul(x: jax.Array, w: jax.Array, out_dtype: Any = None) -> jax.Array:
    """bf16×bf16 matmul with float32 MXU accumulation.

    Measured on v5e: letting the accumulation type default to the
    operand dtype (bf16) drops XLA onto a ~4-5x slower path (26-42
    TF/s vs 139 TF/s with preferred_element_type=f32).  Always
    accumulate f32 and downcast explicitly.
    """
    out = jax.lax.dot_general(
        x, w, (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    return out.astype(out_dtype or x.dtype)


def scattered_grad_matmul(x: jax.Array, w: jax.Array,
                          w_axes: Tuple[str, str]) -> jax.Array:
    """``matmul(x, w)``, x (B, S, K) by w (K, N) of logical axes ``w_axes``,
    whose backward reduces the weight's gradient scattered under a mesh
    that shards one of w's dimensions over the batch's axes: each device
    receives only the part it keeps, and receives it while the matmul's
    own backward computes.  Training's head calls it, and a dense layer's
    output projection and three FFN matmuls; the q, k and v projections
    keep ``matmul``: exchanged by hand too the step read 3.9 ms slower
    than with XLA's three reduce-scatters (PERF.md section 6, PR 50).

    Left to itself XLA sums such a gradient in a reduce-scatter that runs
    alone on this backend whatever flag is set (inside the layers'
    backward loop, seven a layer), or, the head's, WHOLE on every device
    in an all-reduce that runs alone as well (PERF.md section 6, PRs 47
    and 50).  The one collective XLA's TPU backend leaves in flight beside
    compute is the collective-permute.  So every device forms its partial
    product ``x^T g`` in n blocks along the dimension the mesh shards --
    rows, blocks of x's last dimension, for a weight laid out ``("embed",
    ...)``; columns, blocks of g's last dimension, for ``(..., "embed")``
    -- one per device of the mesh axes that shard both that dimension and
    the batch, sends each other device its block, forms its own block
    while those travel, and adds what arrives: the same float32 partial
    products summed in float32, cast once after the sum, as the
    reduce-scatter had them.  The exchange is awaited where the input's
    gradient is, so that it travels under this matmul's own backward.

    Where there is nothing to scatter (no mesh, one device, a manual
    region, no dimension that the batch's axes shard, a width they do not
    divide) this is ``matmul`` and its own backward."""
    mesh = partitioning_mesh()
    if mesh is None or x.ndim != 3:
        return matmul(x, w)
    rules = current_rules()
    batch, seq = rules.axes(("batch", "seq"))
    summed = tuple(a for a in batch + seq if mesh.shape[a] > 1)
    sharded = rules.axes(w_axes)
    side = 0 if any(a in summed for a in sharded[0]) else 1
    scattered = tuple(a for a in sharded[side] if a in summed)
    n = math.prod(mesh.shape[a] for a in scattered)
    if n == 1 or w.shape[side] % n:
        return matmul(x, w)
    whole = tuple(a for a in summed if a not in scattered)
    width = w.shape[side] // n
    # The operands as the activations are laid out: the batch's axes on the
    # rows; the dimension that is not scattered keeps what is left of the
    # weight's axes (``tensor``, under fsdp x tensor), the scattered one is
    # whole in the operands and over ``scattered`` in the result.
    other = axes_entry(tuple(a for a in sharded[1 - side]
                             if a not in batch + seq))

    def laid_out(scattered_dim):
        return (scattered_dim, other) if side == 0 else (other, scattered_dim)

    in_specs = tuple(P(axes_entry(batch), axes_entry(seq), last)
                     for last in laid_out(None))
    out_specs = P(*laid_out(axes_entry(scattered)))

    def scattered_sum(x, g):
        """This device's part of the devices' summed ``x^T g``."""
        me = jax.lax.axis_index(scattered)

        def block(device):
            """This device's partial product for the part ``device`` keeps."""
            cut = [x, g]
            cut[side] = jax.lax.dynamic_slice_in_dim(
                cut[side], device * width, width, 2)
            return jax.lax.dot_general(*cut, (((0, 1), (0, 1)), ((), ())),
                                       preferred_element_type=jnp.float32)

        arriving = [
            jax.lax.ppermute(block((me + hop) % n), scattered,
                             [(d, (d + hop) % n) for d in range(n)])
            for hop in range(1, n)]
        # The barrier keeps this device's own block out of the fusion that
        # adds the arrivals up, which would compute it after the wait.
        total, arriving = jax.lax.optimization_barrier((block(me), arriving))
        for part in arriving:
            total = total + part
        if whole:
            total = jax.lax.psum(total, whole)
        return total.astype(w.dtype)

    @jax.custom_vjp
    def scattering(x, w):
        return matmul(x, w)

    def backward(saved, g):
        x, w = saved
        dx = jax.lax.dot_general(g, w, (((g.ndim - 1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        dw = jax.shard_map(scattered_sum, mesh=mesh, in_specs=in_specs,
                           out_specs=out_specs, check_vma=False)(x, g)
        # Awaited together: without it the scheduler starts the exchange
        # where the gradient is first read, after the layers' backward (the
        # head's) or at the end of a layer's, with nothing left to cover it.
        return jax.lax.optimization_barrier((dx.astype(x.dtype), dw))

    scattering.defvjp(lambda x, w: (matmul(x, w), (x, w)), backward)
    return scattering(x, w)


def rms_norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * scale.astype(jnp.float32)).astype(dtype)


def layer_norm(x: jax.Array, scale: jax.Array, bias: jax.Array,
               eps: float) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                          + eps)
    return (x * scale.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(dtype)


def norm(x: jax.Array, leaves: Dict[str, jax.Array], name: str,
         config: LlamaConfig) -> jax.Array:
    """The norm ``name`` of a layer's (or the model's) leaves: RMSNorm, or
    LayerNorm with its bias ``<name>_bias`` (``LlamaConfig.layer_norm``)."""
    if config.layer_norm:
        return layer_norm(x, leaves[name], leaves[name + "_bias"],
                          config.norm_eps)
    return rms_norm(x, leaves[name], config.norm_eps)


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention factor ``0.1 mscale ln(factor) + 1`` (1 for a
    factor that does not stretch)."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_correction_range(scaling: Dict[str, Any], dim: int,
                          theta: float) -> Tuple[int, int]:
    """``(low, high)``: the pair indices between which YaRN blends the
    stretched frequencies into the published ones -- the pairs that turn
    ``beta_fast`` and ``beta_slow`` times over the original context."""
    def pair_of(turns):
        return (dim * math.log(scaling["original_max_position_embeddings"]
                               / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    return (max(math.floor(pair_of(scaling["beta_fast"])), 0),
            min(math.ceil(pair_of(scaling["beta_slow"])), dim - 1))


def rope_frequencies(head_dim: int, theta: float, scaling: Any = None):
    """``(inv_freq (head_dim / 2,) float32, factor on cos and sin)``.
    Plain RoPE: ``theta ** (-2i / head_dim)`` and 1.  YaRN (``scaling``: a
    published rope_scaling block, a dict or its items): pair i keeps its
    frequency ``f`` below ``low``, takes ``f / factor`` above ``high`` and
    a linear blend between; cos and sin carry ``mscale(factor, mscale) /
    mscale(factor, mscale_all_dim)``."""
    import numpy as np

    pairs = np.arange(0, head_dim // 2, dtype=np.float64)
    freqs = theta ** (-pairs / (head_dim // 2))
    if scaling is None:
        return freqs.astype(np.float32), 1.0
    scaling = dict(scaling)
    low, high = yarn_correction_range(scaling, head_dim, theta)
    keep = 1.0 - np.clip((pairs - low) / max(high - low, 1e-3), 0.0, 1.0)
    freqs = freqs / scaling["factor"] * (1.0 - keep) + freqs * keep
    return freqs.astype(np.float32), (
        yarn_mscale(scaling["factor"], scaling.get("mscale", 1))
        / yarn_mscale(scaling["factor"], scaling.get("mscale_all_dim", 0)))


def rope_table(positions: jax.Array, head_dim: int, theta: float,
               scaling: Any = None) -> Tuple[jax.Array, jax.Array]:
    """(sin, cos) tables, shape (..., seq, head_dim/2), float32; with
    ``scaling`` YaRN's (``rope_frequencies``)."""
    if scaling is None:
        freqs = theta ** (-jnp.arange(0, head_dim // 2, dtype=jnp.float32)
                          / (head_dim // 2))
        angles = positions.astype(jnp.float32)[..., None] * freqs
        return jnp.sin(angles), jnp.cos(angles)
    freqs, factor = rope_frequencies(head_dim, theta, scaling)
    angles = positions.astype(jnp.float32)[..., None] * jnp.asarray(freqs)
    return jnp.sin(angles) * factor, jnp.cos(angles) * factor


def apply_rope(x: jax.Array, sin: jax.Array, cos: jax.Array) -> jax.Array:
    """x: (batch, seq, heads, head_dim); rotate-half convention.

    Computed in x's dtype (bf16 in training): the f32 round-trip costs
    ~85 ms/step on the 440M bench (measured, v5e) for ~2^-8 relative
    angle precision nobody needs at 2k context; tables stay f32 and are
    cast at the multiply.
    """
    x1, x2 = jnp.split(x, 2, axis=-1)
    sin = sin[:, :, None, :].astype(x.dtype)
    cos = cos[:, :, None, :].astype(x.dtype)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def dot_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                  positions: jax.Array,
                  scale: Optional[float] = None,
                  window: Optional[int] = None,
                  keep: Optional[jax.Array] = None) -> jax.Array:
    """Reference einsum attention, causal, GQA via head broadcast.

    q: (B, S, Hq, D); k/v: (B, S, Hkv, D).  All-jnp so XLA fuses; the
    flash/ring impls are drop-in replacements (ray_tpu.ops).  With
    ``window`` a query sees the last ``window`` keys only, its own among
    them; with ``keep`` (B, S, S), nonzero where query row sees key
    column, those keys of the causal ones only.
    """
    B, S, Hq, D = q.shape
    Hkv = k.shape[2]
    group = Hq // Hkv
    qg = q.reshape(B, S, Hkv, group, D)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k,
                        preferred_element_type=jnp.float32)
    scores = scores * (D ** -0.5 if scale is None else scale)
    # Causal mask on absolute positions (supports packed/offset pos).
    mask = positions[:, None, None, :, None] >= positions[:, None, None,
                                                          None, :]
    if window is not None:
        mask &= (positions[:, None, None, :, None]
                 - positions[:, None, None, None, :]) < window
    if keep is not None:
        mask &= keep[:, None, None] != 0
    scores = jnp.where(mask, scores, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v,
                     preferred_element_type=jnp.float32).astype(v.dtype)
    return out.reshape(B, S, Hq, v.shape[-1])


def _get_attention_fn(config: LlamaConfig) -> Callable:
    """The attention callable of a config's ``attention_impl``."""
    impl = config.attention_impl
    if impl == "dot":
        return dot_attention
    try:
        if impl == "flash":
            from ray_tpu.ops.flash_attention import flash_attention_causal
            return flash_attention_causal
        if impl == "ring":
            from ray_tpu.ops.ring_attention import ring_attention_causal
            return ring_attention_causal
    except ImportError as e:
        raise NotImplementedError(
            f"attention_impl={impl!r} requires ray_tpu.ops ({e})") from e
    raise ValueError(f"unknown attention_impl {impl!r}")


@jax.named_scope("qkv_proj")
def _qkv_rope(x: jax.Array, layer: Dict[str, jax.Array], sin, cos,
              config: LlamaConfig, kind: str = "attention"):
    """Shared by the training forward and the KV-cache decode path —
    the conventions here (f32 MXU accumulation via matmul, bf16 rope)
    must stay identical across both.  ``kind``: the attending layer's,
    which decides whether it rotates (``LlamaConfig.ropes``).  A config
    with an indexer gives a fourth fresh row beside ``(q, k, v)``: the
    index queries, index key and weights of ``indexer.project``."""
    c = config
    B, S, _ = x.shape
    dt = c.dtype
    h = norm(x, layer, "attn_norm", c).astype(dt)
    if c.diff_attention:
        return _paired_rows(h, layer, c, kind)
    q = matmul(h, layer["wq"].astype(dt))
    k = matmul(h, layer["wk"].astype(dt))
    if c.qk_norm:
        q = rms_norm(q, layer["q_norm"], c.norm_eps)
        k = rms_norm(k, layer["k_norm"], c.norm_eps)
    q = q.reshape(B, S, c.n_heads, c.head_dim)
    k = k.reshape(B, S, c.n_kv_heads, c.head_dim)
    v = matmul(h, layer["wv"].astype(dt)).reshape(B, S, c.n_kv_heads,
                                                  c.head_dim)
    if c.qk_head_norm:
        q = rms_norm(q, layer["q_norm"], c.norm_eps)
        k = rms_norm(k, layer["k_norm"], c.norm_eps)
    if c.ropes(kind):
        q = apply_rope(q, sin, cos)
        k = apply_rope(k, sin, cos)
    q = with_logical_constraint(q, "batch", "seq", "heads", "head_dim")
    k = with_logical_constraint(k, "batch", "seq", "kv_heads", "head_dim")
    if c.index_topk:
        from ray_tpu.models import indexer

        return q, k, v, indexer.project(h, layer, c)
    return q, k, v


def _paired_rows(h: jax.Array, layer: Dict[str, jax.Array],
                 config: LlamaConfig, kind: str):
    """``_qkv_rope`` under differential attention (NoPE, biased
    projections where the config has them), K and V two heads a row: a
    pair's keys ``[k_2g | k_2g+1]`` side by side and its ONE value, (B, S,
    kv_row_heads, 2 D) each, as the projection lays them.  Queries (B, S,
    H, D) come as wide as such a row, (B, S, H, 2 D): an even head's
    values on the side of its pair's first key, an odd head's on the side
    of the second, zeros on the other -- ``q . [k_2g | k_2g+1]`` is then
    the head's own score, and the 2 H / n_kv_heads query heads that share a
    row are a grouped-query group of it: every attention here computes a
    pair's two softmaxes as plain grouped-query heads (``diff_combine``
    subtracts them).  A cross layer has a query alone."""
    c, dt = config, config.dtype
    B, S, _ = h.shape

    def project(w, b):
        out = matmul(h, layer[w].astype(dt))
        return out + layer[b].astype(dt) if c.attn_bias else out

    q = project("wq", "bq").reshape(B, S, c.n_heads // 2, 2, c.head_dim)
    zeros = jnp.zeros_like(q[..., 0, :])
    q = jnp.stack(
        [jnp.concatenate([q[..., 0, :], zeros], -1),
         jnp.concatenate([zeros, q[..., 1, :]], -1)], axis=3
    ).reshape(B, S, c.n_heads, c.kv_row_dim)
    if kind == "cross":
        return q, None, None
    rows = (B, S, c.kv_row_heads, c.kv_row_dim)
    return (q, project("wk", "bk").reshape(rows),
            project("wv", "bv").reshape(rows))


@jax.named_scope("diff_combine")
def diff_combine(attn: jax.Array, layer: Dict[str, jax.Array], depth,
                 config: LlamaConfig) -> jax.Array:
    """Differential attention's combination, the one function prefill and
    decode share: attn (B, S, H, 2 D), head 2j a pair's first softmax over
    its 2 D-wide value and head 2j + 1 its second ->

        a_j = attn_2j - lambda attn_2j+1
        lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init
        lambda_init = 0.8 - 0.6 exp(-0.3 depth)
        o_j = RMSNorm(a_j) * sub_norm * (1 - lambda_init)

    (B, S, H / 2, 2 D) in the compute type, computed in float32 from the
    attention's results as the kernels hand them out (their values'
    type).  ``depth``: the layer's index among all, traced or not."""
    f32 = jnp.float32
    B, S, H, W = attn.shape
    lam_init = 0.8 - 0.6 * jnp.exp(-0.3 * jnp.asarray(depth, f32))
    dots = [jnp.sum(layer[a].astype(f32) * layer[b].astype(f32))
            for a, b in (DIFF_LEAVES[:2], DIFF_LEAVES[2:4])]
    lam = jnp.exp(dots[0]) - jnp.exp(dots[1]) + lam_init
    pairs = attn.astype(f32).reshape(B, S, H // 2, 2, W)
    a = pairs[..., 0, :] - lam * pairs[..., 1, :]
    a = rms_norm(a, layer["sub_norm"], config.norm_eps) * (1.0 - lam_init)
    return a.astype(config.dtype)


def _rope_interleaved(x: jax.Array, sin, cos) -> jax.Array:
    """RoPE on pairs taken INTERLEAVED, ``(2i, 2i+1)`` turning by pair
    i's angle (DeepSeek-V2's public code): the pairs are brought to the
    rotate-half order and turned by ``apply_rope``; the result stays in
    that order, which a dot product of two such rows does not see.  x:
    (batch, seq, heads, rope_dim)."""
    return apply_rope(jnp.concatenate([x[..., 0::2], x[..., 1::2]], -1),
                      sin, cos)


# Heads a latent prefill expands and attends at once: a 12,288-token
# prompt's 128 heads of 192-wide q and k (laid out as 256 lanes), v and
# the result are 2.4 GB whole, 0.6 GB a group of 32 (AOT for a v5e, PR 37).
LATENT_HEAD_GROUP = 32


@jax.named_scope("qkv_proj")
def latent_down(x: jax.Array, layer: Dict[str, jax.Array], sin, cos,
                config: LlamaConfig):
    """A latent attention layer's DOWN projections, shared by prefill and
    decode: x (B, S, D) -> ``(c_q (B, S, q_lora_rank) normed, latent (B, S,
    latent_row))``.  ``latent`` is what the cache keeps of a token:
    ``c_kv`` after its norm, ``k_rope`` after RoPE, zeros to whole lanes."""
    c = config
    B, S, _ = x.shape
    dt = c.dtype
    h = rms_norm(x, layer["attn_norm"], c.norm_eps).astype(dt)
    cq = rms_norm(matmul(h, layer["wq_a"].astype(dt)), layer["q_a_norm"],
                  c.norm_eps)
    kv = matmul(h, layer["wkv_a"].astype(dt))
    ckv, k_rope = jnp.split(kv, [c.kv_lora_rank], axis=-1)
    ckv = rms_norm(ckv, layer["kv_a_norm"], c.norm_eps)
    k_rope = _rope_interleaved(k_rope[:, :, None], sin, cos)[:, :, 0]
    pad = c.latent_row - c.kv_lora_rank - c.qk_rope_head_dim
    latent = jnp.concatenate(
        [ckv, k_rope, jnp.zeros((B, S, pad), dt)], axis=-1)
    return cq, latent


@jax.named_scope("qkv_proj")
def latent_queries(cq: jax.Array, wq_b: jax.Array, sin, cos,
                   config: LlamaConfig):
    """Queries UP from ``c_q`` for the heads whose columns ``wq_b`` (Rq,
    heads, nope + rope) holds -> ``(q_nope (B, S, heads, nope), q_rope (B,
    S, heads, rope) roped)``."""
    q = jnp.einsum("bsr,rhd->bshd", cq, wq_b.astype(config.dtype),
                   preferred_element_type=jnp.float32).astype(config.dtype)
    q_nope, q_rope = jnp.split(q, [config.qk_nope_head_dim], axis=-1)
    return q_nope, _rope_interleaved(q_rope, sin, cos)


def _wq_b_heads(layer, config: LlamaConfig) -> jax.Array:
    """``wq_b`` a head apart: (Rq, H, nope + rope)."""
    return layer["wq_b"].reshape(-1, config.n_heads, config.head_dim)


@jax.named_scope("mla_expand")
def latent_expand(latent, q_rope, wk_b, wv_b, config: LlamaConfig):
    """The EXPANDED path's keys and values for the heads of ``wk_b`` (heads,
    nope, R) / ``wv_b`` (heads, R, v): ``[k_nope_i ; v_i] = c_kv W_UKV``,
    the one roped key part copied to every head -> k (B, S, heads, nope +
    rope), v (B, S, heads, v_head_dim).  ``q_rope``: for its shape."""
    c = config
    dt = c.dtype
    ckv = latent[..., :c.kv_lora_rank]
    k_rope = latent[..., c.kv_lora_rank:c.kv_lora_rank + c.qk_rope_head_dim]
    k_nope = jnp.einsum("bsc,hdc->bshd", ckv, wk_b.astype(dt),
                        preferred_element_type=jnp.float32).astype(dt)
    v = jnp.einsum("bsc,hcd->bshd", ckv, wv_b.astype(dt),
                   preferred_element_type=jnp.float32).astype(dt)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None], q_rope.shape)], -1)
    return k, v


def latent_attend_expanded(cq, latent, layer, sin, cos, config: LlamaConfig,
                           attend: Callable) -> jax.Array:
    """A prompt's latent attention in the EXPANDED form: queries, keys
    and values a head, attended by ``attend(q, k, v) -> (B, S, heads,
    v_head_dim)`` as plain multi-head attention with a 192-wide q/k head
    and a 128-wide v head.  A long prompt's heads go a group of
    ``LATENT_HEAD_GROUP`` at a time, one group after another."""
    c = config
    H = c.n_heads
    wq_b = _wq_b_heads(layer, c)

    def heads(wq, wk, wv):
        q_nope, q_rope = latent_queries(cq, wq, sin, cos, c)
        k, v = latent_expand(latent, q_rope, wk, wv, c)
        with jax.named_scope("attention"):
            return attend(jnp.concatenate([q_nope, q_rope], -1), k, v)

    group = LATENT_HEAD_GROUP
    if cq.shape[1] <= FLASH_PREFILL_FROM or H <= group or H % group:
        return heads(wq_b, layer["wk_b"], layer["wv_b"])

    def one(g):
        cut = lambda w, axis: jax.lax.dynamic_slice_in_dim(
            w, g * group, group, axis)
        return heads(cut(wq_b, 1), cut(layer["wk_b"], 0),
                     cut(layer["wv_b"], 0))

    out = jax.lax.map(one, jnp.arange(H // group))     # (G, B, S, group, v)
    return jnp.moveaxis(out, 0, 2).reshape(out.shape[1:3] + (H, -1))


@jax.named_scope("mla_absorb")
def latent_absorb_query(q_nope, q_rope, layer: Dict[str, jax.Array],
                        config: LlamaConfig) -> jax.Array:
    """The ABSORBED path's queries (decode): ``q~_i = W_UK,i^T q_nope_i``,
    so that a head's score against a cached row is one dot product of
    ``[q~_i ; q_rope_i ; 0]`` with the row as it lies.  q_nope (B, H,
    nope), q_rope (B, H, rope) -> (B, H, latent_row)."""
    c = config
    qt = jnp.einsum("bhd,hdc->bhc", q_nope, layer["wk_b"].astype(c.dtype),
                    preferred_element_type=jnp.float32).astype(c.dtype)
    pad = c.latent_row - c.kv_lora_rank - c.qk_rope_head_dim
    return jnp.concatenate(
        [qt, q_rope, jnp.zeros(q_rope.shape[:2] + (pad,), c.dtype)], -1)


@jax.named_scope("mla_absorb")
def latent_absorb_values(u: jax.Array, layer: Dict[str, jax.Array],
                         config: LlamaConfig) -> jax.Array:
    """``o_i = W_UV,i u_i``: u (B, H, kv_lora_rank), a head's
    probability-weighted sum of the cached ``c_kv`` -> (B, H, v_head_dim)."""
    return jnp.einsum("bhc,hcd->bhd", u, layer["wv_b"].astype(config.dtype),
                      preferred_element_type=jnp.float32
                      ).astype(config.dtype)


EXPERT_STACKS = ("w_gate", "w_up", "w_down")
# A serving prefill longer than this attends through the flash forward:
# at 4,096 positions one row's (28, S, S) float32 scores are 1.9 GB.
FLASH_PREFILL_FROM = 2048


def split_expert_stacks(layers: Dict[str, jax.Array],
                        config: LlamaConfig):
    """(what a layer scan slices per layer, what it closes over): the
    ``[L, E, ...]`` expert matrices stay whole (two of them where an expert
    has no gate), for ``attn_out_ffn`` to read at ``layer_index``;
    everything of a dense model is sliced."""
    if config.moe_experts == 0:
        return layers, {}
    return ({k: v for k, v in layers.items() if k not in EXPERT_STACKS},
            {k: layers[k] for k in EXPERT_STACKS if k in layers})


def gate_attention(x: jax.Array, attn: jax.Array,
                   layer: Dict[str, jax.Array], config: LlamaConfig):
    """An attending layer's output gate (``attn_gate``): ``attn * sigmoid(
    W_gate h)``, h the layer's normed input (``_qkv_rope``'s, which XLA
    computes once), elementwise over what the output projection reads.
    x (B, S, D) the layer's input; attn (B, S, heads, head_dim)."""
    c = config
    with jax.named_scope("qkv_proj"):
        h = norm(x, layer, "attn_norm", c).astype(c.dtype)
        gate = matmul(h, layer["w_attn_gate"].astype(c.dtype), jnp.float32)
    with jax.named_scope("attn_out"):
        return (attn.astype(jnp.float32)
                * jax.nn.sigmoid(gate).reshape(attn.shape)).astype(attn.dtype)


def residual_add(x: jax.Array, branch: jax.Array,
                 config: LlamaConfig,
                 post_norm: Optional[jax.Array] = None) -> jax.Array:
    """``x + residual_multiplier * branch``: both branches of a layer.
    ``post_norm``: the weight of the branch's own output norm
    (``sandwich_norm``), applied first."""
    if post_norm is not None:
        branch = rms_norm(branch, post_norm, config.norm_eps)
    if config.residual_multiplier != 1.0:
        branch = branch * config.residual_multiplier
    return x + branch.astype(x.dtype)


def attn_out_ffn(x: jax.Array, attn: jax.Array,
                 layer: Dict[str, jax.Array], config: LlamaConfig,
                 valid: Optional[jax.Array] = None,
                 layer_index: Optional[jax.Array] = None,
                 training: bool = False):
    """Output projection + FFN half of an attending layer
    (``layer_block``'s; the conventions shared with ``_qkv_rope`` live
    here).  Returns what ``ffn_half`` does.  ``x`` is the layer's input:
    what a router placed before attention reads."""
    B, S, _ = x.shape
    route_x = x if config.moe_router_input == "layer" else None
    with jax.named_scope("attn_out"):
        out = scattered_grad_matmul(attn.reshape(B, S, config.o_dim),
                                    layer["wo"].astype(config.dtype),
                                    ("heads", "embed"))
        if config.attn_bias:
            out = out + layer["bo"].astype(config.dtype)
        x = residual_add(x, out, config, layer["post_attn_norm"]
                         if config.sandwich_norm else None)
    if config.no_ffn:       # a block of attention alone (``block_pattern``)
        return x, jnp.zeros((), jnp.float32), None
    return ffn_half(x, layer, config, valid, layer_index, route_x,
                    training)


@jax.named_scope("ffn")
def ffn_half(x: jax.Array, layer: Dict[str, jax.Array],
             config: LlamaConfig, valid: Optional[jax.Array] = None,
             layer_index: Optional[jax.Array] = None,
             route_x: Optional[jax.Array] = None,
             training: bool = False):
    """The FFN half of a layer, after whichever mixer (attention's output
    projection, a Mamba-2 mixer) has been added to ``x``.  ``route_x``:
    what the router reads where that is not the normed ``x``
    (``moe_router_input``).

    Returns ``(x, aux, expert_rows)``: the layer's Switch aux loss and
    the rows each expert computed ((E,) int32) — a constant 0 and None
    for a dense config.  ``valid`` (broadcastable to (B, S)) marks the
    rows that are real; experts compute no others.  With ``layer_index``
    the expert matrices in ``layer`` are the whole ``[L, E, ...]`` stacks
    (``split_expert_stacks``), read in place.  ``training``:
    ``moe.moe_ffn_dropless``'s -- the expert rows are then the router's
    CHOICES counted over all ``moe_experts``, held here or not: what the
    balance update of a selection bias reads (``balance_router_bias``).

    All of it is scope ``ffn``; an expert layer's ``router``,
    ``expert_dispatch`` and ``expert_ffn`` (``models/moe.py``) lie inside
    it, and the innermost scope is an op's own."""
    c = config
    dt = c.dtype
    x = with_logical_constraint(x, "batch", "seq", None)
    h = norm(x, layer, "mlp_norm", c).astype(dt)
    post_norm = layer["post_mlp_norm"] if c.sandwich_norm else None
    if c.moe_experts == 0:
        gate = scattered_grad_matmul(h, layer["w_gate"].astype(dt),
                                     ("embed", "mlp"))
        up = scattered_grad_matmul(h, layer["w_up"].astype(dt),
                                   ("embed", "mlp"))
        # Named so the "attn_ffn" remat policy can save it (inert under
        # every other policy and outside jax.checkpoint).
        from jax.ad_checkpoint import checkpoint_name

        ff = checkpoint_name(jax.nn.silu(gate) * up, "ffn_act")
        ff = with_logical_constraint(ff, "batch", "seq", "mlp")
        x = residual_add(x, scattered_grad_matmul(
            ff, layer["w_down"].astype(dt), ("mlp", "embed")), c,
            post_norm)
        return (with_logical_constraint(x, "batch", "seq", None),
                jnp.zeros((), jnp.float32), None)
    from ray_tpu.models import moe
    from ray_tpu.parallel.sharding import current_mesh

    mcfg = expert_config(c)
    moe_params = {k: layer[k] for k in ("router",) + EXPERT_STACKS
                  + (("router_bias",) if c.moe_router_bias else ())
                  if k in layer}
    rows_in = h
    if c.moe_latent_size:
        # latent experts: the dispatch's rows are the latent's, the router
        # reads the normed stream at full width
        with jax.named_scope("latent_proj"):
            rows_in = matmul(h, layer["w_lat_in"].astype(dt))
        route_x = h if route_x is None else route_x
    mesh = current_mesh()
    if mesh is not None and mesh.shape.get("expert", 1) > 1:
        # Expert-parallel training: the dense dispatch, whose sharding
        # constraint lowers to the all-to-all (it has a capacity).
        ff, aux = moe.moe_ffn(h, moe_params, mcfg)
        expert_rows = None
    elif c.moe_dispatch_chunk and h.shape[1] > c.moe_dispatch_chunk:
        ff, aux, expert_rows = _dispatch_in_chunks(
            rows_in, moe_params, mcfg, valid, layer_index, route_x,
            c.moe_dispatch_chunk, training)
    else:
        ff, aux, expert_rows = moe.moe_ffn_dropless(
            rows_in, moe_params, mcfg, valid=valid, layer_index=layer_index,
            route_x=route_x, training=training)
    if c.moe_latent_size:
        with jax.named_scope("latent_proj"):
            ff = matmul(ff, layer["w_lat_out"].astype(dt))
    if c.moe_shared_size:
        # the shared expert: a plain SwiGLU every token passes (two
        # matrices and a squared ReLU where the experts have no gate), added
        # to what its routed experts gave
        with jax.named_scope("shared_expert"):
            if mcfg.gated:
                shared = jax.nn.silu(
                    matmul(h, layer["ws_gate"].astype(dt))) \
                    * matmul(h, layer["ws_up"].astype(dt))
            else:
                shared = moe.relu2(matmul(h, layer["ws_up"].astype(dt)))
            ff = ff + matmul(shared, layer["ws_down"].astype(dt))
    x = residual_add(x, ff, c, post_norm)
    return with_logical_constraint(x, "batch", "seq", None), aux, \
        expert_rows


def expert_config(config: LlamaConfig):
    """The ``moe.MoEConfig`` of the config's expert layers."""
    from ray_tpu.models import moe

    c = config
    return moe.MoEConfig(hidden_size=c.moe_latent_size or c.hidden_size,
                         intermediate_size=c.expert_width,
                         n_experts=c.moe_experts, top_k=c.moe_top_k,
                         capacity_factor=c.moe_capacity_factor,
                         norm_topk=c.moe_norm_topk,
                         activation=c.moe_activation, dtype=c.dtype,
                         groups=c.moe_groups, top_groups=c.moe_top_groups,
                         routed_scale=c.moe_routed_scale, held=c.moe_held,
                         score=c.moe_router_score)


def _dispatch_in_chunks(h, moe_params, mcfg, valid, layer_index, route_x,
                        chunk: int, training: bool = False):
    """``moe.moe_ffn_dropless`` over h (B, S, D), at most ``chunk``
    positions at a time and one chunk after another (a dispatch mixes no
    positions): ``(ff, aux averaged, expert rows summed)``.  The chunks
    are equal: the fewest that divide S into whole sublane tiles."""
    from ray_tpu.models import moe

    B, S, D = h.shape
    n = next((n for n in range(-(-S // chunk), S // 8 + 1)
              if S % (8 * n) == 0), None)
    if n is None:
        raise ValueError(f"no equal chunks of at most {chunk} positions "
                         f"divide a prefill of {S}")
    chunk = S // n

    def chunks(a):
        return jnp.moveaxis(a.reshape(B, S // chunk, chunk, *a.shape[2:]),
                            1, 0)

    valid = jnp.ones((B, S), bool) if valid is None \
        else jnp.broadcast_to(valid, (B, S))
    xs = (chunks(h), chunks(valid)) + (
        () if route_x is None else (chunks(route_x),))

    def one(args):
        return moe.moe_ffn_dropless(
            args[0], moe_params, mcfg, valid=args[1],
            layer_index=layer_index,
            route_x=args[2] if len(args) > 2 else None,
            training=training)

    ff, aux, rows = jax.lax.map(one, xs)
    return (jnp.moveaxis(ff, 0, 1).reshape(B, S, D), aux.mean(),
            rows.sum(0))


def lm_head(params: PyTree, config: LlamaConfig) -> jax.Array:
    """The (hidden, vocab) output matrix: the embedding transposed where
    the config ties them.  The one place that choice is made."""
    if config.tie_embeddings:
        return params["embed_tokens"].astype(config.dtype).T
    return params["lm_head"].astype(config.dtype)


@jax.named_scope("head")
def head_logits(x: jax.Array, params: PyTree,
                config: LlamaConfig) -> jax.Array:
    """Logits of normed hidden states, divided by ``logits_scaling``."""
    logits = matmul(x, lm_head(params, config))
    if config.logits_scaling != 1.0:
        logits = logits / config.logits_scaling
    return logits


@jax.named_scope("embed")
def embed(params: PyTree, tokens: jax.Array,
          config: LlamaConfig) -> jax.Array:
    """Token embeddings times ``embedding_multiplier``, in the type the
    residual stream is carried in."""
    x = params["embed_tokens"].astype(config.dtype)[tokens]
    if config.embedding_multiplier != 1.0:
        x = x * config.embedding_multiplier
    return x.astype(config.stream_dtype or config.dtype)


# ---------------------------------------------------------------------------
# The layer pattern: a scan iteration is one PERIOD of layers
# ---------------------------------------------------------------------------

# A differential layer's own leaves: the four lambda vectors (head_dim
# each) and the weight of the norm over a pair's 2 x head_dim value.
DIFF_LEAVES = ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2", "sub_norm")
ATTENTION_LEAVES = ("wq", "wk", "wv", "wo", "q_norm", "k_norm",
                    "bq", "bk", "bv", "bo", "w_attn_gate") + DIFF_LEAVES


def _leaf_kind(name: str) -> Optional[str]:
    """The kind of layer that alone has this leaf (None: every layer);
    "attention" stands for every attending kind (``_leaf_group``)."""
    if name in ATTENTION_LEAVES:
        return "attention"
    if name.startswith("conv_"):
        return "conv"
    if name.startswith("gmu_"):
        return "gmu"
    if name.startswith("kda_"):
        return "kda"
    if name.startswith("power_"):
        return "power"
    return "mamba" if name.startswith("ssm_") else None


def _leaf_group(kind: str) -> str:
    """The kind under whose name a layer of ``kind`` finds its leaves
    (a model has Mamba-2 or Mamba-1 layers, never both: ``ssm_*``)."""
    if kind in ATTENDING_KINDS:
        return "attention"
    return "mamba" if kind == "mamba1" else kind


def by_period(tree: PyTree, config: LlamaConfig) -> PyTree:
    """Leaves stacked over layers (all of them, or those of one kind:
    weights, a cache) -> ``(periods, how many a period holds, ...)``, what
    a scan over periods slices.  A period of ONE layer is the layer: the
    tree as it is, and ``period_layers`` / ``stack_period`` /
    ``merge_periods`` add and take away nothing either, so a plain
    decoder's scan is traced as it always was."""
    if config.period_len == 1:
        return tree
    periods = config.n_layers // config.period_len
    return jax.tree.map(
        lambda x: x.reshape((periods, x.shape[0] // periods) + x.shape[1:]),
        tree)


def merge_periods(tree: PyTree, config: LlamaConfig) -> PyTree:
    """``by_period``'s inverse, for what a scan over periods stacked."""
    if config.period_len == 1:
        return tree
    return jax.tree.map(
        lambda x: x.reshape((x.shape[0] * x.shape[1],) + x.shape[2:]), tree)


def scanned_layers(layers: Dict[str, jax.Array], config: LlamaConfig):
    """What a scan over periods takes as xs of the layer stacks: for a
    plain decoder the stacks themselves (a period is a layer: the scan
    slices it, as it always has); for a pattern NOTHING -- ``period_layers``
    reads each layer of the period out of the stacks where they lie.  (A
    period's slice of a stack, read by several layers, is materialised by
    XLA: at granite-4.0-h-micro's widths 1.6 GB of weights copied out a
    period, seen in the HLO compiled for a v5e.)"""
    return layers if config.period_len == 1 else None


def period_layers(layers: Dict[str, jax.Array], period, p: jax.Array,
                  config: LlamaConfig):
    """Per layer of period ``p``: ``(kind, index among the period's layers
    of that kind, its leaves)``.  ``layers``: the whole stacks; ``period``:
    ``scanned_layers``' slice.  The attending kinds share their leaves'
    stacks, in the layers' order."""
    if config.period_len == 1:
        return [(config.period[0], 0, period)]
    groups = [_leaf_group(kind) for kind in config.period]
    seen = {kind: 0 for kind in LAYER_KINDS}
    out = []
    for j, (kind, group) in enumerate(zip(config.period, groups)):
        i = seen[kind]
        seen[kind] += 1
        at = {None: layer_index(p, config.period_len, j),
              group: layer_index(p, groups.count(group),
                                 groups[:j].count(group))}
        out.append((kind, i, {
            name: jax.lax.dynamic_index_in_dim(
                leaf, at[_leaf_kind(name)], 0, keepdims=False)
            for name, leaf in layers.items()
            if _leaf_kind(name) in (None, group)}))
    return out


def layer_of(period: PyTree, i: int, config: LlamaConfig) -> PyTree:
    """The ``i``-th layer's slice of a period's slice of a ``by_period``
    tree of one kind (a cache)."""
    if config.period_len == 1:
        return period
    return jax.tree.map(lambda x: x[i], period)


def stack_period(items, config: LlamaConfig):
    """A period's per-layer results as one tree with a leading axis
    (None where the period has no such layer or the results are None)."""
    if not items or items[0] is None:
        return None
    if config.period_len == 1:
        return items[0]
    return jax.tree.map(lambda *xs: jnp.stack(xs), *items)


def state_mixer(kind: str):
    """Of a kind of layer that keeps a state a slot: ``(its module --
    ``prefill`` and ``decode`` --, the scope of its pre-norm and
    in-projection, the scope of its residual add)``."""
    if kind == "conv":
        from ray_tpu.models import shortconv

        return shortconv, "conv_proj", "conv_out"
    if kind == "mamba1":
        # (it also hands back its scan output, the memory a gmu layer
        # reads: the caller's ``state_step`` keeps it)
        from ray_tpu.models import mamba1

        return mamba1, "ssm_proj", "ssm_out"
    if kind == "kda":
        from ray_tpu.models import kda

        return kda, "ssm_proj", "ssm_out"
    if kind == "power":
        # (its q and k are rotated: the caller's ``state_step`` hands it
        # the rope table, ``power_retention.ROPES``)
        from ray_tpu.models import power_retention

        return power_retention, "ssm_proj", "ssm_out"
    from ray_tpu.models import mamba2

    return mamba2, "ssm_proj", "ssm_out"


def layer_index(p: jax.Array, per_period: int, i: int) -> jax.Array:
    """Period ``p``'s ``i``-th layer of ``per_period``, among all."""
    return p if per_period == 1 else p * per_period + i


def layer_block(x, layer, kind: str, config: LlamaConfig, sin, cos,
                attend: Callable, state_step: Optional[Callable] = None,
                valid=None, at=None, memory=None):
    """One layer of ``kind``: THE place that says what a decoder layer is
    made of, for training, the prefills and the decode step alike.  What
    the fresh rows meet (themselves, a cache, a carried state) is the
    caller's: ``attend(q, k, v)`` (latent attention: ``attend(cq,
    latent)``; behind an indexer: ``attend(q, k, v, index)``; a cross
    layer: ``attend(q, None, None)``, the K/V layer's rows being the
    caller's) and ``state_step(mixer, h)``, closures of the scan body that
    owns the cache or the carry, return ``(the mixer's output, ys)``, ``ys``
    what the caller keeps; an ``attend`` scopes its ``kv_write`` and
    ``attention`` itself.  ``valid``: ``ffn_half``'s, or (B,), whole rows;
    ``at``: ``layer_index``'s arguments where ``layer`` holds the experts'
    whole stacks (and what a differential layer's depth is counted from);
    ``memory``: what a gmu layer gates, the scan output of the Mamba-1
    layer before it at the same positions, (B, S, ssm_inner).  Returns
    ``(x, aux, expert rows, ys)``."""
    c = config

    def rows_and_place():       # the FFN half's, traced after the mixer
        return dict(
            valid=valid[:, None] if getattr(valid, "ndim", 0) == 1
            else valid,
            layer_index=None if at is None else layer_index(*at),
            # the training walk scans the experts' stacks (``at`` None)
            training=at is None)

    if c.kv_lora_rank or kind in ATTENDING_KINDS:
        fresh = (latent_down(x, layer, sin, cos, c) if c.kv_lora_rank
                 else _qkv_rope(x, layer, sin, cos, c, kind))
        attn, ys = attend(*fresh)
        if c.diff_attention:
            attn = diff_combine(attn, layer,
                                c.layer_offset + layer_index(*at), c)
        if c.attn_gate:
            attn = gate_attention(x, attn, layer, c)
        return attn_out_ffn(x, attn, layer, c, **rows_and_place()) + (ys,)
    if kind == "gmu":
        with jax.named_scope("gmu"):
            h = norm(x, layer, "attn_norm", c).astype(c.dtype)
            gate = jax.nn.silu(matmul(h, layer["gmu_in"].astype(c.dtype),
                                      jnp.float32))
            out = matmul((gate * memory).astype(c.dtype),
                         layer["gmu_out"].astype(c.dtype))
            x = residual_add(x, out, c)
        return ffn_half(x, layer, c, **rows_and_place()) + (None,)
    mixer, proj_scope, out_scope = state_mixer(kind)
    with jax.named_scope(proj_scope):
        h = norm(x, layer, "attn_norm", c).astype(c.dtype)
    out, ys = state_step(mixer, h)
    with jax.named_scope(out_scope):
        x = residual_add(x, out, c)
    if c.no_ffn:        # a block of the mixer alone (``block_pattern``)
        return x, jnp.zeros((), jnp.float32), None, ys
    return ffn_half(x, layer, c, **rows_and_place()) + (ys,)


def rope_for(positions: jax.Array, config: LlamaConfig):
    """``rope_table`` of a config at ``positions`` (..., S)."""
    with jax.named_scope("qkv_proj"):
        return rope_table(positions, config.rope_dim, config.rope_theta,
                          config.rope_scaling)


def walk_block(sin, cos, positions, kv_step: Callable, window_step=None,
               valid=None, lengths=None, cross_step=None,
               memory_at=None) -> Callable:
    """``layer_block`` over whole sequences with ``layer_walk``'s steps:
    ``block(x, layer, its slice of the cache walked, at, kind, config,
    memory) -> (x, aux, expert rows, ys, memory)``.  ``memory``: the scan
    output of the last Mamba-1 layer walked (None where the model has no
    gmu layer to read it), every position's or, with ``memory_at`` (B, 1),
    that position's alone; a cross layer's queries go to ``cross_step(q,
    positions) -> attn``."""
    def block(x, layer, kv_layer, at, kind, c, memory=None):
        def attend(q, k, v, *index):
            if kind == "cross":
                with jax.named_scope("cross_attention"):
                    return cross_step(q, positions), None
            with jax.named_scope("attention"):
                if kind == "window":
                    return window_step(q, k, v, positions)
                return kv_step(q, k, v, positions, kv_layer, *index)

        def attend_expanded(cq, latent):
            # latent attention, expanded: attended as heads of their own
            # keys and values; kept: the latent rows
            return latent_attend_expanded(
                cq, latent, layer, sin, cos, c,
                lambda q, k, v: kv_step(q, k, v, positions, None)[0]), latent

        def state_step(mixer, h):
            nonlocal memory
            out, ys, *scan_output = mixer.prefill(
                h, layer, c, lengths,
                *((sin, cos) if getattr(mixer, "ROPES", False) else ()))
            if scan_output and memory is not None:
                memory = scan_output[0] if memory_at is None else \
                    jnp.take_along_axis(scan_output[0],
                                        memory_at[:, :, None], axis=1)
            return out, ys

        return layer_block(
            x, layer, kind, c, sin, cos,
            attend_expanded if c.kv_lora_rank else attend,
            state_step, valid, at, memory) + (memory,)

    return block


def walk_layers(carry, params: PyTree, config: LlamaConfig, block: Callable,
                kv_layers: Any = None, scan_experts: bool = False,
                parts=None):
    """The layers of ``config`` over ``carry``, the middle of every forward
    pass: a scan over the PERIODS of the layer pattern, ``block(carry,
    layer, kv_layer, at, kind, the part's config) -> (carry, expert rows,
    ys)`` a layer (``walk_block`` on the residual stream; what else rides
    the carry is the caller's), each of ``config.parts()`` a scan of its
    own over ``params[its key]``.  ``scan_experts``: the ``[L, E, ...]``
    expert matrices are sliced by the scan like every other leaf, ``at``
    None (training: what the dense dispatch under an ``expert`` mesh axis
    and the backward take); else closed over and read in place (serving).
    -> ``(carry, (ys over the attention layers, (L, E) expert rows, ys over
    the state-keeping layers, ys over the window layers))``, None for none.
    ``parts``: those of ``config.parts()`` to walk (all of them)."""
    def walk_part(carry, c, layers, kv_layers):         # c: the part's
        sliced, stacks = (layers, {}) if scan_experts \
            else split_expert_stacks(layers, c)
        plen = c.period_len

        def body(carry, period_index_cache):
            period, p, kv_period = period_index_cache
            ys, rows = {kind: [] for kind in LAYER_KINDS}, []
            for j, (kind, i, layer) in enumerate(
                    period_layers(sliced, period, p, c)):
                carry, rows_j, ys_j = block(
                    carry, {**layer, **stacks},
                    layer_of(kv_period, i, c) if kind == "attention"
                    else None,
                    None if scan_experts else (p, plen, j), kind, c)
                rows.append(rows_j)
                ys[kind].append(ys_j)
            # (a model has one kind of state-keeping layer: one list holds
            # a state)
            return carry, (stack_period(ys["attention"], c),
                           stack_period(rows, c),
                           stack_period(
                               sum((ys[kind] for kind in STATE_KINDS), []),
                               c),
                           stack_period(ys["window"], c))

        # ``layer_scan``: the loop's own slicing of a layer's weights and
        # stacking of what a layer saves; an op inside a block's scope
        # keeps that one, the innermost.
        with jax.named_scope("layer_scan"):
            carry, stacked = jax.lax.scan(
                body, carry,
                (scanned_layers(sliced, c),
                 jnp.arange(c.n_layers // plen, dtype=jnp.int32),
                 by_period(kv_layers, c)), unroll=c.scan_unroll)
            return carry, merge_periods(stacked, c)

    outs = []
    for part, key, l0 in config.parts() if parts is None else parts:
        a0 = config.layers_before(l0, "attention")
        carry, out = walk_part(
            carry, part, params[key],
            jax.tree.map(
                lambda a: a[a0:a0 + part.layers_of("attention")], kv_layers))
        outs.append(out)
    # Each result over the parts that have it, in the layers' order (a
    # dense part computes no expert's rows, a part without attending
    # layers keeps no K/V).
    return carry, tuple(over_parts([o[i] for o in outs]) for i in range(4))


# ---------------------------------------------------------------------------
# Forward / loss
# ---------------------------------------------------------------------------

def embed_sharded(params: PyTree, tokens, config: LlamaConfig):
    """Training's lookup (``forward``, a pipeline's first stage)."""
    # ZeRO-3 semantics for the lookup: all-gather the fsdp-sharded
    # embed dim of the table BEFORE the gather.  Without this the
    # gather's output inherits the table's D-sharding and the SPMD
    # partitioner falls into "involuntary full rematerialization"
    # resharding it to (batch, seq) (observed in the 8-way dryrun).
    with jax.named_scope("embed"):
        emb = with_logical_constraint(
            params["embed_tokens"].astype(config.dtype), "vocab", None)
        x = emb[tokens]
        if config.embedding_multiplier != 1.0:      # as ``embed``
            x = x * config.embedding_multiplier
        return with_logical_constraint(x, "batch", "seq", None)


def head_loss_logits(x, params: PyTree, config: LlamaConfig):
    """Training's final norm and head, in the scope ``loss_fn`` goes on in."""
    with jax.named_scope("head_loss"):
        x = norm(x, params, "final_norm", config)
        logits = scattered_grad_matmul(x, lm_head(params, config),
                                       ("embed", "vocab"))
        if config.logits_scaling != 1.0:            # as ``head_logits``
            logits = logits / config.logits_scaling
        return with_logical_constraint(logits, "batch", "seq", "vocab")


def train_block(config: LlamaConfig, sin, cos, positions) -> Callable:
    """``walk_block`` as training runs it: the rows attended as they are
    (a window layer's through the same function, told its band), nothing
    kept, under the config's remat."""
    attention_fn = _get_attention_fn(config)
    block = walk_block(
        sin, cos, positions, lambda q, k, v, positions, _cache: (
            attention_fn(q, k, v, positions), None),
        lambda q, k, v, positions: (
            attention_fn(q, k, v, positions, window=config.window_size),
            None))
    if config.remat:
        block = jax.checkpoint(block, policy=_remat_policy(config),
                               static_argnums=(4, 5))
    return block


def train_layers(x, params: PyTree, config: LlamaConfig, positions):
    """``walk_layers`` as training runs it: x (B, S, D) -> ``(x, aux sum,
    the router's choices an expert layer and expert, (L, E) int32; None for
    a dense config)``."""
    block = train_block(config, *rope_for(positions, config), positions)

    def summing(carry, *layer_args):
        x, aux, rows, ys, _memory = block(carry[0], *layer_args)
        return (x, carry[1] + aux), rows, ys

    (x, aux), (_kv, rows, _states, _windows) = walk_layers(
        (x, jnp.zeros((), jnp.float32)), params, config, summing,
        scan_experts=True)
    return x, aux, rows


def forward(params: PyTree, tokens: jax.Array, config: LlamaConfig,
            positions: Optional[jax.Array] = None,
            return_aux: bool = False, return_expert_rows: bool = False):
    """Logits for next-token prediction.  tokens: (B, S) int32.

    With ``return_aux=True`` returns (logits, aux) where aux is the
    summed MoE load-balancing loss over layers (0.0 for dense); with
    ``return_expert_rows`` also, last, the router's choices an expert
    layer and expert ((L, E) int32; None for a dense config)."""
    c = config
    if not c.plain_decoder:
        raise NotImplementedError(
            "llama.forward (training) computes layers of softmax attention, "
            "full or over a window, with the default scale and logits and a "
            "router on the FFN's input (LlamaConfig.plain_decoder): a "
            "config with state-space, short-convolution, cross or gmu "
            "layers, latent or differential attention, an indexer or any "
            "other term of that property is served only "
            "(llama_serve.build_*)")
    if c.layers_of("window") and c.attention_impl == "ring":
        raise NotImplementedError(
            "attention_impl='ring' has no band: window layers train "
            "through 'flash' or 'dot'")
    if positions is not None and c.attention_impl != "dot":
        # flash/ring mask on raw row index, not positions — packed or
        # offset sequences would silently attend across boundaries.
        raise NotImplementedError(
            f"custom positions require attention_impl='dot' "
            f"(got {c.attention_impl!r})")
    custom_positions = positions is not None
    if positions is None:
        positions = jnp.broadcast_to(
            jnp.arange(tokens.shape[1], dtype=jnp.int32), tokens.shape)
    x = embed_sharded(params, tokens, c)

    from ray_tpu.parallel.sharding import current_mesh

    mesh = current_mesh()
    expert_rows = None
    if (c.pipeline_microbatches > 0 and mesh is not None
            and mesh.shape.get("pipe", 1) > 1):
        if not c.one_stage_stack:
            raise NotImplementedError(
                "pipeline stages slice one stack of one kind of layer "
                "(LlamaConfig.one_stage_stack)")
        if c.moe_experts > 0:
            raise NotImplementedError(
                "MoE layers inside pipeline stages are not supported "
                "yet (the GPipe schedule carries no aux accumulator); "
                "use expert parallelism with pipe=1")
        if custom_positions:
            raise NotImplementedError(
                "pipeline parallelism assumes the default arange "
                "position layout (packed/offset positions differ per "
                "batch row; microbatches share one row)")
        if c.attention_impl == "ring":
            raise NotImplementedError(
                "attention_impl='ring' inside pipeline stages would "
                "nest shard_maps; use flash or dot with pipe > 1")
        from ray_tpu.parallel.pipeline import pipeline_layers

        # The block closes over batch-shaped sin/cos/positions; a
        # microbatch needs the broadcastable single-row versions, which
        # are only equivalent for the default arange layout.
        sin, cos = rope_for(positions, c)
        block = train_block(c, sin[:1], cos[:1], positions[:1])
        batch_axes = [a for a in ("data", "fsdp") if a in mesh.shape
                      and mesh.shape[a] > 1]
        x = pipeline_layers(
            lambda h, layer: block(h, layer, None, None, "attention", c)[0],
            params["layers"], x,
            mesh=mesh, num_microbatches=c.pipeline_microbatches,
            batch_axes=batch_axes)
        aux_total = jnp.zeros((), jnp.float32)
    else:
        x, aux_total, expert_rows = train_layers(x, params, c, positions)

    logits = head_loss_logits(x, params, c)
    out = (logits,) + ((aux_total,) if return_aux else ()) \
        + ((expert_rows,) if return_expert_rows else ())
    return out if len(out) > 1 else logits


def loss_fn(params: PyTree, batch: Dict[str, jax.Array],
            config: LlamaConfig) -> jax.Array:
    """Mean next-token cross-entropy.  batch: tokens (B,S) int32,
    optional loss_mask (B,S)."""
    return loss_and_expert_rows(params, batch, config)[0]


def loss_and_expert_rows(params: PyTree, batch: Dict[str, jax.Array],
                         config: LlamaConfig):
    """``(loss_fn's loss, forward's expert rows)``: what the train step
    differentiates, the rows its auxiliary output."""
    tokens = batch["tokens"]
    positions = batch.get("positions")
    if positions is None:
        # Run the forward at the full sequence length and drop the last
        # position's logits, instead of slicing tokens to S-1: a
        # 2047-long sequence does not tile the flash kernel's blocks
        # (it would take the kernel's pad-and-slice path every step).
        logits, aux, rows = forward(params, tokens, config, return_aux=True,
                                    return_expert_rows=True)
        logits = logits[:, :-1]
    else:
        # Packed/offset positions (dot-attention path): keep the old
        # S-1 slice so the last raw token never becomes a key — at full
        # length a small positions[S-1] (new-document start) would be
        # attended by every later-positioned query.
        logits, aux, rows = forward(params, tokens[:, :-1], config,
                                    positions=positions[:, :-1],
                                    return_aux=True, return_expert_rows=True)
    return next_token_loss(logits, batch, config, aux), rows


def next_token_loss(logits: jax.Array, batch: Dict[str, jax.Array],
                    config: LlamaConfig, aux=0.0) -> jax.Array:
    """``loss_fn``'s mean cross-entropy of logits (B, S - 1, V) against the
    batch's next tokens, plus the aux loss of a config with experts."""
    targets = batch["tokens"][:, 1:]
    with jax.named_scope("head_loss"):
        logits = logits.astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, targets[..., None],
                                   axis=-1).squeeze(-1)
        nll = logz - gold
        mask = batch.get("loss_mask")
        if mask is None:
            ce = jnp.mean(nll)
        else:
            mask = mask[:, 1:].astype(jnp.float32)
            ce = jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    if config.moe_experts > 0:
        # Per-layer mean so the weight is depth-invariant.
        ce = ce + config.moe_aux_weight * aux / config.n_layers
    return ce


# ---------------------------------------------------------------------------
# Train step
# ---------------------------------------------------------------------------

def warmup_rate(learning_rate: float, warmup_steps: int, step):
    """The rate of update ``step`` (from 1) under a linear warm-up over
    ``warmup_steps``; the rate itself, untouched, without one."""
    if not warmup_steps:
        return learning_rate
    return learning_rate * jnp.minimum(
        1.0, step.astype(jnp.float32) / warmup_steps)


def default_optimizer(learning_rate: float = 3e-4, warmup_steps: int = 0):
    import optax

    return optax.chain(
        optax.clip_by_global_norm(1.0),
        # (optax counts the updates already made: from 0)
        optax.adamw((lambda count: warmup_rate(
            learning_rate, warmup_steps, count + 1))
            if warmup_steps else learning_rate, weight_decay=0.1),
    )


# Leaves of ``params`` that no gradient trains: the optimizer state has no
# moments for them, the clipped norm no part of them.
UNTRAINED_LEAVES = ("router_bias",)


def split_untrained(params: PyTree):
    """``(params without UNTRAINED_LEAVES, {stack: {leaf: value}})``: the
    tree the optimizer sees and what the step updates by rule.  A tree
    with no such leaf comes back as it is, beside {}."""
    fixed = {key: {k: sub[k] for k in UNTRAINED_LEAVES if k in sub}
             for key, sub in params.items() if isinstance(sub, dict)}
    fixed = {key: sub for key, sub in fixed.items() if sub}
    if not fixed:
        return params, fixed
    return {key: ({k: v for k, v in sub.items() if k not in fixed[key]}
                  if key in fixed else sub)
            for key, sub in params.items()}, fixed


def merge_untrained(trained: PyTree, fixed) -> PyTree:
    """``split_untrained``'s inverse."""
    if not fixed:
        return trained
    return {key: ({**sub, **fixed[key]} if key in fixed else sub)
            for key, sub in trained.items()}


def balance_router_bias(fixed, expert_rows: jax.Array,
                        config: LlamaConfig):
    """The selection biases after a step (``moe_balance_rate``): an expert
    chosen by fewer tokens than the layer's mean goes up by the rate, one
    chosen by more down, and the step is centred so that the biases keep
    their mean.  ``fixed``: ``split_untrained``'s, a ``router_bias`` (L,
    E) float32 a stack of expert layers; ``expert_rows`` (all expert
    layers, E) int32 in the layers' order, every expert counted whether
    held here or not (``forward``'s).  Scope ``router_balance``."""
    out, at = {}, 0
    with jax.named_scope("router_balance"):
        for _part, key, _ in config.parts():
            if key not in fixed:
                continue
            bias = fixed[key]["router_bias"]
            chosen = expert_rows[at:at + bias.shape[0]].astype(jnp.float32)
            at += bias.shape[0]
            d = config.moe_balance_rate * jnp.sign(
                chosen.mean(-1, keepdims=True) - chosen)
            out[key] = {**fixed[key],
                        "router_bias": bias + d - d.mean(-1, keepdims=True)}
    return out


def dispatch_compact_share(expert_rows: jax.Array, batch,
                           config: LlamaConfig):
    """The share of a step's expert layers whose dispatch was ONE block of
    ``moe.compact_rows`` sorted rows (the held experts' rows fitted it; a
    layer under 1.0 went through two blocks or more that step), float32 --
    from ``forward``'s ``expert_rows`` (expert layers, E) and the batch's
    shape, which decide it.  None where no layer is dispatched in blocks:
    every expert held, or half and more, or a dispatch in chunks (whose
    rows arrive summed)."""
    from ray_tpu.models import moe

    B, S = batch["tokens"].shape
    S -= "positions" in batch       # ``loss_and_expert_rows``
    bound = moe.compact_rows(B * S, expert_config(config))
    if bound == B * S * config.moe_top_k or (
            config.moe_dispatch_chunk and S > config.moe_dispatch_chunk):
        return None
    first, count = config.moe_held
    held = expert_rows[:, first:first + count].sum(-1)
    return jnp.mean((held <= bound).astype(jnp.float32))


def init_train_state(rng: jax.Array, config: LlamaConfig,
                     optimizer=None,
                     fused: bool = False) -> Dict[str, Any]:
    """``fused=True`` pairs with ``make_train_step(fused=True)``: the
    opt_state is a ``FusedAdamWState`` instead of the optax chain
    tuple (same logical contents — count + two moment trees).

    The whole state is built by ONE jitted program, directly under its
    shardings when a mesh is active: params and every optimizer tree
    shaped like them (both Adam moments) by the logical-axis rules,
    scalars replicated.  No device ever holds the unsharded state, and
    the train step meets the layout it keeps."""
    if fused and optimizer is not None:
        raise ValueError("fused=True replaces the optax chain; "
                         "pass hyperparameters, not an optimizer")
    from ray_tpu.parallel.sharding import current_mesh, current_rules

    return _train_state_builder(config, optimizer, fused, current_mesh(),
                                current_rules())(rng)


@functools.lru_cache(maxsize=16)
def _train_state_builder(config: LlamaConfig, optimizer, fused: bool,
                         mesh, rules) -> Callable:
    """The jitted ``rng -> train state`` for one (config, optimizer,
    mesh, rules); cached so repeated inits share one trace and one
    compile."""
    if fused:
        from ray_tpu.train.optim import fused_adamw_init as opt_init
    else:
        opt_init = (optimizer or default_optimizer(
            warmup_steps=config.lr_warmup_steps)).init

    def build(rng):
        params = init_params(rng, config)
        return {
            "params": params,
            "opt_state": opt_init(split_untrained(params)[0]),
            "step": jnp.zeros((), jnp.int32),
        }

    if mesh is None or mesh.size == 1:
        return jax.jit(build)
    from ray_tpu.parallel.sharding import logical_sharding

    param_shardings = jax.tree.map(
        lambda axes: logical_sharding(axes, mesh, rules),
        param_logical_axes(config),
        is_leaf=lambda v: isinstance(v, tuple))
    # (the optimizer's trees are shaped like the params it trains)
    by_def = {jax.tree.structure(tree): tree for tree in (
        split_untrained(param_shardings)[0], param_shardings)}
    replicated = logical_sharding((), mesh, rules)
    shardings = jax.tree.map(
        lambda sub: by_def.get(jax.tree.structure(sub), replicated),
        jax.eval_shape(build, jax.random.key(0)),
        is_leaf=lambda sub: jax.tree.structure(sub) in by_def)
    return jax.jit(build, out_shardings=shardings)


def make_train_step(config: LlamaConfig, optimizer=None,
                    donate: bool = True, fused: bool = False,
                    learning_rate: float = 3e-4) -> Callable:
    """Returns jitted ``train_step(state, batch) -> (state, metrics)``.

    Grad accumulation/clipping live in the optax chain; the step is a
    single XLA program — gradient psums over data/fsdp axes are inserted
    by the compiler from the shardings (no hand-written allreduce).

    ``fused=True`` replaces the optax chain with the single-pass fused
    AdamW (``train/optim.py``): identical hyperparameters and clip
    semantics as ``default_optimizer()``, ~6 tree passes fewer of
    param-sized HBM traffic in the optimizer slice of the step.  Loss
    parity with the optax step is a tier-1 gate."""
    import optax

    if fused:
        if optimizer is not None:
            raise ValueError("fused=True replaces the optax chain; "
                             "pass hyperparameters, not an optimizer")
        from ray_tpu.train.optim import (fused_adamw_update,
                                         fused_hyperparams)

        hp = fused_hyperparams(learning_rate)

        def update(grads, opt_state, trained):
            rate = warmup_rate(learning_rate, config.lr_warmup_steps,
                               opt_state.count + 1)
            return fused_adamw_update(grads, opt_state, trained,
                                      **{**hp, "learning_rate": rate})
    else:
        if optimizer is None:
            optimizer = default_optimizer(learning_rate,
                                          config.lr_warmup_steps)

        def update(grads, opt_state, trained):
            updates, opt_state = optimizer.update(grads, opt_state, trained)
            return (optax.apply_updates(trained, updates), opt_state,
                    optax.global_norm(grads))

    def step(state, batch):
        # What no gradient trains (a router's selection bias) stays out of
        # the differentiated tree, the optimizer and the clipped norm, and
        # is updated by its own rule from the step's expert rows.
        trained, fixed = split_untrained(state["params"])
        (loss, expert_rows), grads = jax.value_and_grad(
            lambda trained: loss_and_expert_rows(
                merge_untrained(trained, fixed), batch, config),
            has_aux=True)(trained)
        with jax.named_scope("optimizer"):
            trained, opt_state, gnorm = update(grads, state["opt_state"],
                                               trained)
        metrics = {"loss": loss, "grad_norm": gnorm,
                   "step": state["step"] + 1}
        if expert_rows is not None:
            metrics["expert_rows"] = expert_rows
            compact = dispatch_compact_share(expert_rows, batch, config)
            if compact is not None:
                metrics["dispatch_compact_share"] = compact
        if fixed:
            if expert_rows is None:
                raise NotImplementedError(
                    "a router's selection bias is balanced by the rows the "
                    "dropless dispatch counts: not under an expert mesh axis")
            fixed = balance_router_bias(fixed, expert_rows, config)
            metrics["router_bias_max"] = jnp.max(jnp.stack([
                jnp.max(jnp.abs(sub["router_bias"]))
                for sub in fixed.values()]))
        new_state = {"params": merge_untrained(trained, fixed),
                     "opt_state": opt_state, "step": metrics["step"]}
        return new_state, metrics

    return _annotate_step(
        jax.jit(step, donate_argnums=(0,) if donate else ()))


class _AnnotatedStep:
    """Stamp each dispatch of the jitted train step with a
    ``jax.profiler.TraceAnnotation`` carrying the ambient trace id
    (observability/device.py): a device trace captured mid-training
    shows ``train.step#trace=<id>`` slices that correlate with the
    cluster timeline.  No-op cost when the device plane is disabled
    (shared nullcontext); everything else of the jitted program's
    surface (``lower``/``trace``/donation semantics) passes through
    untouched via delegation.  With tracing enabled the first dispatch
    also registers the program with the shapes of that call
    (``device.register_program``: what ``device.program_scopes`` later
    reads the step's instruction -> scope map from)."""

    __slots__ = ("_jitted", "_registered")

    def __init__(self, jitted: Callable):
        self._jitted = jitted
        self._registered = False

    def __call__(self, state, batch):
        from ray_tpu.observability import device as _device

        if not self._registered:
            self._registered = True
            from ray_tpu.observability import tracing as _tracing

            if _tracing.enabled():
                _device.register_program("train.step", self._jitted,
                                         (state, batch))
        with _device.annotation("train.step"):
            return self._jitted(state, batch)

    def __getattr__(self, name):
        return getattr(self._jitted, name)


def _annotate_step(jitted: Callable) -> Callable:
    return _AnnotatedStep(jitted)


# ---------------------------------------------------------------------------
# KV-cache decode (serving path)
# ---------------------------------------------------------------------------

def init_kv_cache(config: LlamaConfig, batch: int, max_len: int,
                  dtype: Any = None) -> Dict[str, jax.Array]:
    """Slot-structured KV cache for continuous batching: (L, B, S, Hkv,
    D) per tensor.  The serve replica owns one cache and admits
    requests into free batch slots (reference has no TPU decode loop to
    mirror; design follows the fixed-shape constraint of jit: cache
    shape and batch are static, per-slot positions are data)."""
    c = config
    dt = dtype or c.dtype
    shape = (c.layers_of("attention"), batch, max_len, c.n_kv_heads,
             c.head_dim)
    return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}


def init_paged_kv_cache(config: LlamaConfig, num_blocks: int,
                        block_size: int, dtype: Any = None,
                        kv_quant: Optional[str] = None
                        ) -> Dict[str, jax.Array]:
    """Block-pool KV cache for paged attention (vLLM SOSP '23 shape):
    ``(num_blocks, L, block_size, Hkv, D)`` per tensor.  BLOCK-major —
    one block's K (or V) across all layers is a single contiguous
    slab, so the prefill→decode KV handoff exports per-block zero-copy
    views (cluster/serialization.export_kv_blocks) instead of
    gathering.  Block 0 is reserved as the null/padding block: block
    tables pad with it, attention masks whatever it holds, and
    scatter-back writes land there harmlessly.  Memory scales with
    ``num_blocks`` (live tokens), not ``max_slots × max_len``.

    ``kv_quant`` ("int8"/"fp8", serve/kv_cache.KV_QUANT_FORMATS)
    stores blocks reduced-precision with one f32 scale per KV ROW —
    (block, layer, position, kv_head), ``k_scale``/``v_scale`` shaped
    ``(num_blocks, L, block_size, Hkv)`` — nearly halving the bytes
    per token (values drop 2 bytes → 1, scales add 4/head_dim), which
    the serving plane converts into ~2x the blocks (and therefore
    decode batch width) on the same pool budget.  The decode programs
    dequantize on gather and requantize on scatter
    (``quantize_kv_blocks``/``dequantize_kv_blocks``)."""
    c = config
    shape = (num_blocks, c.n_layers, block_size, c.n_kv_heads,
             c.head_dim)
    if kv_quant is None:
        dt = dtype or c.dtype
        return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}
    from ray_tpu.serve.kv_cache import kv_quant_info

    fmt = kv_quant_info(kv_quant)
    qdt = jnp.dtype(fmt.dtype_name)
    sshape = (num_blocks, c.n_layers, block_size, c.n_kv_heads)
    return {"k": jnp.zeros(shape, qdt), "v": jnp.zeros(shape, qdt),
            "k_scale": jnp.zeros(sshape, jnp.float32),
            "v_scale": jnp.zeros(sshape, jnp.float32)}


def quantize_kv_blocks(x: jax.Array, qmax: float,
                       qdtype: Any) -> Tuple[jax.Array, jax.Array]:
    """Per-(block, layer, position, head) symmetric quantization of KV
    block updates.  x: (N, L, bs, Hkv, D) full precision; returns
    (stored (N, L, bs, Hkv, D) qdtype, scale (N, L, bs, Hkv) f32)
    with ``stored * scale ≈ x``.  One scale per KV ROW (amax over
    head_dim only): rope rotates K rows through position-dependent
    dynamic ranges, so row granularity cuts the error a further ~2-4x
    over per-block-per-head scales for 4/head_dim ≈ 3% extra bytes.
    The amax element maps exactly onto ``±qmax``, which makes
    dequantize→requantize a FIXED POINT: the decode loop re-scatters
    every gathered block each chunk (including untouched COW prefix
    blocks), and without that idempotence shared blocks would drift a
    little every chunk."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=4)
    scale = jnp.maximum(amax, 1e-30) / qmax
    q = xf / scale[..., None]
    if jnp.issubdtype(jnp.dtype(qdtype), jnp.integer):
        q = jnp.clip(jnp.round(q), -qmax, qmax)
    return q.astype(qdtype), scale


def dequantize_kv_blocks(stored: jax.Array, scale: jax.Array,
                         out_dtype: Any) -> jax.Array:
    """Inverse of :func:`quantize_kv_blocks` (same block layout)."""
    return (stored.astype(jnp.float32)
            * scale[..., None]).astype(out_dtype)


def layer_walk(params: PyTree, tokens: jax.Array, config: LlamaConfig,
               kv_step: Callable, positions: Optional[jax.Array] = None,
               kv_layers: Any = None, valid: Optional[jax.Array] = None,
               lengths: Optional[jax.Array] = None,
               window_step: Optional[Callable] = None):
    """The forward pass that serving shares: embed, rope table,
    ``walk_layers`` over ``walk_block``, final norm, head.  The callers
    differ only in ``kv_step``, what an attention layer does with its fresh
    K/V rows and what its queries attend.

    tokens: (B, S); positions: (B, S) absolute, each row's 0..S-1 when
    left out.  ``kv_step(q, k, v, positions, kv_layer) -> (attn, ys)``:
    q (B, S, Hq, D) and k, v (B, S, Hkv, D) roped, ``kv_layer`` this
    layer's slice of ``kv_layers`` (leading dim: the attention layers; a
    cache scanned a layer at a time).  ``valid`` (broadcastable to (B,
    S)) marks the rows that are real: experts compute no others.  With
    ``lengths`` (B,) the logits are those of each row's last real
    position alone, (B, V), ``valid`` defaults to position < length, and
    a Mamba layer's states are those of that position; without, (B, S,
    V) and every position is real.  A Mamba layer starts from an empty
    state: only a cold prefill walks one.  A window layer's queries and
    fresh rows go to ``window_step(q, k, v, positions) -> (attn, ys)``
    instead (no cache is walked for one); of a model with latent attention
    ``kv_step`` gets a group of heads' q, k and v EXPANDED and no cache.
    -> ``(logits, *walk_layers' results)``, the states ``(recurrent, conv)``
    over the Mamba or ``(conv,)`` over the short-convolution layers."""
    c = config
    x = embed(params, tokens, c)
    if positions is None:
        positions = jnp.broadcast_to(
            jnp.arange(tokens.shape[1], dtype=jnp.int32)[None, :],
            tokens.shape)
    sin, cos = rope_for(positions, c)
    if valid is None and lengths is not None:
        valid = positions < lengths[:, None]
    if c.kv_layer is not None:
        x, results = _walk_to_kv_layer(
            x, params, c, kv_step, window_step, kv_layers, positions, valid,
            lengths, sin, cos)
    else:
        block = walk_block(sin, cos, positions, kv_step, window_step, valid,
                           lengths)

        def on_stream(x, *layer_args):
            # serving carries the stream alone: no aux loss, no checkpoint
            x, _aux, rows, ys, _memory = block(x, *layer_args)
            return x, rows, ys

        x, results = walk_layers(x, params, c, on_stream, kv_layers)
    ys, expert_rows, ssm_ys, win_ys = results
    with jax.named_scope("head"):
        x = norm(x, params, "final_norm", c).astype(c.dtype)
        if lengths is None:
            return (head_logits(x, params, c), ys, expert_rows, ssm_ys,
                    win_ys)
        last = x if c.kv_layer is not None else jnp.take_along_axis(
            x, jnp.maximum(lengths - 1, 0)[:, None, None],
            axis=1)                                          # (B,1,H)
        return (head_logits(last, params, c)[:, 0], ys, expert_rows,
                ssm_ys, win_ys)


def _walk_to_kv_layer(x, params, config: LlamaConfig, kv_step, window_step,
                      kv_layers, positions, valid, lengths, sin, cos):
    """``layer_walk``'s middle for a decoder-hybrid-decoder: the layers
    BEFORE the K/V layer over every position (its self-decoder, whose
    states and window rows a cache takes in) and the K/V layer's key and
    value rows at every position; then the K/V layer's own query,
    attention, output projection and FFN, and every gmu and cross layer
    after it, at the positions whose logits are asked for alone -- with
    ``lengths`` each row's last real position, (B, 1, H): the rest of a
    prompt reaches the cross-decoder through the K/V rows and nothing
    else, so a prefill is linear in the prompt and about half the
    model's matmuls.  Without ``lengths`` every position goes on (what a
    test holds the skipping walk to).  -> ``(x at those positions,
    walk_layers' results)``."""
    c = config
    at = None if lengths is None else \
        jnp.maximum(lengths - 1, 0)[:, None]                    # (B, 1)

    def stepping(block):
        def on_stream(stream, *layer_args):
            x, _aux, rows, ys, memory = block(stream[0], *layer_args,
                                              memory=stream[1])
            return (x, memory), rows, ys
        return on_stream

    parts = c.parts()
    head = [part for part in parts if part[2] < c.kv_layer]
    B, S = positions.shape
    memory = jnp.zeros((B, S if at is None else 1, c.ssm_inner), jnp.float32)
    (x, memory), before = walk_layers(
        (x, memory), params, c,
        stepping(walk_block(sin, cos, positions, kv_step, window_step,
                            valid, lengths, memory_at=at)),
        kv_layers, parts=head)
    # The K/V layer is a part of its own (``parts``): its rows at every
    # position, by the projection every other layer's rows come from.
    part, key, _ = parts[len(head)]
    _q, k, v = _qkv_rope(x, jax.tree.map(lambda a: a[0], params[key]),
                         sin, cos, part)
    if at is not None:
        x = jnp.take_along_axis(x, at[:, :, None], axis=1)
        positions, valid = at, (lengths > 0)[:, None]

    def shared_rows(q, positions):
        return _cache_attend(q, k, v, positions, c.attn_scale)

    (x, memory), after = walk_layers(
        (x, memory), params, c,
        stepping(walk_block(
            sin, cos, positions,
            lambda q, _k, _v, positions, _cache: (
                shared_rows(q, positions), (k, v)),
            valid=valid, cross_step=shared_rows)),
        parts=parts[len(head):])
    return x, tuple(over_parts([a, b]) for a, b in zip(before, after))


def over_parts(results):
    """One result of ``layer_walk`` over the parts that have it (None:
    none has), concatenated along the layers where several do."""
    first, *more = [r for r in results if r is not None] or [None]
    if not more:
        return first
    return jax.tree.map(lambda *a: jnp.concatenate(a), first, *more)


def prefill_forward(params: PyTree, tokens: jax.Array,
                    lengths: jax.Array, config: LlamaConfig,
                    return_expert_rows: bool = False):
    """Causal forward over right-padded prompts for cache insertion.

    tokens: (G, P) int32 right-padded prompts; lengths: (G,) real
    lengths.  Runs plain causal attention WITHIN each prompt (no cache
    read — massively cheaper than attending the full slot cache) and
    returns (last_logits (G, V), ks, vs) where ks/vs are (L, G, P,
    Hkv, D) ready to insert into slot caches and last_logits are the
    logits at each prompt's final real token (so the first generated
    token comes out of the prefill call itself — one less decode
    round-trip of TTFT).  Padding rows produce garbage K/V beyond
    lengths; the decode path overwrites each position before it first
    attends it, so they are never observed.  Experts compute the real
    positions only (position < length: a group's padding rows come with
    length 0); ``return_expert_rows`` adds a fourth result, the (L, E)
    int32 rows each layer's experts computed (None for a dense
    config)."""
    last_logits, ks, vs, expert_rows, _states, _window, _index_keys = \
        prefill_with_states(params, tokens, lengths, config)
    if return_expert_rows:
        return last_logits, ks, vs, expert_rows
    return last_logits, ks, vs


def prefill_with_states(params: PyTree, tokens: jax.Array,
                        lengths: jax.Array, config: LlamaConfig):
    """``prefill_forward`` with everything a serving cache takes in:
    ``(last_logits, ks, vs, expert rows, Mamba states, window K/V, index
    keys)`` -- ks/vs over the attention layers alone; the states ``(recurrent (Lm,
    G, N, nh x hd), conv (Lm, K - 1, G, conv_dim))`` as of each row's
    last real position, None for a model without Mamba layers; the
    window layers' ``(ks, vs)`` at every position of the prompt (which
    of them a cache keeps is the cache's business), None without such
    layers.  A model with latent attention attends the EXPANDED form and
    returns its latent rows ``(L, G, P, latent_row)`` as ``ks``, None as
    ``vs``.  Behind an indexer a query attends the keys its index scores
    select (``indexer.prefill_keep``: a mask, the attention computes dense
    masked tiles), and the index keys come back transposed, ``(L, G,
    index_head_dim, P)``, as a serving cache keeps them; None without one.

    Attention is the masked einsum while its (G, Hq, P, P) float32
    scores are small, and the flash forward (``ops/flash_attention.py``,
    scores never leave the chip; a window layer's tiles outside its band
    are skipped, and the q blocks that lie wholly past a row's length)
    for prompts longer than ``FLASH_PREFILL_FROM``."""
    scale = config.attn_scale
    if tokens.shape[1] > FLASH_PREFILL_FROM:  # raylint: disable=recompile-hazard -- the engine's prefill shapes are its buckets, each warmed once; which attention a bucket takes is fixed with its shape
        from ray_tpu.ops.flash_attention import flash_prefill_attention

        # A latent model's call writes no softmax statistics: only a
        # backward pass reads ``lse`` (6 MB for 128 heads at 12,288
        # positions since it is lane-dense, 768 MB as a width-1 column).
        # Nor does the masked call behind an indexer.  Every call is
        # told the rows' ``lengths``: a q block wholly in a row's padding
        # runs no tile and comes back as zeros.
        def attend(q, k, v, positions, window, keep=None):
            return flash_prefill_attention(
                q, k, v, scale=scale, window=window, keep=keep,
                lengths=lengths,
                lse=not (config.kv_lora_rank or config.index_topk
                         or config.diff_attention))
    else:
        def attend(q, k, v, positions, window, keep=None):
            return dot_attention(q, k, v, positions, scale, window, keep)

    def kv_step(q, k, v, positions, _cache, index=None):
        if index is None:
            return attend(q, k, v, positions, None), (k, v)
        from ray_tpu.models import indexer

        qi, ki, w = index
        ki_t = jnp.swapaxes(ki, 1, 2)
        keep = indexer.prefill_keep(qi, ki_t, w, config.index_topk, lengths)
        with jax.named_scope("sparse_attention"):
            return attend(q, k, v, positions, None, keep), (k, v, ki_t)

    def window_step(q, k, v, positions):
        return attend(q, k, v, positions, config.window_size), (k, v)

    last_logits, ys, expert_rows, states, window = layer_walk(
        params, tokens, config, kv_step, lengths=lengths,
        window_step=window_step)
    # latent attention keeps ONE leaf: its rows come back as ``ks``; a
    # model without an attending layer keeps no rows at all
    ks, vs, *index_keys = (ys, None) \
        if config.kv_lora_rank or ys is None else ys
    return (last_logits, ks, vs, expert_rows, states, window,
            index_keys[0] if index_keys else None)


@jax.named_scope("attention")
def _cache_attend(q, ck, cv, q_positions, scale, key_positions=None,
                  key_valid=None):
    """q: (B, T, Hq, D); ck/cv: (B, S, Hkv, D); q_positions: (B, T).
    Causal against absolute cache positions: key j visible to query at
    position p iff j <= p.  With EXPLICIT ``key_positions`` /
    ``key_valid`` (both (B, S)) a key's position is what they say, not
    its index — the warm (prefix-hit) prefill attends [gathered prefix
    blocks || suffix], where a key's gathered index no longer equals its
    absolute position for the suffix half."""
    B, T, Hq, D = q.shape
    S = ck.shape[1]
    Hkv = ck.shape[2]
    group = Hq // Hkv
    qg = q.reshape(B, T, Hkv, group, D)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg, ck,
                        preferred_element_type=jnp.float32) * scale
    if key_positions is None:
        key_pos = jnp.arange(S, dtype=jnp.int32)
        mask = key_pos[None, None, None, None, :] <= \
            q_positions[:, None, None, :, None]
    else:
        mask = (key_valid[:, None, None, None, :]
                & (key_positions[:, None, None, None, :]
                   <= q_positions[:, None, None, :, None]))
    scores = jnp.where(mask, scores, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(scores, axis=-1).astype(cv.dtype)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, cv,
                     preferred_element_type=jnp.float32).astype(cv.dtype)
    return out.reshape(B, T, Hq, D)


def forward_with_cache(params: PyTree, tokens: jax.Array,
                       positions: jax.Array, cache: Dict[str, jax.Array],
                       config: LlamaConfig
                       ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Run T new tokens per slot against the cache.

    tokens: (B, T) int32; positions: (B, T) absolute positions (a
    slot's current length .. +T-1).  Writes the new K/V into the cache
    at those positions and returns (logits (B, T, V), new_cache).  No
    program that serves runs it; tests hold T > 1 through a cache to the
    reference with it."""
    if not config.one_kv_stack:
        raise NotImplementedError(
            "forward_with_cache holds one K/V stack alone; a config with "
            "state-space, short-convolution or window layers, a list of "
            "layer_types or latent attention runs through "
            "llama_serve.build_prefill / build_decode_k")
    scale = config.attn_scale

    # The T new K/V rows go into each slot's cache at its own positions
    # (per-slot write offsets = data, shapes static).
    def write(cache_bslice, rows, pos0):
        return jax.lax.dynamic_update_slice(
            cache_bslice, rows, (pos0, jnp.int32(0), jnp.int32(0)))

    def kv_step(q, k, v, positions, cache_l):
        ck_l, cv_l = cache_l
        with jax.named_scope("kv_write"):
            ck_l = jax.vmap(write)(ck_l, k.astype(ck_l.dtype),
                                   positions[:, 0])
            cv_l = jax.vmap(write)(cv_l, v.astype(cv_l.dtype),
                                   positions[:, 0])
        return (_cache_attend(q, ck_l, cv_l, positions, scale),
                (ck_l, cv_l))

    logits, (new_k, new_v), _rows, _states, _window = layer_walk(
        params, tokens, config, kv_step, positions=positions,
        kv_layers=(cache["k"], cache["v"]))
    return logits, {"k": new_k, "v": new_v}
