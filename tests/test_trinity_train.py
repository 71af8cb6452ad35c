"""Trinity-Mini's shape at toy widths through ``llama.loss_fn`` and the
train step, held to ``benchmarks/references/afmoe_decoder.py`` (float32, a
block of queries against every key under the mask written out, every held
expert on every token): a leading dense window layer, two window expert
layers and a full NoPE one; q/k norm a head, an output gate, sandwich norms,
the embedding's multiplier; a shared expert beside a held range of
sigmoid-routed experts with a selection bias that the step balances.

Everything here is float32 on both sides, where no near-tie of the router
breaks differently: the tolerance is the order of sums alone, and a
bfloat16 term anywhere in the program's float32 path fails it.
"""

import contextlib
import functools
from dataclasses import replace as dataclass_replace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import family
from benchmarks.lib import program
from benchmarks.references import afmoe_decoder as reference
from benchmarks.tools import train_check
from ray_tpu.models import llama, moe
from ray_tpu.models.llama import LlamaConfig

VOCAB, SEQ, WINDOW = 256, 32, 8       # a sequence of four windows
TOL = 1e-4          # float32 both sides, no flips: measured 2e-6
BROKEN = 1e-2       # what a term in the wrong place reads at least
TOY = dict(
    name="toy-afmoe", hidden_act="silu", bias=False, vocab_size=VOCAB,
    hidden_size=64, num_hidden_layers=4, num_attention_heads=4,
    num_key_value_heads=2, head_dim=16, intermediate_size=96,
    max_position_embeddings=64, rope_theta=10000, rms_norm_eps=1e-5,
    tie_word_embeddings=False, sliding_window=WINDOW, num_dense_layers=1,
    layer_types=["sliding_attention"] * 3 + ["full_attention"],
    num_experts=4, num_experts_per_tok=4, num_shared_experts=1,
    moe_intermediate_size=32, route_norm=True, route_scale=2.826,
    mup_enabled=True,
    share=dict(num_experts_published=16, experts_first=4, experts_held=4),
    program_fields=dict(
        layer_types=["window"] * 3 + ["attention"], window_size=WINDOW,
        first_dense_layers=1, nope_kinds=["attention"], qk_head_norm=True,
        attn_gate=True, embedding_multiplier=8.0, sandwich_norm=True,
        moe_experts=16, moe_held=[4, 4], moe_top_k=4, moe_norm_topk=True,
        moe_routed_scale=2.826, moe_router_score="sigmoid",
        moe_router_bias=True, moe_intermediate_size=32, moe_shared_size=32,
        moe_aux_weight=0.0, attention_impl="dot", dtype="float32",
        remat=False))


def _cfg(**over):
    return program.llama_config(TOY, **over)


@pytest.fixture(scope="module")
def model():
    """(config, params, tokens, the reference's loss and gradient, its
    logits).  The norms' weights and the bias are drawn, so that where each
    is applied shows."""
    cfg = _cfg()
    rng = np.random.default_rng(7)

    def drawn(path, leaf):      # (numpy: nothing to compile)
        name, noise = path[-1].key, rng.standard_normal(leaf.shape)
        if name == "router_bias":
            return jnp.asarray(0.2 * noise, jnp.float32)
        if name.endswith("norm"):
            return jnp.asarray(1 + 0.3 * noise, jnp.float32)
        fan_in = leaf.shape[0] if name == "lm_head" else leaf.shape[-2]
        scale = fan_in ** -0.5 / (cfg.embedding_multiplier
                                  if name == "embed_tokens" else 1.0)
        return jnp.asarray(scale * noise, jnp.float32)

    params = jax.tree_util.tree_map_with_path(drawn, jax.eval_shape(
        lambda: llama.init_params(jax.random.key(0), cfg)))
    tokens = np.random.default_rng(1).integers(0, VOCAB, (2, SEQ)).astype(
        np.int32)
    return (cfg, params, tokens, reference.loss_and_grads(params, tokens, TOY),
            reference.logits(params, tokens, TOY))


def _loss_and_gaps(cfg, params, tokens, theirs):
    loss, ours = jax.jit(
        lambda p, b: jax.value_and_grad(llama.loss_fn)(p, b, cfg))(
        params, {"tokens": jnp.asarray(tokens)})
    return float(loss), reference.gradient_gaps(ours, theirs)


def _logit_gap(cfg, params, tokens, theirs):
    """Largest |logit - the reference's| over every position, op by op
    (nothing to compile a variant but the ops it alone has)."""
    with jax.disable_jit():
        return float(jnp.abs(
            llama.forward(params, jnp.asarray(tokens), cfg) - theirs).max())


# ------------------------------------------------ loss and every gradient
def test_loss_and_every_kinds_gradient_are_the_references(model):
    """Through the flash kernels (interpreted) under the cell's remat."""
    cfg, params, tokens, (ref_loss, theirs), _ = model
    fields = {"remat": True, "remat_policy": "attn",
              "attention_impl": "flash"}
    assert [(key, part.period, part.n_layers)
            for part, key, _ in cfg.parts()] == [
        ("dense_layers", ("window",), 1), ("layers", ("window",), 2),
        ("layers_1", ("attention",), 1)]
    loss, gaps = _loss_and_gaps(_cfg(**fields), params, tokens, theirs)
    assert abs(loss - ref_loss) <= TOL * ref_loss
    assert set(gaps) >= {
        "embed_tokens", "lm_head", "final_norm", "post_attn_norm",
        "post_mlp_norm", "q_norm", "k_norm", "w_attn_gate", "router",
        "ws_gate", "w_gate", "dense.w_gate", "router_bias"}
    assert gaps["router_bias"] == 0.0       # zero on both sides, not 0/0
    assert max(gaps.values()) <= TOL, gaps


# ---------------------------- a term of plain_decoder each, in a wrong place
def _bias_in_gates(route):
    def wrong(xt, router, k, norm_topk, groups, top_groups, scale, score,
              bias):
        probs, _, idx = route(xt, router, k, norm_topk, groups, top_groups,
                              scale, score, bias)
        gates = jnp.take_along_axis(probs + bias, idx, axis=-1)
        return probs, scale * gates / (gates.sum(-1, keepdims=True)
                                       + 1e-6), idx
    return wrong


def _gate_before_norm(_gate):
    def wrong(x, attn, layer, c):
        g = llama.matmul(x.astype(c.dtype),
                         layer["w_attn_gate"].astype(c.dtype), jnp.float32)
        return (attn * jax.nn.sigmoid(g).reshape(attn.shape)).astype(
            attn.dtype)
    return wrong


def _norm_after_the_sum(_add):
    def wrong(x, branch, config, post_norm=None):
        return llama.rms_norm(x + branch.astype(x.dtype), post_norm,
                              config.norm_eps)
    return wrong


# term of plain_decoder -> (fields of the config, patch of the program)
WRONG = {
    "intact": ({}, None),
    "window: one key too wide": ({"window_size": WINDOW + 1}, None),
    "nope_kinds: RoPE on the full layer": ({"nope_kinds": ()}, None),
    "qk_head_norm: left out": ({"qk_head_norm": False}, None),
    "moe_router_score: softmax": ({"moe_router_score": "softmax"}, None),
    "moe_router_bias: out of the selection":
        ({"moe_router_bias": False}, None),
    "moe_router_bias: inside the gates":
        ({}, lambda: train_check.patched(moe, "_route", _bias_in_gates)),
    "moe_held: shifted by one expert": ({"moe_held": (5, 4)}, None),
    "moe_shared_size: no shared expert": ({"moe_shared_size": 0}, None),
    "attn_gate: left out": ({"attn_gate": False}, None),
    "attn_gate: before the norm":
        ({}, lambda: train_check.patched(llama, "gate_attention", _gate_before_norm)),
    "embedding_multiplier: left out": ({"embedding_multiplier": 1.0}, None),
    "sandwich_norm: left out": ({"sandwich_norm": False}, None),
    "sandwich_norm: after the sum":
        ({}, lambda: train_check.patched(llama, "residual_add", _norm_after_the_sum)),
    "first_dense_layers: the dense layer's up and gate swapped": (
        "swap", None),
    # benchmarks/tools/train_check.py's, as the chip's readings are of them
    # (PERF.md section 2); the band out of dq shows in a gradient alone:
    # test_the_banded_flash_backward_is_masked_dot_attention
    **{f"train_check: {v}": (train_check.broken_program(v, TOY)[0],
                             train_check.broken_program(v, TOY)[1])
       for v in ("gate_without_sigmoid", "held_shifted")},
}


@pytest.mark.parametrize("term", list(WRONG))
def test_a_term_in_the_wrong_place_reads_over_the_tolerance(model, term):
    """Each term that came out of ``plain_decoder`` is held by the
    comparison: the same weights under a program that has it wrong in one
    place leave some position's logits at least 100 x the tolerance off
    (``intact``: within it)."""
    cfg, params, tokens, _, theirs = model
    fields, patch = WRONG[term]
    if fields == "swap":
        dense = params["dense_layers"]
        params = {**params, "dense_layers": {
            **dense, "w_gate": dense["w_up"], "w_up": dense["w_gate"]}}
        fields = {}
    with (patch() if patch else contextlib.nullcontext()):
        gap = _logit_gap(_cfg(**fields), params, tokens, theirs)
    assert gap <= TOL * 10 if term == "intact" else gap >= BROKEN, gap


# --------------------------------------------------- the banded backward
@pytest.mark.parametrize("seq,window,block", [
    (1536, 384, 512),      # four windows; strips on the diagonal's tiles,
                           # a tile the band's edge crosses, one behind it
])
def test_the_banded_flash_backward_is_masked_dot_attention(seq, window,
                                                           block):
    from ray_tpu.ops.flash_attention import flash_attention

    ks = jax.random.split(jax.random.key(0), 4)
    q, w = (jax.random.normal(k, (1, seq, 2, 32)) for k in ks[:2])
    k, v = (jax.random.normal(k, (1, seq, 1, 32)) for k in ks[2:])
    positions = jnp.arange(seq)[None]

    def flash(q, k, v):
        return jnp.sum(w * flash_attention(
            q, k, v, block_q=block, block_k=block, window=window))

    def dot(q, k, v):
        return jnp.sum(w * llama.dot_attention(q, k, v, positions,
                                               window=window))

    got = jax.jit(jax.value_and_grad(flash, (0, 1, 2)))(q, k, v)
    want = jax.jit(jax.value_and_grad(dot, (0, 1, 2)))(q, k, v)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    for g, d in zip(got[1], want[1]):
        np.testing.assert_allclose(g, d, atol=2e-5)
    # and the band matters: dq built without it is another
    with train_check.broken_program("no_band_in_dq", TOY)[1]():
        wrong = jax.jit(jax.grad(flash))(q, k, v)
    assert float(jnp.abs(wrong - want[1][0]).max()) > 1e-2


def test_a_window_without_a_causal_diagonal_is_refused():
    from ray_tpu.ops.flash_attention import flash_attention

    x = jnp.zeros((1, 128, 2, 16))
    with pytest.raises(ValueError, match="band under the causal"):
        flash_attention(x, x, x, causal=False, window=8)
    with pytest.raises(NotImplementedError, match="has no band"):
        llama.forward(None, jnp.zeros((1, 8), jnp.int32),
                      _cfg(attention_impl="ring"))


# ----------------------------------------------------- shares add up
def test_eight_shares_add_up_to_the_uncut_layer():
    """Eight ranks' routed parts + the shared expert counted once = the
    layer with every expert, forward and in the gradient of a held
    expert's matrices.  One compiled share serves as all eight: rank r is
    rank 0 of the experts renumbered by -2r (the router's columns and the
    bias rolled, its two matrices sliced where they lie)."""
    fields = dict(
        dtype=jnp.float32, n_layers=1, moe_experts=16, moe_top_k=4,
        moe_intermediate_size=32, moe_shared_size=32,
        moe_router_score="sigmoid", moe_router_bias=True,
        moe_routed_scale=2.826, tie_embeddings=False)
    whole = LlamaConfig.debug(**fields)
    share = LlamaConfig.debug(**fields, moe_held=(0, 2))
    layer = jax.tree.map(lambda x: x[0], jax.jit(
        lambda key: llama.init_params(key, whole))(
        jax.random.key(3))["layers"])
    x, w = jax.random.normal(jax.random.key(4), (2, 2, 16, 64))
    stacks = ("w_gate", "w_up", "w_down")
    shared = ("ws_gate", "ws_up", "ws_down")

    def rank(layer, r):
        mine = {**layer,
                "router": jnp.roll(layer["router"], -2 * r, axis=-1),
                "router_bias": jnp.roll(layer["router_bias"], -2 * r),
                **{k: jax.lax.dynamic_slice_in_dim(layer[k], 2 * r, 2)
                   for k in stacks},
                # counted once: rank 0's alone is not zero
                **{k: jnp.where(r == 0, layer[k], 0) for k in shared}}
        return llama.ffn_half(x, mine, share)[0] - x

    want, got, d_whole, d_rank = jax.jit(lambda layer: (
        llama.ffn_half(x, layer, whole)[0] - x,
        jax.lax.map(lambda r: rank(layer, r), jnp.arange(8)).sum(0),
        jax.grad(lambda l: jnp.sum(
            w * (llama.ffn_half(x, l, whole)[0] - x)))(layer),
        jax.grad(lambda l: jnp.sum(w * rank(l, 3)))(layer)))(layer)
    np.testing.assert_allclose(got, want, atol=1e-5)
    for name in stacks:
        np.testing.assert_allclose(d_rank[name][6:8], d_whole[name][6:8],
                                   atol=1e-5)
        assert float(jnp.abs(d_rank[name][:6]).max()) == 0.0


@pytest.mark.parametrize("path", ["compact", "whole"])
def test_rows_no_group_computes_never_reach_the_gradient(monkeypatch, path):
    """On the chip a grouped matmul leaves the rows behind its last group
    as the buffer held them, forward and backward (NaN in the first run of
    the cell, PR 57); the CPU's computes zeros there.  With such rows
    poisoned as the chip leaves them, a share's gradient is what it is
    without: finite, and the same -- in a block of ``moe.compact_rows``
    sorted rows, where they are the rows between the held experts' count
    and the block's end, and on the whole path (the bound at every
    assignment), where they are every assignment elsewhere."""
    real = jax.lax.ragged_dot
    if path == "whole":
        monkeypatch.setattr(moe, "compact_rows", lambda T, c: T * c.top_k)

    def poisoned(lhs, rhs, group_sizes, **kw):
        mine = jnp.arange(lhs.shape[0])[:, None] < jnp.sum(group_sizes)

        @jax.custom_vjp
        def dot(lhs, rhs):
            return jnp.where(mine, real(lhs, rhs, group_sizes, **kw), jnp.nan)

        def fwd(lhs, rhs):
            return dot(lhs, rhs), (lhs, rhs)

        def bwd(saved, g):       # the kernels read their groups' rows alone
            d_lhs, d_rhs = jax.vjp(
                lambda l, r: real(l, r, group_sizes, **kw), *saved)[1](
                jnp.where(mine, g, 0))
            return jnp.where(mine, d_lhs, jnp.nan), d_rhs

        dot.defvjp(fwd, bwd)
        return dot(lhs, rhs)

    cfg = moe.MoEConfig(hidden_size=32, intermediate_size=16, n_experts=8,
                        top_k=2, dtype=jnp.float32, held=(2, 2),
                        score="sigmoid")
    params = moe.init_moe_params(jax.random.key(0), dataclass_replace(
        cfg, held=()))
    params = {k: (v[2:4] if k != "router" else v) for k, v in params.items()}
    x = jax.random.normal(jax.random.key(1), (1, 24, 32))
    held = int(jax.jit(lambda x: moe.moe_ffn_dropless(
        x, params, cfg, training=True)[2])(x)[2:4].sum())
    # some rows are the held experts', and rows lie between them and C
    assert 0 < held < moe.compact_rows(24, cfg) == (24 if path == "compact"
                                                    else 48)

    def grads():        # (traced anew a call: the patch is seen)
        return jax.jit(jax.grad(lambda x, p: jnp.sum(jnp.square(
            moe.moe_ffn_dropless(x, p, cfg, training=True)[0])),
            (0, 1)))(x, params)

    want = grads()
    monkeypatch.setattr(jax.lax, "ragged_dot", poisoned)
    got = grads()
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert bool(jnp.isfinite(g).all())
        np.testing.assert_allclose(g, w, atol=1e-6)


# --------------------------- the dispatch in blocks is the whole dispatch
SHARE = moe.MoEConfig(hidden_size=32, intermediate_size=16, n_experts=16,
                      top_k=4, dtype=jnp.float32, held=(4, 4),
                      score="sigmoid")


@functools.lru_cache(maxsize=None)
def _share_layer(compact: bool, held: int):
    """``(valid, bias) -> (out, expert rows, the gradient of a weighted
    sum by x and every leaf)`` of a share of ``held`` experts from the
    fourth over 2 x 32 tokens: through ``moe_ffn_dropless(training=True)``
    as it is, or with the bound at all 256 assignments, which is the
    program before it had one."""
    cfg = dataclass_replace(SHARE, held=(4, held))
    params = moe.init_moe_params(jax.random.key(0), dataclass_replace(
        SHARE, held=()))
    params = {k: (v if k == "router" else v[4:4 + held])
              for k, v in params.items()}
    x, w = jax.random.normal(jax.random.key(1), (2, 2, 32, 32))

    def run(valid, bias):
        def weighted(x, params):
            out, _aux, rows = moe.moe_ffn_dropless(
                x, {**params, "router_bias": bias}, cfg, valid=valid,
                training=True)
            return jnp.sum(w * out), (out, rows)

        (_, (out, rows)), grads = jax.value_and_grad(
            weighted, (0, 1), has_aux=True)(x, params)
        return out, rows, grads

    run = jax.jit(run)
    if not compact:
        with pytest.MonkeyPatch.context() as patch:
            patch.setattr(moe, "compact_rows", lambda T, c: T * c.top_k)
            run(jnp.ones((2, 32), bool), jnp.zeros(16))      # traced here
    return run


@pytest.mark.parametrize("held,bias,padded,held_rows", [
    (4, 0.0, False, (1, 128)), (4, 0.0, True, (1, 128)),
    (4, 0.35, False, (129, 255)), (4, 10.0, False, (256, 256)),
    (4, -10.0, False, (0, 0)), (5, 10.0, False, (256, 256))],
    ids=["under the bound", "under it beside padding", "over the bound",
         "every assignment held", "none held",
         "every one held and the last block past the end"])
def test_the_compact_dispatch_is_the_whole_one(held, bias, padded,
                                               held_rows):
    """A training share gathers, multiplies and adds back its sorted rows
    in blocks of ``compact_rows`` (128 of 256 for 4 held of 16, 160 for 5:
    the second block then ends past the last assignment), as many as hold
    a held expert's row: one where they fit it, none where nothing is
    held, all where the drawn selection bias sends every assignment here
    (dropless: no routing loses a row).  The output, the router's choices
    and the gradient of every leaf and of x are the program's without a
    bound, however many blocks the step goes through."""
    assert [moe.compact_rows(64, dataclass_replace(SHARE, held=h))
            for h in ((4, 4), (4, 5), (0, 8), ())] == [128, 160, 256, 256]
    valid = jnp.ones((2, 32), bool)
    if padded:
        valid = valid.at[1, 20:].set(False)
    bias = jnp.zeros(16).at[4:4 + held].set(bias)
    got = _share_layer(True, held)(valid, bias)
    want = _share_layer(False, held)(valid, bias)
    np.testing.assert_array_equal(got[1], want[1])
    low, high = held_rows
    assert low <= int(got[1][4:4 + held].sum()) <= high
    assert int(got[1].sum()) == 4 * int(valid.sum())
    for g, w in zip(jax.tree.leaves((got[0], got[2])),
                    jax.tree.leaves((want[0], want[2]))):
        assert bool(jnp.isfinite(g).all())
        np.testing.assert_allclose(g, w, atol=2e-6)


# --------------------------------------------------- the balance update
SMALL = dict(dtype=jnp.float32, n_layers=2, moe_experts=8, moe_top_k=2,
             moe_held=(2, 2), moe_intermediate_size=32, moe_aux_weight=0.0,
             moe_router_score="sigmoid", moe_router_bias=True,
             tie_embeddings=False, lr_warmup_steps=4)
RATE, DECAY = 3e-4, 0.1     # ``make_train_step``'s defaults


@functools.lru_cache(maxsize=None)
def _one_step(fused: bool):
    """The first step of a two-layer expert model, and how far each of its
    first five moves a weight: what the balance and the warm-up tests read,
    as plain data (it crosses ``train.report``)."""
    cfg = LlamaConfig.debug(**SMALL)
    state = llama.init_train_state(jax.random.key(7), cfg, fused=fused)
    tokens = jax.random.randint(jax.random.key(1), (2, 16), 0, 256)
    old = state["params"]["layers"]
    moments = [jax.tree_util.keystr(path) for path, _ in
               jax.tree_util.tree_leaves_with_path(state["opt_state"])]
    step = llama.make_train_step(cfg, fused=fused, donate=False)
    new, m = step(state, {"tokens": tokens})
    moved, at = [], (state, new)
    for _ in range(5):
        moved.append(float(jnp.abs(at[1]["params"]["layers"]["wq"]
                                   - at[0]["params"]["layers"]["wq"]).max()))
        at = (at[1], step(at[1], {"tokens": tokens})[0])
    return {
        "moved_wq": moved,
        # Adam's first update is the gradient's sign beside the decay
        "sign_step": float(jnp.abs(
            new["params"]["layers"]["wq"] - old["wq"]
            + RATE / 4 * DECAY * old["wq"]).max()),
        "moments": moments,
        "bias_old": np.asarray(old["router_bias"]).tolist(),
        "bias_new": np.asarray(new["params"]["layers"]["router_bias"]
                               ).tolist(),
        "router_moved": float(jnp.abs(new["params"]["layers"]["router"]
                                      - old["router"]).max()),
        "rows": np.asarray(m["expert_rows"]).tolist(),
        "compact": float(m["dispatch_compact_share"]),
        "grad_norm": float(m["grad_norm"]),
        "bias_max": float(m["router_bias_max"]), "tokens": tokens.size,
        "rate": cfg.moe_balance_rate, "top_k": cfg.moe_top_k}


def _check_the_balance(got):
    assert got["moments"] and not any("router_bias" in path
                                      for path in got["moments"])
    rows = np.asarray(got["rows"], np.float64)
    # every expert counted, held here or not: tokens x top-k a layer
    assert rows.shape == (2, 8)
    assert (rows.sum(-1) == got["tokens"] * got["top_k"]).all()
    # the layers whose held experts' (2 and 3) rows fitted twice their even
    # share, 32 of the 64 assignments: the dispatch moved those rows alone
    assert got["compact"] == (rows[:, 2:4].sum(-1) <= 32).mean()
    old, new = np.asarray(got["bias_old"]), np.asarray(got["bias_new"])
    d = got["rate"] * np.sign(rows.mean(-1, keepdims=True) - rows)
    # the rule alone moved it: sign, centring, no AdamW step, no decay (a
    # decayed bias would have shrunk by lr x 0.1 x b as well)
    np.testing.assert_allclose(new - old, d - d.mean(-1, keepdims=True),
                               atol=1e-7)
    assert np.abs(new - old).max() > 0
    np.testing.assert_allclose((new - old).sum(-1), 0, atol=1e-6)
    assert got["bias_max"] == pytest.approx(np.abs(new).max())
    assert got["grad_norm"] > 0
    assert got["router_moved"] > 1e-5       # AdamW did step the rest


def test_the_optax_step_balances_the_bias_and_adamw_never_sees_it():
    _check_the_balance(_one_step(fused=False))


def test_the_fused_step_does_through_the_trainer_and_reports_the_rows():
    """Through ``JaxTrainer`` -> ``init_train_state`` / ``make_train_step``,
    the entry points the train cells use: the step's expert rows, the
    bias's largest magnitude and the share of layers dispatched compactly
    go back through ``train.report`` and into the gauges beside
    ``ray_tpu_train_step_seconds``."""
    import ray_tpu
    from ray_tpu import train
    from ray_tpu.observability import device, metrics as obs_metrics
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    def loop(_config):
        train.report(_one_step(fused=True))

    try:
        result = JaxTrainer(
            loop, scaling_config=ScalingConfig(num_workers=1),
            run_config=RunConfig(name="toy-balance")).fit()
    finally:
        ray_tpu.shutdown()
    _check_the_balance(result.metrics)
    device.record_expert_balance(np.asarray([[3, 1], [2, 2]]), 0.25, 0.5)
    summary = obs_metrics.metrics_summary()
    assert summary["ray_tpu_train_dispatch_compact_share"][""] == 0.5
    assert summary["ray_tpu_train_router_bias_max"][""] == 0.25
    assert summary["ray_tpu_train_expert_load_imbalance"][""] == 1.5


@pytest.mark.parametrize("fused", [True, False])
def test_the_rate_warms_up_over_the_configs_steps(fused):
    """``lr_warmup_steps`` = 4: update 1 moves a weight by a quarter of the
    rate, update n by n / 4 of it at most, the fourth and later by the whole
    (the bias's own rule is not slowed: ``_check_the_balance``)."""
    got = _one_step(fused=fused)
    assert got["sign_step"] == pytest.approx(RATE / 4, rel=1e-2)
    for moved, share in zip(got["moved_wq"], (0.25, 0.5, 0.75, 1.0, 1.0)):
        assert moved <= 1.05 * share * RATE
    assert got["moved_wq"][4] > 0.5 * RATE


def test_the_dense_cells_step_lowers_what_the_parent_lowered():
    """A band argument in the backward kernels and a bias leaf in the
    optimizer are where the dense train cells could have been slowed: the
    fused step of a dense flash config under the cells' remat lowers to the
    text the PARENT commit (2a8063a, PR 56) lowered, by sha256."""
    import hashlib

    cfg = LlamaConfig.debug(attention_impl="flash", remat=True,
                            remat_policy="attn")
    state = jax.eval_shape(
        llama._train_state_builder(cfg, None, True, None, None),
        jax.random.key(0))
    text = llama.make_train_step(cfg, fused=True).lower(
        state, {"tokens": jax.ShapeDtypeStruct((2, 128), jnp.int32)}
    ).as_text()
    assert hashlib.sha256(text.encode()).hexdigest()[:16] \
        == "84ad2ba5da1dffe6"


# ------------------------------------------- what forward still refuses
_SSM = dict(ssm_heads=4, ssm_head_dim=16, ssm_state=16, ssm_chunk=8)
REFUSED = {
    "attention_multiplier": dict(attention_multiplier=0.125),
    "logits_scaling": dict(logits_scaling=8.0),
    "moe_router_input": dict(moe_experts=4, moe_router_input="layer"),
    "rope_scaling": dict(rope_scaling={
        "type": "yarn", "factor": 40, "beta_fast": 32, "beta_slow": 1,
        "mscale": 0.707, "mscale_all_dim": 0.707,
        "original_max_position_embeddings": 16}),
    "layer_norm": dict(layer_norm=True),
    "diff_attention": dict(diff_attention=True, rope=False, attn_bias=True),
    "kv_lora_rank": dict(kv_lora_rank=32, q_lora_rank=16,
                         qk_nope_head_dim=8, qk_rope_head_dim=8,
                         v_head_dim=16),
    "index_topk": dict(index_heads=2, index_head_dim=8, index_topk=4),
    "mamba": dict(layer_pattern=("mamba", "attention"), **_SSM),
    "conv": dict(layer_types=("conv", "attention")),
    "mamba1": dict(layer_types=("mamba1", "attention"), ssm_inner=32,
                   ssm_dt_rank=4, ssm_state=8),
    "kda": dict(layer_pattern=("attention", "kda"), kda_heads=4,
                kda_head_dim=16, kda_gate_rank=8, kda_chunk=8),
}


@pytest.mark.parametrize("term", list(REFUSED))
def test_forward_still_refuses_the_terms_no_test_holds(term):
    cfg = LlamaConfig.debug(**REFUSED[term])
    assert not cfg.plain_decoder
    with pytest.raises(NotImplementedError, match="served only"):
        llama.forward(None, jnp.zeros((1, 8), jnp.int32), cfg)


def test_a_pipeline_stage_still_runs_one_stack_alone():
    from ray_tpu.models.llama_pipeline import check_pipeline_config

    cfg = _cfg()
    assert cfg.plain_decoder and not cfg.one_stage_stack
    with pytest.raises(NotImplementedError, match="one stack of one kind"):
        check_pipeline_config(cfg, 2)
    assert LlamaConfig.debug(qk_head_norm=True, attn_gate=True,
                             sandwich_norm=True).one_stage_stack


# ------------------------------------------------------------- and served
def test_prefill_then_decode_through_rings_and_pool_is_the_reference(model):
    """``layer_block`` is one function for training and serving: the two new
    norms land in the prefill and in the decode step too.  Every position's
    logits of the prefill walk, then a prompt that wraps the ring of 8 keys
    decoded 12 steps through rings and pool, against the reference's full
    forward pass."""
    cfg, params, tokens, _, theirs = model
    mine = llama.layer_walk(
        params, jnp.asarray(tokens), cfg,
        lambda q, k, v, pos, _cache: (
            llama.dot_attention(q, k, v, pos, cfg.attn_scale), (k, v)),
        window_step=lambda q, k, v, pos: (
            llama.dot_attention(q, k, v, pos, cfg.attn_scale,
                                window=cfg.window_size), (k, v)))[0]
    assert float(jnp.std(theirs)) > 0.3
    np.testing.assert_allclose(mine, theirs, atol=1e-3)

    n = 20
    assert cfg.max_seq_len == 64
    emitted, _cache = family.serve_one(cfg, params, tokens[0, :n], 13,
                                       slot=1, slots=2, bucket=32)
    gap = reference.teacher_forced_gap(params, tokens[0, :n], emitted, TOY)
    assert len(emitted) == 13 and float(gap.max()) <= 1e-3
