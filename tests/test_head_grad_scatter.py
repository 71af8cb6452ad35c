"""The head's weight gradient under a mesh (``llama.scattered_grad_matmul``): the
backward forms each device's partial product and reduces it scattered over
the mesh axes that shard the weight's rows.  On the simulated CPU devices,
float32, toy widths: loss and every gradient leaf of ``loss_fn`` under the
mesh against the same on one device, the head's gradient laid out as the
rules lay the weight out; where nothing is laid out (no mesh, a manual
region, the pipeline's stage) the traced program is the plain matmul's."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import llama
from ray_tpu.parallel import MeshSpec, use_mesh
from ray_tpu.parallel.sharding import (logical_sharding, partitioning_mesh,
                                       suppress_constraints)

MESHES = [{"fsdp": 4}, {"data": 2, "fsdp": 2}, {"fsdp": 2, "tensor": 2}]


def _config(tied):
    return llama.LlamaConfig.debug(dtype=jnp.float32, tie_embeddings=tied)


def _batch(cfg):
    return {"tokens": jax.random.randint(jax.random.key(1), (8, 32), 0,
                                         cfg.vocab_size, jnp.int32)}


def _loss_and_grads(cfg, params, batch):
    return jax.jit(jax.value_and_grad(
        lambda p: llama.loss_fn(p, batch, cfg)))(params)


@pytest.mark.parametrize("tied", [True, False], ids=["tied", "untied"])
@pytest.mark.parametrize("mesh", MESHES, ids=lambda m: "x".join(
    f"{k}{v}" for k, v in m.items()))
def test_loss_and_every_gradient_match_one_device(mesh, tied):
    cfg = _config(tied)
    params = llama.init_params(jax.random.key(0), cfg)
    batch = _batch(cfg)
    ref_loss, ref_grads = _loss_and_grads(cfg, params, batch)
    with use_mesh(MeshSpec(**mesh).build(jax.devices()[:4])):
        loss, grads = _loss_and_grads(cfg, params, batch)
        head = "embed_tokens" if tied else "lm_head"
        axes = ("vocab", "embed") if tied else ("embed", "vocab")
        assert grads[head].sharding.is_equivalent_to(
            logical_sharding(axes), grads[head].ndim)
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
    assert jax.tree.structure(grads) == jax.tree.structure(ref_grads)
    for (path, got), want in zip(jax.tree.leaves_with_path(grads),
                                 jax.tree.leaves(ref_grads)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-6, err_msg=str(path))


def _primitives(jaxpr, found=None):
    found = set() if found is None else found
    for eqn in jaxpr.eqns:
        found.add(eqn.primitive.name)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _primitives(sub, found)
    return found


def _head_grad_primitives(cfg):
    x = jnp.ones((4, 8, cfg.hidden_size), cfg.dtype)
    w = jnp.ones((cfg.hidden_size, cfg.vocab_size), cfg.dtype)
    return _primitives(jax.make_jaxpr(jax.grad(
        lambda x, w: llama.scattered_grad_matmul(
            x, w, ("embed", "vocab")).sum(), argnums=(0, 1)))(
            x, w).jaxpr)


def test_the_scatter_is_there_under_a_mesh_only():
    """One device, no mesh and a manual region trace the plain matmul and
    its own backward; a mesh that shards the rows traces the scatter."""
    cfg = _config(False)
    plain = _head_grad_primitives(cfg)
    assert not plain & {"shard_map", "custom_vjp_call", "psum_scatter",
                        "reduce_scatter", "ppermute"}
    with use_mesh(MeshSpec(fsdp=4).build(jax.devices()[:4])):
        assert partitioning_mesh() is not None
        assert "shard_map" in _head_grad_primitives(cfg)
        with suppress_constraints():
            assert partitioning_mesh() is None
            assert _head_grad_primitives(cfg) == plain
    with use_mesh(MeshSpec(fsdp=1).build(jax.devices()[:1])):
        assert partitioning_mesh() is None
        assert _head_grad_primitives(cfg) == plain


def test_the_pipelines_last_stage_under_its_own_mesh():
    """``llama_pipeline``'s last stage calls the same head: its loss and
    the gradients of its parameters and of its input under an fsdp mesh
    are the unsharded stage's."""
    from ray_tpu.models import llama_pipeline

    cfg = _config(False)
    params = llama.init_params(jax.random.key(0), cfg)
    stage = llama_pipeline.stage_slice(params, 1, 2)
    tokens = _batch(cfg)["tokens"]
    h_in = jax.random.normal(jax.random.key(2), (8, 32, cfg.hidden_size),
                             cfg.dtype)

    def fwd_loss():     # traced anew under whatever mesh is active
        return jax.jit(jax.value_and_grad(
            llama_pipeline.make_stage_fwd_loss(cfg), argnums=(0, 1)))(
                stage, h_in, tokens)

    ref_loss, ref_grads = fwd_loss()
    with use_mesh(MeshSpec(fsdp=4).build(jax.devices()[:4])):
        loss, grads = fwd_loss()
        assert grads[0]["lm_head"].sharding.is_equivalent_to(
            logical_sharding(("embed", "vocab")), 2)
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
    for got, want in zip(jax.tree.leaves(grads), jax.tree.leaves(ref_grads)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-6)


LAYERS = "jit(step)/transpose(jvp(layer_scan))/while/body/closed_call/" \
         "checkpoint/ffn/shard_map/ppermute"
HEAD = "jit(step)/transpose(jvp(head_loss))/shard_map/ppermute"
PIPELINE = "jit(step)/jvp(layer_scan)/shard_map/while/body/ppermute"


@pytest.mark.parametrize("op_name,row", [
    (None, "%collective-permute-start.3"),
    (LAYERS, "layers' gradient exchange, ffn"),
    (HEAD, "head's gradient exchange"),
    (PIPELINE, "%collective-permute-start.3"),
], ids=["by-op", "layers-exchange", "heads-exchange", "not-an-exchange"])
def test_collectives_alone_counts_each_op_once_and_fusions_apart(op_name,
                                                                 row):
    """``tools.collectives_alone.by_op`` on a synthetic chip: an async
    permute in flight under a matmul and alone past its end, a sync
    all-reduce alone, a fused reduce-scatter that no reader counts.  The
    permute is a row of its own unless the compiled step's text gives it
    the ``op_name`` of a weight gradient's exchange (``exchanges``): the
    layers' by their scope, apart from the head's and from any other
    permute."""
    from tools.collectives_alone import by_op, exchanges

    permute = "%collective-permute-start.3 = (f32[512,92544]) " \
              "collective-permute-start(%fusion.1)"
    reduce = "%all-reduce.27 = bf16[2048,92544] all-reduce(%fusion.2)"
    fused = "%fusion.18 = bf16[92544,512] fusion(%fusion.3), " \
            "kind=kCustom, calls=%all-reduce-scatter.7"
    matmul = "%fusion.9 = bf16[4096,2048] fusion(%p.1), kind=kOutput"
    ops = [(0.0, 0.1, permute), (0.1, 4.0, matmul), (6.0, 9.0, reduce),
           (9.0, 11.0, fused)]
    compiled = "\n".join(
        f"  {text}, metadata={{op_name=\"{name}\"}}" for text, name in [
            (permute, op_name), (matmul, "jit(step)/jvp(ffn)/dot_general")]
        if name)
    rows_of = exchanges(compiled)
    assert list(rows_of.values()) == ([row] if row[0] != "%" else [])
    collectives, fusions = by_op(ops, [(0.0, 5.0, permute)], rows_of)
    assert [(n if n == row else n.split()[0], round(t, 6), round(a, 6), c)
            for n, t, a, c in collectives] == [
        ("%all-reduce.27", 3.0, 3.0, 1), (row, 5.0, 1.1, 1)]
    assert [(n.split()[0], t, c) for n, t, c in fusions] == [
        ("%fusion.18", 2.0, 1)]
