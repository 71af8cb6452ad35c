"""A dense layer's weight gradients under a mesh
(``llama.scattered_grad_matmul`` at ``wo``, ``w_gate``, ``w_up``,
``w_down``): the backward forms each device's partial product in blocks of
the dimension the mesh shards -- rows of ``w_gate`` / ``w_up``, columns of
``wo`` / ``w_down`` -- and reduces it scattered.  On the simulated CPU
devices, float32, toy widths, two layers under the scan and the remat that
training runs: loss and every gradient leaf of ``loss_fn`` under the mesh
against the same on one device, each layer weight's gradient laid out as
the rules lay the weight out; where nothing is laid out (no mesh, a manual
region such as a pipeline's stage) or the axes do not divide the width,
the traced program is the plain matmul's."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_head_grad_scatter import MESHES, _primitives

from ray_tpu.models import llama
from ray_tpu.parallel import MeshSpec, use_mesh
from ray_tpu.parallel.sharding import (logical_sharding, partitioning_mesh,
                                       suppress_constraints)

EXCHANGED = {"wo": ("heads", "embed"), "w_gate": ("embed", "mlp"),
             "w_up": ("embed", "mlp"), "w_down": ("mlp", "embed")}
SCATTER = {"shard_map", "ppermute", "psum_scatter", "reduce_scatter"}


def _config(tied=True):
    return llama.LlamaConfig.debug(dtype=jnp.float32, tie_embeddings=tied,
                                   remat=True)


def _loss_and_grads(cfg, params, batch):
    return jax.jit(jax.value_and_grad(
        lambda p: llama.loss_fn(p, batch, cfg)))(params)


@pytest.mark.parametrize("tied", [True, False], ids=["tied", "untied"])
@pytest.mark.parametrize("mesh", MESHES, ids=lambda m: "x".join(
    f"{k}{v}" for k, v in m.items()))
def test_loss_and_every_gradient_match_one_device(mesh, tied):
    cfg = _config(tied)
    params = llama.init_params(jax.random.key(0), cfg)
    batch = {"tokens": jax.random.randint(jax.random.key(1), (8, 32), 0,
                                          cfg.vocab_size, jnp.int32)}
    ref_loss, ref_grads = _loss_and_grads(cfg, params, batch)
    with use_mesh(MeshSpec(**mesh).build(jax.devices()[:4])):
        loss, grads = _loss_and_grads(cfg, params, batch)
        for name, axes in EXCHANGED.items():
            assert grads["layers"][name].sharding.is_equivalent_to(
                logical_sharding(("layers",) + axes), 3), name
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
    assert jax.tree.structure(grads) == jax.tree.structure(ref_grads)
    for (path, got), want in zip(jax.tree.leaves_with_path(grads),
                                 jax.tree.leaves(ref_grads)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-6, err_msg=str(path))


def _layer_grad_primitives(cfg):
    """What the gradient of a layer's output projection and FFN traces."""
    layer = jax.tree.map(lambda leaf: leaf[0], llama.init_params(
        jax.random.key(0), cfg)["layers"])
    x = jnp.ones((4, 8, cfg.hidden_size), cfg.dtype)
    attn = jnp.ones((4, 8, cfg.n_heads, cfg.head_dim), cfg.dtype)
    return _primitives(jax.make_jaxpr(jax.grad(
        lambda layer, x: llama.attn_out_ffn(x, attn, layer, cfg)[0].sum(),
        argnums=(0, 1)))(layer, x).jaxpr)


def test_a_layer_scatters_under_a_mesh_only():
    """No mesh, one device and a manual region (a pipeline stage's layers
    are traced under ``suppress_constraints``) trace the plain matmuls and
    their own backward; a mesh that shards the batch traces the exchange."""
    cfg = _config()
    plain = _layer_grad_primitives(cfg)
    assert not plain & (SCATTER | {"custom_vjp_call"})
    with use_mesh(MeshSpec(fsdp=4).build(jax.devices()[:4])):
        assert {"shard_map", "ppermute"} <= _layer_grad_primitives(cfg)
        with suppress_constraints():
            assert partitioning_mesh() is None
            assert _layer_grad_primitives(cfg) == plain
    with use_mesh(MeshSpec(fsdp=1).build(jax.devices()[:1])):
        assert _layer_grad_primitives(cfg) == plain


def _matmul_grad_primitives(shape, axes):
    x = jnp.ones((4, 8, shape[0]), jnp.float32)
    w = jnp.ones(shape, jnp.float32)
    return _primitives(jax.make_jaxpr(jax.grad(
        lambda x, w: llama.scattered_grad_matmul(x, w, axes).sum(),
        argnums=(0, 1)))(x, w).jaxpr)


@pytest.mark.parametrize("shape,axes,scatters", [
    ((64, 128), ("embed", "mlp"), True),        # rows over fsdp
    ((128, 64), ("mlp", "embed"), True),        # columns over fsdp
    ((66, 128), ("embed", "mlp"), False),       # 66 rows, four devices
    ((128, 66), ("mlp", "embed"), False),       # 66 columns
    ((64, 128), ("heads", "mlp"), False),       # neither dimension sharded
], ids=["rows", "columns", "rows-not-divided", "columns-not-divided",
        "nothing-sharded"])
def test_which_dimension_is_scattered_is_read_from_the_rules(shape, axes,
                                                             scatters):
    """Rows for a weight laid out ``("embed", ...)``, columns for ``(...,
    "embed")``; a width the axes do not divide, or no dimension over the
    batch's axes, is ``matmul``."""
    plain = _matmul_grad_primitives(shape, axes)
    assert not plain & SCATTER
    with use_mesh(MeshSpec(fsdp=4).build(jax.devices()[:4])):
        found = _matmul_grad_primitives(shape, axes)
    assert ({"shard_map", "ppermute"} <= found) if scatters \
        else found == plain


@pytest.mark.parametrize("mesh", MESHES, ids=lambda m: "x".join(
    f"{k}{v}" for k, v in m.items()))
@pytest.mark.parametrize("shape,axes", [
    ((64, 128), ("embed", "mlp")), ((128, 64), ("mlp", "embed"))],
    ids=["rows", "columns"])
def test_one_matmuls_gradients_match_the_plain_matmuls(mesh, shape, axes):
    """The function alone, both ways round, under each mesh: output and
    both gradients are ``matmul``'s, the weight's laid out as the weight."""
    x = jax.random.normal(jax.random.key(3), (8, 16, shape[0]), jnp.float32)
    w = jax.random.normal(jax.random.key(4), shape, jnp.float32)

    def run(fn):
        return jax.jit(jax.value_and_grad(
            lambda x, w: jnp.sum(jnp.sin(fn(x, w))), argnums=(0, 1)))(x, w)

    want, (want_dx, want_dw) = run(llama.matmul)
    with use_mesh(MeshSpec(**mesh).build(jax.devices()[:4])):
        got, (dx, dw) = run(
            lambda x, w: llama.scattered_grad_matmul(x, w, axes))
        assert dw.sharding.is_equivalent_to(logical_sharding(axes), 2)
    np.testing.assert_allclose(float(got), float(want), rtol=1e-4)
    np.testing.assert_allclose(np.asarray(dx), np.asarray(want_dx),
                               rtol=1e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(dw), np.asarray(want_dw),
                               rtol=1e-4, atol=2e-4)
