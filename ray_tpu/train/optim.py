"""Fused AdamW for the jitted train step.

The default optax chain (``clip_by_global_norm → adamw``) walks the
param pytree ~6 times per step — clip tree, two moment trees, a
bias-corrected update tree, a weight-decay tree, and the final
``apply_updates`` tree — and every intermediate tree is a full set of
f32 param-sized HBM buffers XLA must materialize between
transformations.  At the 435M bench the optimizer slice of the step is
pure HBM bandwidth (step time less gradient time), so the fused
variant computes the SAME math in ONE ``tree_map`` pass per leaf:

    gscale    = min(1, clip / ||g||)          (one global reduction)
    mu        = b1*mu + (1-b1)*g'
    nu        = b2*nu + (1-b2)*g'^2
    p        -= lr * (mu_hat / (sqrt(nu_hat) + eps) + wd*p)

per leaf in one fused expression, so XLA emits a single
read-g/read-p/read-moments → write-p/write-moments kernel per param
instead of a chain of seven.  Numerics replicate the installed optax
implementations exactly (same clip trigger semantics, same bias
correction ``1 - b**t``), so loss curves are parity up to float
reassociation — asserted by ``tests/test_models.py``'s fused-vs-optax
parity gate.
"""

from __future__ import annotations

from typing import Any, Dict, NamedTuple

import jax
import jax.numpy as jnp

PyTree = Any


class FusedAdamWState(NamedTuple):
    count: jax.Array  # int32 step counter (optax-compatible semantics)
    mu: PyTree
    nu: PyTree


def fused_adamw_init(params: PyTree) -> FusedAdamWState:
    zeros = jax.tree.map(jnp.zeros_like, params)
    return FusedAdamWState(count=jnp.zeros((), jnp.int32), mu=zeros,
                           nu=jax.tree.map(jnp.zeros_like, params))


def global_norm(tree: PyTree) -> jax.Array:
    return jnp.sqrt(sum(
        jnp.sum(jnp.square(x.astype(jnp.float32)))
        for x in jax.tree.leaves(tree)))


def fused_adamw_update(grads: PyTree, state: FusedAdamWState,
                       params: PyTree, *, learning_rate: float = 3e-4,
                       b1: float = 0.9, b2: float = 0.999,
                       eps: float = 1e-8, weight_decay: float = 0.1,
                       clip_norm: float = 1.0) -> tuple:
    """One fused step; returns ``(new_params, new_state, grad_norm)``
    (grad_norm is the PRE-clip norm, matching the train-step metric)."""
    gnorm = global_norm(grads)
    # optax.clip_by_global_norm semantics: scale only when the norm
    # exceeds the bound (lax.select on the trigger, not a min() — the
    # grad flows differ under meta-gradients, and parity is the point).
    trigger = gnorm < clip_norm
    count = state.count + jnp.ones((), jnp.int32)
    c1 = 1.0 - b1 ** count.astype(jnp.float32)
    c2 = 1.0 - b2 ** count.astype(jnp.float32)

    def leaf(p, g, mu, nu):
        g = jax.lax.select(trigger, g,
                           (g / gnorm.astype(g.dtype)) * clip_norm)
        mu = b1 * mu + (1.0 - b1) * g
        nu = b2 * nu + (1.0 - b2) * jnp.square(g)
        update = (mu / c1) / (jnp.sqrt(nu / c2) + eps) \
            + weight_decay * p
        return p - learning_rate * update, mu, nu

    out = jax.tree.map(leaf, params, grads, state.mu, state.nu)
    new_params = jax.tree.map(lambda t: t[0], out,
                              is_leaf=lambda t: isinstance(t, tuple))
    new_mu = jax.tree.map(lambda t: t[1], out,
                          is_leaf=lambda t: isinstance(t, tuple))
    new_nu = jax.tree.map(lambda t: t[2], out,
                          is_leaf=lambda t: isinstance(t, tuple))
    return (new_params,
            FusedAdamWState(count=count, mu=new_mu, nu=new_nu), gnorm)


def fused_hyperparams(learning_rate: float = 3e-4) -> Dict[str, float]:
    """The hyperparameters matching ``models.llama.default_optimizer``
    (the parity baseline the fused step must reproduce)."""
    return dict(learning_rate=learning_rate, b1=0.9, b2=0.999,
                eps=1e-8, weight_decay=0.1, clip_norm=1.0)
