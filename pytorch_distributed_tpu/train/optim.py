"""SGD with exactly the reference optimizer's update semantics.

The reference uses ``torch.optim.SGD(lr, momentum=0.9, weight_decay=1e-4)``
(reference distributed.py:153-156).  Torch semantics, which differ from some
JAX-ecosystem defaults and therefore warrant this ~40-line pure implementation:

- weight decay is *coupled* (added to the gradient): ``g = g + wd * p``
- momentum buffer: ``buf = mu * buf + g`` (dampening 0, no bias correction)
- update: ``p = p - lr * buf``  (LR multiplies the *buffer*, so step-decay LR
  takes effect immediately, mid-momentum — exactly like torch)

Implemented as init/update pure functions over pytrees so the update lives
inside the jitted SPMD step; ``lr`` is a traced scalar operand.  An optax
optimizer can be substituted anywhere the harness accepts ``tx`` — this module
is the default because its numerics are the parity target.

``--zero wus`` (parallel/zero.py) re-implements exactly this ``_upd`` on flat
1/N parameter chunks so the weight-update-sharded step is bit-compatible with
the replicated one: any change to the update math here must be mirrored in
``zero.wus_apply_updates`` (the 3-step parity fence in tests/test_zero.py
catches drift).
"""

from __future__ import annotations

from typing import Any, Tuple

import jax
import jax.numpy as jnp

Pytree = Any


def sgd_init(params: Pytree) -> Pytree:
    """Zero momentum buffers shaped like ``params``."""
    return jax.tree_util.tree_map(jnp.zeros_like, params)


def sgd_update(
    grads: Pytree,
    momentum_buf: Pytree,
    params: Pytree,
    lr: jnp.ndarray | float,
    momentum: float = 0.9,
    weight_decay: float = 1e-4,
) -> Tuple[Pytree, Pytree]:
    """One SGD step; returns ``(new_params, new_momentum_buf)``.

    Momentum/weight-decay math runs in the parameter dtype's f32 master copy —
    callers keep params in f32 and cast to bf16 only for compute (the
    apex-recipe-equivalent policy, SURVEY.md §7.1).
    """

    def _upd(g, buf, p):
        g = g + weight_decay * p
        buf = momentum * buf + g
        return p - lr * buf, buf

    flat = jax.tree_util.tree_map(_upd, grads, momentum_buf, params)
    new_params = jax.tree_util.tree_map(lambda t: t[0], flat, is_leaf=lambda t: isinstance(t, tuple))
    new_buf = jax.tree_util.tree_map(lambda t: t[1], flat, is_leaf=lambda t: isinstance(t, tuple))
    return new_params, new_buf


def adamw(settings: dict, learning_rate=None):
    """AdamW as an optax ``tx`` from a configuration file's ``optimizer``
    group (``lr``, ``b1``, ``b2``, ``eps``, ``weight_decay``): float32
    moments, decoupled weight decay on matrices only (norm scales and the
    router's selection bias are not decayed).  ``learning_rate`` (a value or
    an optax schedule) overrides the group's ``lr``."""
    import optax

    return optax.adamw(
        settings["lr"] if learning_rate is None else learning_rate,
        b1=settings["b1"], b2=settings["b2"], eps=settings["eps"],
        weight_decay=settings["weight_decay"],
        mask=lambda params: jax.tree_util.tree_map(
            lambda p: p.ndim >= 2, params))
