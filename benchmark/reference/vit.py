"""Plain float32 reference: ViT (Dosovitskiy et al. 2020), forward and loss.

Patch embedding as one matrix product over flattened 16x16x3 patches, a
class token, learned position embeddings, pre-LayerNorm encoder blocks with
dense multi-head attention and a GELU MLP, a final LayerNorm and a linear
head on the class token.  ``jax.numpy`` only: no flax, no kernel, nothing
from the program but the names in its parameter tree.  Two departures from
the published model, both the program's and both listed in the
configuration's ``assumed``: GELU in its tanh form, LayerNorm epsilon 1e-6.
Run it under ``jax.default_matmul_precision("highest")``.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

# What `correct` allows between the program under its bf16 policy and this
# reference (same two measures as reference/resnet50.py).  Twelve blocks of
# four bf16 matrix products each, every block's input renormalised by a
# float32 LayerNorm and the softmax kept in float32: rounding of 0.4% a
# product accumulates to about 1% of the largest logit.  Measured on the
# v5e (PR 22): see PERF.md Findings.  The bound is about three times that;
# an 8-bit float in any product, or a bf16 softmax over 197 keys, exceeds it.
TOLERANCE = {"logits_rel": 0.03, "loss_abs": 0.03}

_EPS = 1e-6
_HI = lax.Precision.HIGHEST


def _ln(x, p):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + _EPS) * p["scale"] + p["bias"]


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        jnp.sqrt(2.0 / jnp.pi) * (x + 0.044715 * x ** 3)))


def _dense(x, p):
    return jnp.matmul(x, p["kernel"], precision=_HI) + p["bias"]


def _attention(x, p):
    def proj(name):  # kernel [D, heads, head_dim], bias [heads, head_dim]
        return jnp.einsum("nld,dhk->nlhk", x, p[name]["kernel"],
                          precision=_HI) + p[name]["bias"]
    q, k, v = proj("query"), proj("key"), proj("value")
    scores = jnp.einsum("nqhk,nshk->nhqs", q, k, precision=_HI) / jnp.sqrt(
        jnp.float32(q.shape[-1]))
    scores = scores - scores.max(-1, keepdims=True)
    probs = jnp.exp(scores)
    probs = probs / probs.sum(-1, keepdims=True)
    out = jnp.einsum("nhqs,nshk->nqhk", probs, v, precision=_HI)
    return jnp.einsum("nqhk,hkd->nqd", out, p["out"]["kernel"],
                      precision=_HI) + p["out"]["bias"]


def forward(cfg, params, images):
    """Logits [N, classes] for float32 ``images`` [N, H, W, 3]."""
    n, h, w, c = images.shape
    ps, d = cfg["patch_size"], cfg["hidden_size"]
    gh, gw = h // ps, w // ps
    x = images.astype(jnp.float32).reshape(n, gh, ps, gw, ps, c)
    x = x.transpose(0, 1, 3, 2, 4, 5).reshape(n, gh * gw, ps * ps * c)
    x = _dense(x, params["patch_embed"])
    x = jnp.concatenate(
        [jnp.broadcast_to(params["cls_token"], (n, 1, d)), x], axis=1)
    x = x + jnp.concatenate(
        [params["cls_pos_embedding"],
         params["pos_embedding"].reshape(1, gh * gw, d)], axis=1)
    for i in range(cfg["num_hidden_layers"]):
        p = params[f"encoder_{i}"]
        x = x + _attention(_ln(x, p["ln_1"]), p["self_attention"])
        y = _dense(_gelu_tanh(_dense(_ln(x, p["ln_2"]), p["mlp_fc1"])),
                   p["mlp_fc2"])
        x = x + y
    return _dense(_ln(x, params["ln_f"])[:, 0], params["head"])


def loss(logits, labels):
    """Mean softmax cross-entropy from integer labels."""
    m = logits.max(-1, keepdims=True)
    logp = logits - m - jnp.log(jnp.sum(jnp.exp(logits - m), -1,
                                        keepdims=True))
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], 1))
