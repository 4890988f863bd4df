"""Plain float32 reference: torchvision's bottleneck ResNet, forward and loss.

He et al. 2015 with torchvision's v1.5 placement (the stride sits on the
3x3 convolution), train-mode batch norm (statistics of the batch itself,
biased variance, epsilon 1e-5).  ``jax.numpy`` and ``lax`` only: no flax, no
kernel, nothing from the program.  It reads the program's parameter tree
(``conv_init``, ``bn_init``, ``Bottleneck_<n>/Conv_<k>``,
``FusedBatchNormAct_<k>``, ``fc``), which is the only thing it knows of it.
Run it under ``jax.default_matmul_precision("highest")``: on a TPU a
float32 convolution is otherwise computed in bfloat16 passes.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

# What `correct` allows between the program under its bf16 policy and this
# reference, on 16 seeded images with the weights the run starts from.
# logits_rel is max|difference| over max|reference logit|, loss_abs the
# difference of the two mean cross-entropies.  bf16 keeps 8 bits, a relative
# step of 0.4%; rounding at each of the ~50 convolution outputs adds up like
# a random walk, a few steps in all, because batch norm renormalises every
# layer.  Measured on the v5e (PR 22): see PERF.md Findings for the values.
# The bound is about three times that, and well under what an 8-bit
# float (relative step 6%, or 12% for e5m2) or bf16 batch-norm statistics
# would give, so computing below the stated precision fails it.
TOLERANCE = {"logits_rel": 0.03, "loss_abs": 0.03}

_EPS = 1e-5


def check_params(params):
    """The weights the comparison uses: the run's start weights, except that
    a batch-norm scale that starts at 0 (the last of every block) is 0.25.
    With the scales at 0 no block's three convolutions would reach the
    logits, and the comparison would cover the stem, the four projection
    shortcuts and the head alone.  At 0.25 every block adds a quarter of
    its branch; at 1 the sixteen blocks amplify bf16's rounding to 12% of
    the largest logit (CPU, 224x224), too much to tell precisions apart."""
    def wake(path, leaf):
        if getattr(path[-1], "key", None) != "scale":
            return leaf
        return jnp.where(jnp.all(leaf == 0), jnp.full_like(leaf, 0.25), leaf)
    return jax.tree_util.tree_map_with_path(wake, params)


def _conv(x, w, stride: int = 1):
    pad = (w.shape[0] - 1) // 2
    return lax.conv_general_dilated(
        x, w.astype(jnp.float32), (stride, stride),
        [(pad, pad), (pad, pad)],
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=lax.Precision.HIGHEST)


def _bn(x, p, relu: bool):
    mean = x.mean((0, 1, 2))
    var = ((x - mean) ** 2).mean((0, 1, 2))
    y = (x - mean) / jnp.sqrt(var + _EPS) * p["scale"] + p["bias"]
    return jnp.maximum(y, 0.0) if relu else y


def _bottleneck(x, p, stride: int):
    y = _bn(_conv(x, p["Conv_0"]["kernel"]), p["FusedBatchNormAct_0"], True)
    y = _bn(_conv(y, p["Conv_1"]["kernel"], stride),
            p["FusedBatchNormAct_1"], True)
    y = _bn(_conv(y, p["Conv_2"]["kernel"]), p["FusedBatchNormAct_2"], False)
    if "Conv_3" in p:  # projection shortcut
        x = _bn(_conv(x, p["Conv_3"]["kernel"], stride),
                p["FusedBatchNormAct_3"], False)
    return jnp.maximum(y + x, 0.0)


def forward(cfg, params, images):
    """Logits [N, classes] for float32 ``images`` [N, H, W, 3], train mode."""
    x = images.astype(jnp.float32)
    x = _bn(_conv(x, params["conv_init"]["kernel"], 2), params["bn_init"],
            True)
    x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
                          [(0, 0), (1, 1), (1, 1), (0, 0)])
    n = 0
    for i, blocks in enumerate(cfg["stage_sizes"]):
        for j in range(blocks):
            x = _bottleneck(x, params[f"Bottleneck_{n}"],
                            2 if (i > 0 and j == 0) else 1)
            n += 1
    x = x.mean((1, 2))
    return jnp.matmul(x, params["fc"]["kernel"],
                      precision=lax.Precision.HIGHEST) + params["fc"]["bias"]


def loss(logits, labels):
    """Mean softmax cross-entropy from integer labels."""
    m = logits.max(-1, keepdims=True)
    logp = logits - m - jnp.log(jnp.sum(jnp.exp(logits - m), -1,
                                        keepdims=True))
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], 1))
