"""Operations a training step needs, from the configuration's own sizes.

The yardstick's copy of the arithmetic in the program's ``obs/flops.py``
(``_resnet_walk``, ``_vit_walk``): a multiply-add is 2 operations, a
convolution tap that falls on padding costs nothing (XLA's convention),
backward is twice forward, nothing is recomputed, and the optimizer's few
operations per parameter are left out.  A configuration file names its
function under ``"flops"``; ``train_flops_per_item`` looks it up here, or
in ``flops_<name>.py`` beside this file for a model a later PR brings.
"""

from __future__ import annotations

import importlib.util
import os
from typing import Callable, Dict


def _valid_taps(size: int, k: int, stride: int, pad: int) -> int:
    """Kernel taps inside the image, summed over one dimension's outputs."""
    out = (size + 2 * pad - k) // stride + 1
    total = 0
    for o in range(out):
        start = o * stride - pad
        total += max(0, min(start + k, size) - max(start, 0))
    return total


def _conv(h: int, w: int, cin: int, cout: int, k: int, stride: int = 1):
    pad = k // 2
    flops = 2.0 * cout * cin * _valid_taps(h, k, stride, pad) * _valid_taps(
        w, k, stride, pad)
    return flops, (h + 2 * pad - k) // stride + 1, (w + 2 * pad - k) // stride + 1


def resnet_forward_flops(cfg: Dict) -> float:
    """Forward operations for one image: torchvision's bottleneck ResNet."""
    if cfg["block"] != "bottleneck":
        raise ValueError(f"no count for block {cfg['block']!r}")
    size, exp = cfg["image_size"], cfg["expansion"]
    total, h, w = _conv(size, size, cfg["num_channels"],
                        cfg["stage_widths"][0], 7, 2)
    h, w = (h + 2 - 3) // 2 + 1, (w + 2 - 3) // 2 + 1  # max-pool 3x3 s2 p1
    c = cfg["stage_widths"][0]
    for i, (blocks, width) in enumerate(zip(cfg["stage_sizes"],
                                            cfg["stage_widths"])):
        for j in range(blocks):
            s = 2 if (i > 0 and j == 0) else 1
            f1, _, _ = _conv(h, w, c, width, 1)
            f2, h2, w2 = _conv(h, w, width, width, 3, s)
            f3, _, _ = _conv(h2, w2, width, width * exp, 1)
            total += f1 + f2 + f3
            if c != width * exp or s > 1:
                total += _conv(h, w, c, width * exp, 1, s)[0]
            h, w, c = h2, w2, width * exp
    return total + 2.0 * c * cfg["num_classes"]


def vit_forward_flops(cfg: Dict) -> float:
    """Forward operations for one image: ViT encoder, dense attention."""
    d, mlp = cfg["hidden_size"], cfg["intermediate_size"]
    grid = cfg["image_size"] // cfg["patch_size"]
    tokens = grid * grid + 1  # the class token
    total = 2.0 * grid * grid * cfg["patch_size"] ** 2 * cfg["num_channels"] * d
    per_layer = (2.0 * tokens * d * 3 * d      # q, k, v
                 + 4.0 * tokens * tokens * d   # scores and weighted sum
                 + 2.0 * tokens * d * d        # output projection
                 + 4.0 * tokens * d * mlp)     # the two MLP layers
    return total + cfg["num_hidden_layers"] * per_layer + 2.0 * d * cfg[
        "num_classes"]


_FORWARD: Dict[str, Callable[[Dict], float]] = {
    "resnet": resnet_forward_flops,
    "vit": vit_forward_flops,
}


def train_flops_per_item(cfg: Dict) -> float:
    """Forward plus backward operations for one item of ``cfg``."""
    name = cfg["flops"]
    if name in _FORWARD:
        return 3.0 * _FORWARD[name](cfg)
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        f"flops_{name}.py")
    if not os.path.exists(path):
        raise KeyError(f"no FLOPs function {name!r}: not in flops.py and "
                       f"no {os.path.basename(path)}")
    spec = importlib.util.spec_from_file_location(f"flops_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return float(mod.train_flops_per_item(cfg))
