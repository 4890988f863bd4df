"""benchmark/flops.py against the program's obs/flops.image_step_cost."""

import json
import os

import pytest

import flops
from conftest import BENCH
from pytorch_distributed_tpu.obs.flops import image_step_cost


@pytest.mark.parametrize("name", ["resnet50", "vit-b16"])
def test_forward_plus_backward_equals_the_programs_count(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        cfg = json.load(f)
    theirs = image_step_cost(cfg["arch"], 1, cfg["image_size"],
                             cfg["num_classes"])
    want = theirs.breakdown["forward"] + theirs.breakdown["backward"]
    assert flops.train_flops_per_item(cfg) == pytest.approx(want, rel=1e-12)
    # the optimizer's operations, which this count leaves out, are noise
    assert theirs.model_flops / want < 1.01


def test_unknown_function_is_an_error():
    with pytest.raises(KeyError):
        flops.train_flops_per_item({"flops": "no-such-model"})
