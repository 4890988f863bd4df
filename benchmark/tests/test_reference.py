"""The plain references against the program, in float32 at a small size,
with every weight shaken so that no path is switched off (a batch-norm
scale that starts at 0, the zero class token).  Equal to rounding: the
reference reads the program's parameter tree and nothing else of it."""

import jax
import pytest

import harness
from conftest import BENCH
from pytorch_distributed_tpu.parallel import data_parallel_mesh


@pytest.mark.parametrize("name,size", [("resnet50", 64), ("vit-b16", 32)])
def test_float32_program_equals_reference(name, size):
    cfg = harness.load_json(f"{BENCH}/configs/{name}.json")
    cfg.update(image_size=size, precision="fp32")
    model = harness.build_model(cfg)
    mesh = data_parallel_mesh(jax.devices()[:1])
    state = harness.make_state(model, cfg, mesh, 5)
    leaves, tree = jax.tree_util.tree_flatten(state.params)
    keys = jax.random.split(jax.random.PRNGKey(9), len(leaves))
    params = jax.tree_util.tree_unflatten(tree, [
        leaf + 0.05 * jax.random.normal(key, leaf.shape)
        for leaf, key in zip(leaves, keys)])
    out = harness.reference_check(model, cfg, params, state.batch_stats, 5)
    assert out["logits_rel"] < 1e-3 and out["loss_abs"] < 1e-3, out


def test_bf16_policy_is_inside_and_a_coarser_one_outside_the_tolerance():
    """ViT at 32x32 under the stated bf16 policy passes; the same weights
    rounded to 8-bit floats, a precision below the stated one, fail."""
    import jax.numpy as jnp

    cfg = harness.load_json(f"{BENCH}/configs/vit-b16.json")
    cfg.update(image_size=32)
    model = harness.build_model(cfg)
    mesh = data_parallel_mesh(jax.devices()[:1])
    state = harness.make_state(model, cfg, mesh, 5)
    good = harness.reference_check(model, cfg, state.params,
                                   state.batch_stats, 5)
    assert good["ok"], good

    class Coarse:
        """The program with 8-bit weights."""

        def apply(self, variables, *args, **kwargs):
            rounded = jax.tree_util.tree_map(
                lambda x: x.astype(jnp.float8_e4m3fn).astype(x.dtype),
                variables["params"])
            return model.apply({**variables, "params": rounded}, *args,
                               **kwargs)

    bad = harness.reference_check(Coarse(), cfg, state.params,
                                  state.batch_stats, 5)
    assert not bad["ok"], bad
