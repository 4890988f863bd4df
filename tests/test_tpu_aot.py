"""Compile for a TPU v5e 2x2 topology description — no chip needed.

The CPU mesh cannot see what the TPU compiler refuses: off-TPU the Pallas
kernels run in interpret mode, which lowers to plain HLO that GSPMD
partitions happily.  ``jax.experimental.topologies`` hands out v5e device
descriptions that ``jit(...).lower(...).compile()`` accepts, so the real
TPU compiler (Mosaic included) runs here in the sandbox.  Compile-only: it
says nothing about running, timing or memory at run time.

The suite's name sorts after the point where the tier-1 sweep hits its
time ceiling; run it directly: ``pytest tests/test_tpu_aot.py``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import topologies
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from pytorch_distributed_tpu.ops import flash_attention as fa


@pytest.fixture(scope="module")
def v5e_devices():
    return topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices


@pytest.fixture
def compiled_kernels(monkeypatch):
    """``interpret=None`` resolves from ``jax.default_backend()``, which is
    the CPU in this session whatever the compile target: pin the kernels to
    compiled mode, as they are on the chip."""
    monkeypatch.setattr(fa, "_resolve_interpret", lambda interpret: False)


def _mosaic_calls(compiled) -> int:
    return compiled.as_text().count('custom_call_target="tpu_custom_call"')


@pytest.mark.parametrize("shape,causal", [
    ((2, 1024, 4, 64), True),    # aligned: 256/1024 blocks
    ((8, 197, 12, 64), False),   # ViT-B/16: one full-dimension block
])
def test_flash_fwd_bwd_compiles_for_v5e(v5e_devices, shape, causal):
    one = NamedSharding(Mesh(np.array(v5e_devices[:1]), ("x",)), P())
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one)

    def loss(q, k, v):
        out = fa.flash_attention(q, k, v, causal, 256, 1024, False)
        return out.astype(jnp.float32).sum()

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        x, x, x).compile()
    assert _mosaic_calls(compiled) == 3  # forward, dq, dk/dv


@pytest.mark.parametrize("axes,shape,tp", [
    (("data",), (4,), False),
    (("data", "model"), (2, 2), True),
])
def test_gspmd_lm_step_with_flash_compiles_on_four_chips(
        v5e_devices, compiled_kernels, axes, shape, tp):
    """The GSPMD LM step with the Pallas kernel inside it, on a mesh of
    more than one device: the TPU compiler refuses a bare Mosaic call
    there ("Mosaic kernels cannot be automatically partitioned"), so this
    fails unless attention wraps the kernel in a shard_map."""
    from pytorch_distributed_tpu.models.transformer import TransformerLM
    from pytorch_distributed_tpu.parallel.tp import replicated_like, tp_specs
    from pytorch_distributed_tpu.train.lm import make_lm_train_step
    from pytorch_distributed_tpu.train.optim import sgd_init
    from pytorch_distributed_tpu.train.state import TrainState

    mesh = Mesh(np.array(v5e_devices).reshape(shape), axes)
    model = TransformerLM(vocab_size=512, d_model=256, n_heads=4,
                          n_layers=1, dtype=jnp.bfloat16, attn_impl="flash")
    tokens = jax.ShapeDtypeStruct((4, 256), jnp.int32)
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros(tokens.shape, tokens.dtype)))["params"]
    state = jax.eval_shape(
        lambda p: TrainState.create({"params": p}, sgd_init(p)), params)
    specs = tp_specs(params) if tp else replicated_like(params)
    step = make_lm_train_step(model, mesh, specs)
    compiled = step.lower(
        state, tokens, jax.ShapeDtypeStruct((), jnp.float32)).compile()
    assert _mosaic_calls(compiled) == 3
    assert "num_partitions=4" in compiled.as_text()
