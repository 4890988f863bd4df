"""Test configuration: simulate an 8-device TPU mesh on CPU.

Multi-device DP semantics (gradient psum, sharded batches, set_epoch
reshuffle) are testable with no TPU and no cluster via XLA's host-platform
device-count override — the test strategy SURVEY.md §4 prescribes for the
framework (the reference itself has no tests).

The platform is pinned to the CPU here, before the first device use, so the
suite lands on the simulated mesh whatever ``JAX_PLATFORMS`` says outside.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_threefry_partitionable", True)

# The suite is compile-dominated; the persistent compilation cache
# (utils/compile_cache.py: $JAX_COMPILATION_CACHE_DIR, else the fixed
# <checkout>/.jax_cache) lets a second session skip what the first built.
from pytorch_distributed_tpu.utils.compile_cache import (  # noqa: E402
    enable_compile_cache,
)

enable_compile_cache()

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def lm_world32():
    """Session-shared tiny-LM training world: the full-device data mesh,
    the vocab-32 1-layer TransformerLM, and its synthetic dataset.

    Several suites fit this identical configuration (test_zero's parity
    and kill-resume drills, and anything else on the vocab-32 smoke
    model); sharing the objects keeps model.init traced once and — more
    importantly — lets fitted-trainer fixtures below amortize whole
    train-step compiles across tests on the 1-core CI host."""
    from pytorch_distributed_tpu.models.transformer import TransformerLM
    from pytorch_distributed_tpu.parallel import MeshSpec, build_mesh
    from pytorch_distributed_tpu.train.lm import SyntheticTokenDataset

    mesh = build_mesh(MeshSpec(("data",), (jax.device_count(),)))
    model = TransformerLM(vocab_size=32, d_model=32, n_heads=2, n_layers=1)
    ds = SyntheticTokenDataset(64, 16, 32)
    return mesh, model, ds


@pytest.fixture(scope="session")
def lm_wus_ref_fit(lm_world32):
    """The uninterrupted ``--zero wus`` reference run (8 steps, lr 0.05,
    batch 8): one compile + one fit for every test that needs the wus
    baseline (replicated-parity fences, kill-and-resume parity).  Tests
    must treat the returned trainer as read-only."""
    from pytorch_distributed_tpu.train.lm import LMTrainer

    mesh, model, ds = lm_world32
    with mesh:
        t = LMTrainer(model, mesh, ds, batch_size=8, lr=0.05,
                      eval_dataset=None, zero="wus")
        loss = t.fit(8, print_freq=4)
    return t, loss


@pytest.fixture(scope="session")
def get_lowering(tmp_path_factory):
    """Session-shared compiled recipe lowerings.

    Hands back a thin wrapper over ``analysis.core.get_lowering`` — the
    memoized lower+compile sweep over the shardlint RECIPES — so
    everything that needs a recipe's HLO (test_shardlint's detector
    fences, test_comms' and test_memory's ledger parity checks) pays one
    compile per step for the whole session instead of one per test.
    Threshold variations and ledger extraction are pure functions of the
    cached Lowering record.

    The sweep and its on-disk artifact layout are owned by the first-class
    service (``analysis.lowering.LoweringService``): on first build per
    step the service drops ``<name>.hlo`` / ``<name>.json`` (HLO text +
    measured peak/mesh/arg-classes) under ``wrapper.cache_dir`` so
    subprocess consumers (the obs_memory CLI test) and pure-text
    re-analyses read files instead of recompiling.  ``wrapper.
    compile_count()`` exposes the process-wide AOT compile counter for
    the zero-extra-compiles asserts, and ``wrapper.service`` the
    underlying LoweringService (``.load(name)`` for the no-jax disk
    view)."""
    from pytorch_distributed_tpu.analysis import lowering

    cache_dir = tmp_path_factory.mktemp("hlo_cache")
    svc = lowering.service(str(cache_dir))

    def wrapper(name: str):
        return svc.get(name)

    wrapper.cache_dir = cache_dir
    wrapper.compile_count = lowering.compile_count
    wrapper.compile_budget = lowering.compile_budget
    wrapper.service = svc
    return wrapper
