"""End-to-end slice: the minimum path of SURVEY.md §7.3 — synthetic data →
resnet18 → jitted DP train step over 8 simulated devices → meters → validate →
checkpoint → resume."""

import jax
import numpy as np
import pytest

from pytorch_distributed_tpu.train.config import Config, parse_config
from pytorch_distributed_tpu.train.trainer import Trainer


def _cfg(tmp_path, **kw):
    base = dict(
        arch="resnet18",
        batch_size=16,
        epochs=1,
        lr=0.1,
        print_freq=2,
        synthetic=True,
        synthetic_length=48,
        image_size=32,
        num_classes=8,
        seed=0,
        checkpoint_dir=str(tmp_path),
        workers=2,
    )
    base.update(kw)
    return Config(**base)


def test_fit_one_epoch_trains_and_checkpoints(tmp_path, capsys):
    # 2 classes ⇒ val acc ~50% ⇒ first epoch beats best_acc1=0, so the
    # reference's strict `acc1 > best` (distributed.py:215) triggers is_best.
    t = Trainer(_cfg(tmp_path, num_classes=2))
    p0 = jax.tree_util.tree_leaves(t.state.params)[0].copy()
    best = t.fit()
    out = capsys.readouterr().out
    assert "Epoch: [0]" in out
    assert "* Acc@1" in out
    assert (tmp_path / "checkpoint.msgpack").exists()
    assert (tmp_path / "model_best.msgpack").exists()  # first epoch is best
    p1 = jax.tree_util.tree_leaves(t.state.params)[0]
    assert not np.array_equal(np.asarray(p0), np.asarray(p1)), "params must move"
    assert 0.0 <= best <= 100.0


def test_resume_continues_from_checkpoint(tmp_path, capsys):
    t = Trainer(_cfg(tmp_path))
    t.fit()
    step_after = int(t.state.step)

    cfg2 = _cfg(tmp_path, resume=str(tmp_path / "checkpoint.msgpack"), epochs=2)
    t2 = Trainer(cfg2)
    assert cfg2.start_epoch == 1  # epoch 0 was saved ⇒ resume at 1
    assert int(t2.state.step) == step_after
    out = capsys.readouterr().out
    assert "resumed resnet18" in out
    t2.fit()
    assert int(t2.state.step) == 2 * step_after


def test_evaluate_flag_runs_validation_only(tmp_path, capsys):
    t = Trainer(_cfg(tmp_path, evaluate=True))
    s0 = int(t.state.step)
    t.fit()
    out = capsys.readouterr().out
    assert "* Acc@1" in out
    assert "Epoch: [0]" not in out
    assert int(t.state.step) == s0
    assert not (tmp_path / "checkpoint.msgpack").exists()


def test_bf16_precision_trains(tmp_path):
    t = Trainer(_cfg(tmp_path, precision="bf16"))
    t.train_loader.set_epoch(0)
    batch = next(iter(t.feeder(iter(t.train_loader))))
    import jax.numpy as jnp

    state, metrics = t.train_step(t.state, batch, jnp.float32(0.1))
    assert np.isfinite(float(metrics["loss"]))
    # master params stay f32 under the bf16 compute policy
    assert jax.tree_util.tree_leaves(state.params)[0].dtype == jnp.float32


def test_stem_flag_rejected_outside_resnet_family(tmp_path):
    """--stem names a ResNet variant; any other arch must refuse it loudly
    instead of building its usual stem."""
    with pytest.raises(ValueError, match="--stem only applies to the ResNet"):
        Trainer(_cfg(tmp_path, stem="space_to_depth", arch="alexnet"))


def test_parse_config_reference_flag_surface():
    cfg = parse_config(
        ["-a", "resnet50", "-b", "256", "--lr", "0.4", "--wd", "1e-4",
         "-p", "5", "-e", "--seed", "42", "-j", "8"]
    )
    assert cfg.arch == "resnet50"
    assert cfg.batch_size == 256
    assert cfg.lr == 0.4
    assert cfg.print_freq == 5
    assert cfg.evaluate is True
    assert cfg.seed == 42
    assert cfg.workers == 8
