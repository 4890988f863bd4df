"""The resident runner's step and ``Trainer.train_step`` are one program.

Both are lowered at a tiny size on the CPU; the texts must be equal, so the
resident cells time the step the recipe runs and not a hand-assembled one."""

import jax
import jax.numpy as jnp

import harness
from conftest import BENCH


def test_same_lowered_program(tmp_path):
    resident = harness.load_module(BENCH + "/runners/resident_step.py")
    fed = harness.load_module(BENCH + "/runners/fed_epoch.py")
    cfg = harness.load_json(BENCH + "/configs/resnet50.json")
    cfg["image_size"] = 32
    batch = 8
    trainer = fed.build_trainer([
        "--synthetic", "-a", cfg["arch"], "-b", str(batch),
        "--image-size", "32", "--seed", "7", "--synthetic-length", "16",
        "--epoch-csv", str(tmp_path / "e.csv"),
        "--checkpoint-dir", str(tmp_path / "runs")])
    mesh = trainer.mesh
    model = harness.build_model(cfg)
    state = harness.make_state(model, cfg, mesh, 7)
    step = resident.make_step(model, mesh, cfg, 7, state.params)
    data = resident.make_batch(cfg, mesh, batch, 7)
    lr = jnp.float32(0.1)
    # same tree, shapes and placement as the Trainer's own state
    assert (jax.tree_util.tree_structure(state)
            == jax.tree_util.tree_structure(trainer.state))
    mine = step.lower(state, data, lr).as_text()
    theirs = trainer.train_step.lower(trainer.state, data, lr).as_text()
    assert mine == theirs
