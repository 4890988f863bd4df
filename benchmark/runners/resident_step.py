"""Runner `resident_step`: the compiled train step on one resident batch.

The step is built with the arguments ``Trainer._build_for_mesh`` passes
for the recipe's defaults (``benchmark/tests`` holds the two to the same
lowered program), so the cell times the step a user of the recipe runs.
The batch is made on the device from the seed and reused; no loader, no
host-to-device copy.  Timing is ``utils/benchstep.measure_train_step``'s,
made time-bounded: chunks of about a second, a ``block_until_ready`` at
each chunk's end, items and seconds from the drain to the last block.
"""

from __future__ import annotations

import math
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import harness  # noqa: E402


def make_step(model, mesh, cfg, seed: int, params):
    """``make_train_step`` as ``Trainer._build_for_mesh`` calls it with the
    defaults of ``recipes/tpu_native``: GSPMD, no gradient compression, no
    ZeRO, no accumulation, no norms, no guard."""
    from pytorch_distributed_tpu.train.steps import make_train_step

    opt = cfg["optimizer"]
    return make_train_step(
        model, mesh, momentum=opt["momentum"],
        weight_decay=opt["weight_decay"], data_axis="data", wire_dtype=None,
        grad_compress="none", explicit_collectives=False, seed=seed, tx=None,
        accum_steps=1, log_norms=False, guard_nonfinite=False, zero="none",
        params=params, overlap="none", bucket_mb=4.0)


def make_batch(cfg, mesh, batch: int, seed: int):
    """One global batch on the devices, rows sharded over ``data``."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    size, chans = cfg["image_size"], cfg["num_channels"]
    dtype = {"float32": jnp.float32}[cfg["input_dtype"]]

    def draw(seed):
        k_img, k_lab = jax.random.split(jax.random.PRNGKey(seed))
        return {"images": jax.random.normal(
                    k_img, (batch, size, size, chans), dtype),
                "labels": jax.random.randint(
                    k_lab, (batch,), 0, cfg["num_classes"], jnp.int32),
                "weights": jnp.ones((batch,), jnp.float32)}

    rows = NamedSharding(mesh, P("data"))
    return jax.jit(draw, out_shardings={
        "images": rows, "labels": rows, "weights": rows})(jnp.uint32(seed))


def run(cell: harness.Cell) -> harness.Run:
    import jax
    import jax.numpy as jnp

    from pytorch_distributed_tpu.parallel import data_parallel_mesh

    cfg, spans = cell.config, cell.spans
    batch_size = cell.traffic["batch_per_chip"] * cell.chips
    mesh = data_parallel_mesh(cell.devices)
    model = harness.build_model(cfg)
    state = harness.make_state(model, cfg, mesh, cell.seed)
    batch = make_batch(cfg, mesh, batch_size, cell.seed)
    checks = {"batch_on_every_device":
              harness.placed_everywhere(batch, cell.devices)}
    ref = harness.reference_check(model, cfg, state.params,
                                  state.batch_stats, cell.seed)
    harness.say("reference", **ref)
    checks["agrees_with_reference"] = ref["ok"]

    step = make_step(model, mesh, cfg, cell.seed, state.params)
    lr = jnp.float32(cfg["optimizer"]["lr"])
    # warm-up: the first call compiles or loads; the rest size the chunks
    state, metrics = step(state, batch, lr)
    jax.block_until_ready((state, metrics))
    warm = cell.traffic["warmup_steps"]
    t = time.perf_counter()
    for _ in range(warm):
        state, metrics = step(state, batch, lr)
    jax.block_until_ready((state, metrics))  # the drain before the window
    step_s = (time.perf_counter() - t) / warm
    per_chunk = max(1, round(1.0 / step_s))
    harness.say("warm", step_ms=step_s * 1e3, steps_per_chunk=per_chunk)

    losses, attempted = [], 0

    def chunk():
        nonlocal state, metrics, attempted
        with spans("dispatch"):
            for _ in range(per_chunk):
                attempted += 1
                state, metrics = step(state, batch, lr)
                losses.append(metrics["loss"])
        with spans("block"):
            jax.block_until_ready((state, metrics))

    chunk_s, failed = [], 0
    t0 = t1 = time.perf_counter()
    while t1 - t0 < cell.seconds:
        try:
            chunk()
        except Exception as e:  # a step that raised: counted, window ends
            failed += 1
            harness.say("step_raised", error=repr(e)[:300])
            break
        now = time.perf_counter()
        chunk_s.append(now - t1)
        t1 = now
    in_window = attempted
    compiler_bytes = None
    tracer = harness.TraceWindow(cell) if cell.trace else None
    if tracer:  # the same loop, after the window, under the profiler
        tracer.start()
        while tracer.open():
            chunk()
        tracer.stop()
        # the compiler's own count of the bytes a step moves, for the
        # roofline share.  After everything timed: it loads the program a
        # second time, and a traced run that did so before its window
        # stalled 4 s a few steps in (my chip run, PR 22)
        compiled = step.lower(state, batch, lr).compile()
        cost = compiled.cost_analysis()
        compiler_bytes = float(cost["bytes accessed"])
        harness.say("compiler", bytes_accessed=compiler_bytes,
                    flops=cost.get("flops"),
                    memory=str(compiled.memory_analysis()))

    values = [float(x) for x in losses[:in_window]]
    failed += sum(1 for v in values if not math.isfinite(v))
    done = len(chunk_s) * per_chunk
    tenth = max(1, len(values) // 10)
    checks["losses_finite"] = failed == 0
    checks["loss_falls_on_reused_batch"] = bool(
        values and sum(values[-tenth:]) / tenth < sum(values[:tenth]) / tenth)
    checks["no_compile_in_window"] = cell.compiles.inside(t0, t1) == 0
    if cell.chips > 1:
        checks["replicas_identical"] = harness.replicas_identical(
            state.params)
    harness.say("losses", first=values[:3], last=values[-3:], n=len(values))
    harness.say("chunks", seconds=chunk_s, steps_per_chunk=per_chunk)
    return harness.Run(
        items=done * batch_size, window_start=t0, window_end=t1,
        attempted=in_window, failed=failed, checks=checks,
        end_to_end={"throughput_per_chip":
                    done * batch_size / (t1 - t0) / cell.chips},
        trace_file=tracer.file if tracer else None,
        compiler_bytes=compiler_bytes,
        notes={"batch": batch_size, "step_program": "jit_global_step"})
