#!/usr/bin/env python3
"""How ``fixtures/tiny_dp4.xplane.pb.gz`` was recorded (PR 22, on a v5e
host with four chips):

    chiprun --chips 4 -- python3 benchmark/tests/record_fixture.py

A small data-parallel step (a two-layer MLP, GSPMD, so XLA puts in the
gradient all-reduce), six steps inside the harness's own spans, with a
sleep under ``bench:data_wait`` before each step so that the device idles
at a known place.  Small on purpose: a capture of ResNet-50 is 10 MB.
"""

import gzip
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))


def main() -> None:
    from pytorch_distributed_tpu.utils.chip import require_tpu

    found = require_tpu()
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    import harness

    mesh = Mesh(jax.devices(), ("data",))
    rows, repl = NamedSharding(mesh, P("data")), NamedSharding(mesh, P())

    def tiny_step(w, x):
        def loss(w):
            h = jnp.tanh(x @ w["a"])
            return jnp.mean((h @ w["b"]) ** 2)
        g = jax.grad(loss)(w)
        return jax.tree_util.tree_map(lambda p, q: p - 0.01 * q, w, g)

    step = jax.jit(tiny_step, in_shardings=(repl, rows), out_shardings=repl)
    key = jax.random.PRNGKey(0)
    w = jax.device_put({"a": jax.random.normal(key, (1024, 2048)) * 0.03,
                        "b": jax.random.normal(key, (2048, 1024)) * 0.03},
                       repl)
    x = jax.device_put(jax.random.normal(key, (2048 * found["count"], 1024)),
                       rows)
    w = step(w, x)
    jax.block_until_ready(w)

    spans = harness.Spans()
    cell = harness.Cell(name="fixture", config={}, traffic={},
                        chips=found["count"], seed=0, seconds=0.0, trace=True,
                        devices=jax.devices(), spans=spans,
                        compiles=harness.CompileLog())
    tracer = harness.TraceWindow(cell)
    tracer.start()
    for _ in range(6):
        with spans("data_wait"):
            time.sleep(0.002)
        with spans("dispatch"):
            w = step(w, x)
        with spans("block"):
            jax.block_until_ready(w)
    tracer.stop()
    out = os.path.join(os.path.dirname(os.path.dirname(HERE)), "chiprun_out",
                       "fixture")
    os.makedirs(out, exist_ok=True)
    with open(tracer.file, "rb") as src, gzip.open(
            os.path.join(out, "tiny_dp4.xplane.pb.gz"), "wb") as dst:
        shutil.copyfileobj(src, dst)
    print("recorded", os.path.getsize(tracer.file), "bytes,",
          os.path.getsize(os.path.join(out, "tiny_dp4.xplane.pb.gz")),
          "gzipped; device", found)


if __name__ == "__main__":
    main()
