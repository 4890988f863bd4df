#!/usr/bin/env python
"""Clean kernel microbenchmarks: all outputs reduced to scalars on-device so
the device-to-host transfer never pollutes timing.  Measures dispatch latency, MXU
matmul ceiling, and representative ResNet conv fwd/bwd shapes."""

import jax
import jax.numpy as jnp
import numpy as np
from functools import partial

from benchlib import timed_scalar as _timed_scalar  # noqa: E402

# microbenchmark sampling: more iters/warmup than benchlib's quick default
timed_scalar = partial(_timed_scalar, iters=30, warmup=5)


def main():
    # dispatch latency: trivial op
    x1 = jnp.float32(1.0)
    triv = jax.jit(lambda v: v + 1.0)
    t = timed_scalar(triv, x1, iters=50)
    print(f"dispatch latency (trivial jit): {t*1e3:.3f} ms")

    # MXU ceiling: bf16 matmul, scalar readout
    for m in (4096, 8192):
        a = jnp.ones((m, m), jnp.bfloat16)

        @jax.jit
        def mm(a):
            return (a @ a).astype(jnp.float32).sum()

        t = timed_scalar(mm, a)
        print(f"matmul {m}^2 bf16: {t*1e3:.2f} ms -> {2*m**3/t/1e12:.1f} TFLOP/s")

    # f32 matmul for contrast
    a = jnp.ones((4096, 4096), jnp.float32)

    @jax.jit
    def mmf(a):
        return (a @ a).sum()

    t = timed_scalar(mmf, a)
    print(f"matmul 4096^2 f32: {t*1e3:.2f} ms -> {2*4096**3/t/1e12:.1f} TFLOP/s")

    # chained matmuls (amortize any per-launch overhead inside one program)
    m = 4096
    a = jnp.ones((m, m), jnp.bfloat16)

    @jax.jit
    def mm8(a):
        x = a
        for _ in range(8):
            x = x @ a
        return x.astype(jnp.float32).sum()

    t = timed_scalar(mm8, a)
    print(f"8x chained matmul {m}^2 bf16: {t*1e3:.2f} ms -> "
          f"{8*2*m**3/t/1e12:.1f} TFLOP/s")

    # representative ResNet-50 convs (NHWC, bf16): (batch,h,w,cin) x (k,k,cin,cout)
    shapes = [
        (256, 56, 56, 64, 64, 3),    # stage1 3x3
        (256, 28, 28, 128, 128, 3),  # stage2 3x3
        (256, 14, 14, 256, 256, 3),  # stage3 3x3
        (256, 7, 7, 512, 512, 3),    # stage4 3x3
        (256, 56, 56, 64, 256, 1),   # 1x1 expand
    ]
    for (b, h, w, cin, cout, k) in shapes:
        x = jnp.ones((b, h, w, cin), jnp.bfloat16)
        wgt = jnp.ones((k, k, cin, cout), jnp.bfloat16)

        @jax.jit
        def conv(x, wgt):
            y = jax.lax.conv_general_dilated(
                x, wgt, (1, 1), "SAME",
                dimension_numbers=("NHWC", "HWIO", "NHWC"),
                preferred_element_type=jnp.float32,
            )
            return y.sum()

        t = timed_scalar(conv, x, wgt)
        flops = 2 * b * h * w * cin * cout * k * k
        print(f"conv fwd b{b} {h}x{w} {cin}->{cout} k{k}: {t*1e3:.2f} ms -> "
              f"{flops/t/1e12:.1f} TFLOP/s")

        @jax.jit
        def convg(x, wgt):
            def f(x, wgt):
                y = jax.lax.conv_general_dilated(
                    x, wgt, (1, 1), "SAME",
                    dimension_numbers=("NHWC", "HWIO", "NHWC"),
                )
                return y.astype(jnp.float32).sum()

            gx, gw = jax.grad(f, argnums=(0, 1))(x, wgt)
            return gx.astype(jnp.float32).sum() + gw.astype(jnp.float32).sum()

        t = timed_scalar(convg, x, wgt)
        print(f"  conv fwd+bwd: {t*1e3:.2f} ms -> {3*flops/t/1e12:.1f} TFLOP/s eq")


if __name__ == "__main__":
    main()
