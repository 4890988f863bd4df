#!/usr/bin/env python
"""Conv throughput with in-program repetition (fori_loop) so per-launch
dispatch overhead doesn't pollute kernel timing."""

import time

import jax
import jax.numpy as jnp


from benchlib import timed_scalar  # noqa: E402


REPS = 20


def main():
    shapes = [
        (256, 56, 56, 64, 64, 3, 1),
        (256, 28, 28, 128, 128, 3, 1),
        (256, 14, 14, 256, 256, 3, 1),
        (256, 7, 7, 512, 512, 3, 1),
        (256, 56, 56, 256, 64, 1, 1),   # 1x1 reduce
        (256, 14, 14, 1024, 256, 1, 1),
    ]
    for (b, h, w, cin, cout, k, stride) in shapes:
        x0 = jnp.ones((b, h, w, cin), jnp.bfloat16)
        wgt = jnp.ones((k, k, cin, cout), jnp.bfloat16) * 0.01
        flops = 2 * b * (h // stride) * (w // stride) * cin * cout * k * k

        @jax.jit
        def fwd_loop(x0, wgt):
            def body(i, acc):
                y = jax.lax.conv_general_dilated(
                    x0, wgt, (stride, stride), "SAME",
                    dimension_numbers=("NHWC", "HWIO", "NHWC"),
                )
                return acc + y.astype(jnp.float32).mean() * (i + 1)

            return jax.lax.fori_loop(0, REPS, body, jnp.float32(0))

        t = timed_scalar(fwd_loop, x0, wgt) / REPS
        print(f"conv fwd b{b} {h}x{w} {cin}->{cout} k{k}: {t*1e3:.3f} ms -> "
              f"{flops/t/1e12:.1f} TFLOP/s")

        @jax.jit
        def bwd_loop(x0, wgt):
            def f(xw):
                x, wg = xw
                y = jax.lax.conv_general_dilated(
                    x, wg, (stride, stride), "SAME",
                    dimension_numbers=("NHWC", "HWIO", "NHWC"),
                )
                return y.astype(jnp.float32).mean()

            def body(i, acc):
                gx, gw = jax.grad(f)((x0, wgt))
                return (acc + gx.astype(jnp.float32).mean() * (i + 1)
                        + gw.astype(jnp.float32).mean())

            return jax.lax.fori_loop(0, REPS, body, jnp.float32(0))

        t = timed_scalar(bwd_loop, x0, wgt) / REPS
        print(f"  fwd+bwd: {t*1e3:.3f} ms -> {3*flops/t/1e12:.1f} TFLOP/s eq")


if __name__ == "__main__":
    main()
