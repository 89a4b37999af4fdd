"""Does XLA:TPU fuse elementwise producers into dot/conv operand loads?

Decides the round-4 ResNet HBM strategy (VERDICT r3 item 1): if
`relu(x*s+b) @ W` compiles to the same bytes-accessed as `x @ W`, the
normalize+ReLU can ride the consumer's operand load and interior
activations never need a materialized normalized copy.  Compares
bytes-accessed and wall time for materialize-vs-inline variants of the
1x1-conv (as dot) and 3x3-conv cases at ResNet bottleneck shapes.
"""
import time

import jax
import jax.numpy as jnp
import numpy as np


def measure(name, fn, *args, iters=20):
    c = jax.jit(fn).lower(*args).compile()
    ca = c.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0]
    # Timing rides a fori_loop INSIDE one jit with a scalar data
    # dependency chained into the first operand — per-call dispatch
    # otherwise pipelines and lies.
    # The chain adds one elementwise pass over args[0] per iter, constant
    # across variants; `bytes` above is the compiler-exact signal.

    @jax.jit
    def loop(x0, *rest):
        def body(_, x):
            y = fn(x, *rest)
            y0 = y[0] if isinstance(y, tuple) else y
            eps = (y0.ravel()[0] * 0).astype(x0.dtype)
            return x * (1 + eps)
        return jax.lax.fori_loop(0, iters, body, x0)

    jax.block_until_ready(loop(*args))  # compile + warm
    t0 = time.perf_counter()
    jax.block_until_ready(loop(*args))
    dt = (time.perf_counter() - t0) / iters
    print("%-34s bytes=%8.1f MB  flops=%6.2f G  t=%7.3f ms  eff_GBps=%.0f"
          % (name, ca.get("bytes accessed", 0) / 1e6,
             ca.get("flops", 0) / 1e9, dt * 1e3,
             ca.get("bytes accessed", 0) / dt / 1e9))
    return ca.get("bytes accessed", 0), dt


def main():
    rs = np.random.RandomState(0)
    B, H, W_, C, K = 128, 56, 56, 256, 64
    x = jnp.asarray(rs.rand(B * H * W_, C), jnp.bfloat16)
    w = jnp.asarray(rs.rand(C, K), jnp.bfloat16)
    s = jnp.asarray(rs.rand(C), jnp.bfloat16)
    b = jnp.asarray(rs.rand(C), jnp.bfloat16)

    print("== 1x1 conv as dot, [%d, %d] @ [%d, %d] ==" % (B * H * W_, C, C, K))
    measure("dot(x, w)", lambda x, w: x @ w, x, w)
    measure("dot(relu(x*s+b), w)",
            lambda x, w, s, b: jnp.maximum(x * s + b, 0) @ w, x, w, s, b)

    def two_step(x, w, s, b):
        y = jnp.maximum(x * s + b, 0)
        y = jax.lax.optimization_barrier(y)  # force materialization
        return y @ w
    measure("barrier(relu(x*s+b)) @ w", two_step, x, w, s, b)

    print("== 3x3 conv NHWC, [%d,%d,%d,%d] -> %d ==" % (B, H, W_, C, K))
    xc = jnp.asarray(rs.rand(B, H, W_, C), jnp.bfloat16)
    wc = jnp.asarray(rs.rand(3, 3, C, K), jnp.bfloat16)
    dn = jax.lax.conv_dimension_numbers(xc.shape, wc.shape,
                                        ("NHWC", "HWIO", "NHWC"))

    def conv(x, w):
        return jax.lax.conv_general_dilated(x, w, (1, 1), "SAME",
                                            dimension_numbers=dn)
    measure("conv(x, w)", conv, xc, wc)
    measure("conv(relu(x*s+b), w)",
            lambda x, w, s, b: conv(jnp.maximum(x * s + b, 0), w),
            xc, wc, s, b)

    def conv2(x, w, s, b):
        y = jnp.maximum(x * s + b, 0)
        y = jax.lax.optimization_barrier(y)
        return conv(y, w)
    measure("conv(barrier(relu(x*s+b)), w)", conv2, xc, wc, s, b)

    # epilogue side: can a reduction (BN stats of the OUTPUT) fuse into
    # the conv/dot's result write?
    print("== epilogue stat fusion ==")
    def dot_stats(x, w):
        y = x @ w
        yf = y.astype(jnp.float32)
        return y, jnp.mean(yf, 0), jnp.mean(yf * yf, 0)
    measure("dot + out stats", dot_stats, x, w)

    def conv_stats(x, w):
        y = conv(x, w)
        yf = y.astype(jnp.float32)
        return y, jnp.mean(yf, (0, 1, 2)), jnp.mean(yf * yf, (0, 1, 2))
    measure("conv + out stats", conv_stats, xc, wc)


if __name__ == "__main__":
    main()
