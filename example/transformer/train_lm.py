#!/usr/bin/env python
"""Distributed transformer LM training — the modern flagship the 2019
reference lacks (its sequence story is bucketed RNNs; SURVEY §5).

One mesh, every parallelism axis as a sharding choice:

    # single chip / virtual CPU devices
    python example/transformer/train_lm.py --steps 5

    # 8 virtual devices: 2-way data x 2-way tensor x 2-way sequence
    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python example/transformer/train_lm.py --dp 2 --tp 2 --sp 2 \
        --attn ring --steps 5

    # GPipe pipeline: 2 stages x 2-way data
    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python example/transformer/train_lm.py --pp 2 --dp 2 --sp 2 \
        --microbatch 2 --steps 5
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import numpy as np  # noqa: E402


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--dp", type=int, default=1)
    p.add_argument("--tp", type=int, default=1)
    p.add_argument("--sp", type=int, default=1)
    p.add_argument("--pp", type=int, default=1)
    p.add_argument("--ep", type=int, default=1)
    p.add_argument("--microbatch", type=int, default=1)
    p.add_argument("--attn", default="local",
                   choices=["local", "ring", "ulysses", "blockwise"])
    p.add_argument("--dim", type=int, default=64)
    p.add_argument("--layers", type=int, default=2)
    p.add_argument("--heads", type=int, default=4)
    p.add_argument("--vocab", type=int, default=256)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--seq", type=int, default=64)
    p.add_argument("--steps", type=int, default=10)
    p.add_argument("--experts", type=int, default=0)
    args = p.parse_args()

    import jax
    import jax.numpy as jnp
    import jax.random as jr
    import mxnet_tpu  # noqa: F401
    from mxnet_tpu.parallel import create_mesh
    from mxnet_tpu.parallel import transformer as T
    from mxnet_tpu.runtime import use_compilation_cache

    use_compilation_cache()
    n_needed = args.dp * args.tp * args.sp * args.pp * args.ep
    devs = jax.devices()
    assert len(devs) >= n_needed, \
        "need %d devices, have %d (set XLA_FLAGS=" \
        "--xla_force_host_platform_device_count=N)" % (n_needed, len(devs))

    mesh_axes = {k: v for k, v in dict(
        dp=args.dp, tp=args.tp, sp=args.sp, pp=args.pp,
        ep=args.ep).items() if v > 1} or {"dp": 1}
    mesh = create_mesh(devices=devs[:n_needed], **mesh_axes)
    cfg = T.TransformerConfig(
        vocab_size=args.vocab, dim=args.dim, n_layers=args.layers,
        n_heads=args.heads, ffn_hidden=args.dim * 4, max_seq_len=args.seq,
        attn_mode=args.attn, pp=args.pp, n_microbatch=args.microbatch,
        num_experts=args.experts)
    init_fn, step_fn = T.make_train_step(cfg, mesh)

    rs = np.random.RandomState(0)
    toks = jnp.asarray(rs.randint(0, args.vocab, (args.batch, args.seq)),
                       jnp.int32)
    tgts = jnp.roll(toks, -1, axis=1)
    with mesh.mesh:
        state = init_fn(jr.PRNGKey(0))
        for step in range(args.steps):
            state, loss = step_fn(state, toks, tgts)
            print("step %d loss %.4f" % (step, float(loss)))
    print("mesh:", mesh_axes, "attn:", args.attn)


if __name__ == "__main__":
    main()
