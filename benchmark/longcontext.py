"""Long-context single-chip training sweep.

SURVEY makes long-context first-class; this harness measures how far
ONE chip's HBM stretches with the Pallas flash kernels (fwd + flash-2
backward), chunked CE, and full per-layer remat — the single-chip
anchor of the sequence-scaling story (ring/Ulysses over the mesh extend
it across chips; tests/test_parallel.py proves those paths compile and
match dense).

Emits one JSON line per configuration:
  {"dim": D, "layers": L, "seq": S, "params_m": M,
   "tokens_per_sec": T, "model_tflops_per_sec": F, "final_loss": ...}

Measured on one v5e (16 GB), bf16 (recorded in LONGCONTEXT_r04.json):
  668M  at seq 16,384: 15,745 tok/s (63.1 TF/s)
  668M  at seq 32,768: 11,082 tok/s (44.4 TF/s)
  668M  at seq 65,536:  6,885 tok/s (27.6 TF/s)
  1.42B at seq 32,768:  5,679 tok/s (48.5 TF/s)
The TF/s decline with S is the attention share growing (score FLOPs
scale with S^2 while the flash kernel runs below matmul rate — see
docs/ROADMAP.md transformer MFU study); tokens/s stays usable to 64k.

Usage: python benchmark/longcontext.py [--configs dim,layers,seq ...]
"""
import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

DEFAULT_CONFIGS = [(2048, 8, 16384), (2048, 8, 32768), (2048, 8, 65536),
                   (2560, 12, 32768)]


def run(dim, layers, seq, batch=1, iters=3):
    import jax
    import jax.numpy as jnp
    import jax.random as jr
    import mxnet_tpu  # noqa: F401
    from mxnet_tpu.parallel import create_mesh
    from mxnet_tpu.parallel import transformer as T

    cfg = T.TransformerConfig(
        vocab_size=32000, dim=dim, n_layers=layers,
        n_heads=max(1, dim // 128),
        ffn_hidden=dim * 4, max_seq_len=seq, dtype="bfloat16",
        attn_mode="local",
        # chunked CE: [B,S,32k] logits never materialize — mandatory at
        # these sequence lengths
        loss_chunks=max(8, seq // 2048))
    mesh = create_mesh(devices=jax.devices()[:1], dp=1)
    init_fn, step_fn = T.make_train_step(cfg, mesh)
    rs = np.random.RandomState(0)
    with mesh.mesh:
        state = init_fn(jr.PRNGKey(0))
        toks = jnp.asarray(rs.randint(0, cfg.vocab_size, (batch, seq)),
                           jnp.int32)
        # independent targets — same convention as bench.py's
        # transformer bench (targets == inputs would let causal
        # attention copy-predict and collapse the loss)
        tgts = jnp.asarray(rs.randint(0, cfg.vocab_size, (batch, seq)),
                           jnp.int32)
        state, loss = step_fn(state, toks, tgts)
        float(loss)  # compile + warm
        t0 = time.perf_counter()
        for _ in range(iters):
            state, loss = step_fn(state, toks, tgts)
        loss = float(loss)
        dt = (time.perf_counter() - t0) / iters
    n_params = sum(int(np.prod(p.shape))
                   for p in jax.tree_util.tree_leaves(state[0]))
    return {
        "platform": jax.devices()[0].platform,
        "device_kind": jax.devices()[0].device_kind,
        "dim": dim, "layers": layers, "seq": seq, "batch": batch,
        "params_m": round(n_params / 1e6, 1),
        "tokens_per_sec": round(batch * seq / dt, 1),
        "model_tflops_per_sec": round(
            6 * n_params * batch * seq / dt / 1e12, 1),
        "final_loss": round(loss, 4),
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--configs", nargs="*",
                    help="dim,layers,seq triples (default: the sweep)")
    ap.add_argument("--iters", type=int, default=3)
    args = ap.parse_args()
    import jax
    from mxnet_tpu.runtime import use_compilation_cache
    if jax.default_backend() != "tpu":
        sys.exit("longcontext.py measures one TPU chip; JAX's default "
                 "backend here is %r" % jax.default_backend())
    use_compilation_cache()
    failed = []
    for raw in (args.configs or
                ["%d,%d,%d" % c for c in DEFAULT_CONFIGS]):
        try:
            dim, layers, seq = (int(x) for x in raw.split(","))
            print(json.dumps(run(dim, layers, seq, iters=args.iters)),
                  flush=True)
        except Exception as e:  # noqa: BLE001 — an OOM or malformed
            # config must not kill the remaining sweep; it fails the
            # run once the sweep is through
            print(json.dumps({"config": raw, "error": str(e)[:200]}),
                  flush=True)
            failed.append(raw)
    if failed:
        sys.exit("longcontext: %d of the configs failed: %s"
                 % (len(failed), " ".join(failed)))


if __name__ == "__main__":
    main()
