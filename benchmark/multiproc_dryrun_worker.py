"""One process of the 2-process x 4-device multichip dryrun
(VERDICT r3 item 2: validate the MULTI-PROCESS sharded path, not just
the single-process 8-device mesh).

Each process owns 4 virtual CPU devices; jax.distributed stitches them
into one 8-device global mesh; the full GSPMD transformer train step
(dp=4 x sp=2, ring attention, chunked CE) jits over it and runs one
step. Launched by __graft_entry__.dryrun_multichip (phase 6) or by
tools/launch.py -n 2.
"""
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax  # noqa: E402

from tools.launch import force_virtual_cpu_devices  # noqa: E402

# must precede jax.distributed.initialize
force_virtual_cpu_devices(4)

jax.distributed.initialize(os.environ["MXTPU_COORDINATOR"],
                           int(os.environ["MXTPU_NUM_PROCS"]),
                           int(os.environ["MXTPU_PROC_ID"]))

import jax.numpy as jnp  # noqa: E402
import jax.random as jr  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from mxnet_tpu.parallel import create_mesh  # noqa: E402
from mxnet_tpu.parallel import transformer as T  # noqa: E402


def main():
    rank = int(os.environ["MXTPU_PROC_ID"])
    devs = jax.devices()
    assert len(devs) == 8, \
        "expected 8 global devices (2 procs x 4), got %d" % len(devs)
    assert len(jax.local_devices()) == 4, \
        "expected 4 local devices, got %d" % len(jax.local_devices())

    mesh = create_mesh(devices=devs, dp=4, sp=2)
    cfg = T.TransformerConfig(vocab_size=64, dim=16, n_layers=2,
                              n_heads=4, ffn_hidden=32, attn_mode="ring",
                              loss_chunks=4)
    init_fn, step_fn = T.make_train_step(cfg, mesh)
    with mesh.mesh:
        state = init_fn(jr.PRNGKey(0))
        batch_sh = NamedSharding(mesh.mesh, P("dp", "sp"))
        gen = jax.jit(
            lambda k: jr.randint(k, (8, 16), 0, cfg.vocab_size,
                                 dtype=jnp.int32),
            out_shardings=batch_sh)
        toks = gen(jr.PRNGKey(1))
        tgts = gen(jr.PRNGKey(2))
        state, loss = step_fn(state, toks, tgts)
        val = float(loss)  # replicated scalar: addressable everywhere
    assert val == val and val > 0, val
    print("multiproc dryrun rank %d: dp=4 sp=2 over 2 procs ok, "
          "loss=%.4f" % (rank, val))


if __name__ == "__main__":
    main()
