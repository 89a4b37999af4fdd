"""kvstore / collective bandwidth measurement.

ref: /root/reference/tools/bandwidth/measure.py — times push+pull of
model-sized gradient arrays through a kvstore and reports effective
algorithm bandwidth, backing scaling-efficiency claims with numbers.

TPU-native differences: the transport under kvstore is XLA collectives
over the device mesh (psum on ICI) instead of PCIe/NCCL reduce trees,
so this tool also measures the raw mesh allreduce (`--mode mesh`) the
kvstore rides on. Emits ONE JSON line per size, like bench.py:
  {"metric": "kvstore_pushpull_bandwidth", "size_mb": N,
   "gb_per_sec": N, ...}

Usage:
  python tools/bandwidth/measure.py                    # kvstore mode
  python tools/bandwidth/measure.py --mode mesh        # raw psum
  python tools/launch.py -n 4 python tools/bandwidth/measure.py \
      --kv-store dist_sync                             # multi-process
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", ".."))


def parse_args():
    p = argparse.ArgumentParser(description="kvstore bandwidth benchmark "
                                "(ref: tools/bandwidth/measure.py)")
    p.add_argument("--kv-store", type=str, default="local",
                   help="kvstore type: local / device / dist_sync")
    p.add_argument("--mode", type=str, default="kvstore",
                   choices=["kvstore", "mesh"],
                   help="kvstore push/pull, or raw mesh psum")
    p.add_argument("--sizes-mb", type=str, default="1,4,16,64",
                   help="comma-separated tensor sizes in MB")
    p.add_argument("--num-batches", type=int, default=10)
    p.add_argument("--test-results", type=int, default=1,
                   help="verify aggregation numerics like the reference")
    return p.parse_args()


def measure_kvstore(args):
    import numpy as np
    import mxnet_tpu as mx

    if args.kv_store.startswith("dist") and "MXTPU_COORDINATOR" in \
            os.environ:
        import jax
        jax.distributed.initialize(os.environ["MXTPU_COORDINATOR"],
                                   int(os.environ["MXTPU_NUM_PROCS"]),
                                   int(os.environ["MXTPU_PROC_ID"]))
    kv = mx.kv.create(args.kv_store)
    results = []
    for size_mb in [float(s) for s in args.sizes_mb.split(",")]:
        n = int(size_mb * 1024 * 1024 / 4)
        val = mx.nd.ones((n,))
        kv.init(str(int(size_mb * 1000)), mx.nd.zeros((n,)))
        out = mx.nd.zeros((n,))
        key = str(int(size_mb * 1000))
        kv.pushpull(key, val, out=out)         # warm
        float(out.asnumpy()[0])
        t0 = time.perf_counter()
        for _ in range(args.num_batches):
            kv.pushpull(key, val, out=out)
        s = float(out.asnumpy()[0])            # sync
        dt = (time.perf_counter() - t0) / args.num_batches
        if args.test_results:
            # each pushpull round replaces the store with the cross-worker
            # sum of ones (no server optimizer attached)
            want = kv.num_workers
            assert s == want, "aggregation error: got %s want %s" % (
                s, want)
        # algorithm bandwidth: bytes through the reduce per second
        gbps = size_mb / 1024.0 / dt
        rec = {"metric": "kvstore_pushpull_bandwidth",
               "kv_store": args.kv_store, "size_mb": size_mb,
               "ms_per_round": round(dt * 1e3, 3),
               "gb_per_sec": round(gbps, 3),
               "num_workers": kv.num_workers, "rank": kv.rank}
        results.append(rec)
        if kv.rank == 0:
            print(json.dumps(rec))
    return results


def measure_mesh(args):
    """Raw allreduce over the device mesh — the ICI-collective floor the
    kvstore path cannot beat."""
    import jax
    import jax.numpy as jnp
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P
    import numpy as np

    devs = jax.devices()
    mesh = Mesh(np.array(devs), ("dp",))
    results = []
    for size_mb in [float(s) for s in args.sizes_mb.split(",")]:
        n = int(size_mb * 1024 * 1024 / 4 / len(devs)) * len(devs)
        x = jnp.ones((n,), jnp.float32)

        @jax.jit
        def allreduce(v):
            f = shard_map(lambda s: jax.lax.psum(s, "dp"), mesh=mesh,
                          in_specs=P("dp"), out_specs=P("dp"))
            return f(v)

        y = allreduce(x)
        jax.block_until_ready(y)
        t0 = time.perf_counter()
        it = args.num_batches
        for _ in range(it):
            y = allreduce(y * 0 + 1.0)
        jax.block_until_ready(y)
        dt = (time.perf_counter() - t0) / it
        rec = {"metric": "mesh_allreduce_bandwidth",
               "devices": len(devs), "size_mb": size_mb,
               "ms_per_round": round(dt * 1e3, 3),
               "gb_per_sec": round(size_mb / 1024.0 / dt, 3)}
        results.append(rec)
        print(json.dumps(rec))
    return results


if __name__ == "__main__":
    a = parse_args()
    if a.mode == "mesh":
        measure_mesh(a)
    else:
        measure_kvstore(a)
