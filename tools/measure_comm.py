"""Collective-communication bandwidth harness (reference:
tools/bandwidth/measure.py — the kvstore push/pull bandwidth tool).

Measures compiled allreduce (psum) and all_gather throughput over the
active device mesh: the ICI path on real TPU chips, or the virtual CPU
mesh for plumbing checks:

  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python tools/measure_comm.py --size-mb 16
"""
import argparse
import sys
import time

import numpy as np

sys.path.insert(0, ".")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--size-mb", type=float, default=16.0,
                    help="payload per device, MB")
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--dp", type=int, default=0,
                    help="devices to use (0 = all)")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    devices = jax.devices()
    n = args.dp or len(devices)
    devices = devices[:n]
    mesh = Mesh(np.asarray(devices), ("dp",))
    elems = int(args.size_mb * 1e6 / 4)
    x = jnp.arange(n * elems, dtype=jnp.float32).reshape(n, elems)
    x = jax.device_put(x, NamedSharding(mesh, P("dp", None)))

    @jax.jit
    def allreduce(v):
        return jax.shard_map(
            lambda s: jax.lax.psum(s, "dp"), mesh=mesh,
            in_specs=P("dp", None), out_specs=P(None, None))(v)

    @jax.jit
    def allgather(v):
        return jax.shard_map(
            lambda s: jax.lax.all_gather(s, "dp"), mesh=mesh,
            in_specs=P("dp", None), out_specs=P(None, "dp", None))(v)

    for name, fn in (("allreduce", allreduce), ("all_gather", allgather)):
        out = fn(x)
        jax.block_until_ready(out)  # compile
        t0 = time.perf_counter()
        for _ in range(args.iters):
            out = fn(x)
        jax.block_until_ready(out)
        dt = (time.perf_counter() - t0) / args.iters
        # ring cost model: 2(n-1)/n of the payload crosses each link
        payload = elems * 4
        algo_bw = payload / dt / 1e9
        bus_bw = algo_bw * 2 * (n - 1) / n
        print(f"{name:<11} n={n}  {args.size_mb:.0f}MB/dev  "
              f"{dt * 1e3:7.2f} ms   algo {algo_bw:6.2f} GB/s   "
              f"bus {bus_bw:6.2f} GB/s")


if __name__ == "__main__":
    main()
