#!/usr/bin/env python
"""Long-context attention beyond one device's memory: ring attention over
the 'sp' mesh axis (north-star capability; no reference equivalent).

Run on any host:
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    JAX_PLATFORMS=cpu python examples/long_context/ring_attention_demo.py
On a TPU pod the same code runs over real chips (drop the env vars).
``--impl flash`` compiles the Pallas kernel and so needs the chips; off
chip add ``--interpret``.
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from mxnet_tpu import parallel


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seq-len", type=int, default=8192)
    ap.add_argument("--heads", type=int, default=4)
    ap.add_argument("--dim", type=int, default=64)
    ap.add_argument("--impl", choices=["dense", "flash"], default="dense",
                    help="per-hop kernel: flash streams each hop through "
                         "the Pallas kernel (O(T_local*BLOCK) memory)")
    ap.add_argument("--interpret", action="store_true",
                    help="run the flash kernel in the Pallas interpreter "
                         "(off-chip demo of --impl flash)")
    args = ap.parse_args()

    n = len(jax.devices())
    mesh = parallel.create_mesh({"sp": n})
    T = args.seq_len
    print(f"{n}-device ring, T={T}: per-device score tile "
          f"{(T // n)**2 * 4 / 1e6:.1f} MB vs dense {T * T * 4 / 1e9:.2f} GB")

    rng = np.random.RandomState(0)
    spec = P(None, None, "sp", None)
    q, k, v = [jax.device_put(
        rng.randn(1, args.heads, T, args.dim).astype(np.float32) * 0.1,
        NamedSharding(mesh, spec)) for _ in range(3)]

    def loss(q, k, v):
        f = jax.shard_map(
            lambda a, b, c: parallel.ring.ring_attention_inner(
                a, b, c, causal=True, impl=args.impl,
                interpret=args.interpret),
            mesh=mesh, in_specs=(spec,) * 3, out_specs=spec,
            check_vma=(args.impl != "flash"))
        return jnp.mean(f(q, k, v) ** 2)

    val, grads = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))(q, k, v)
    jax.block_until_ready(grads)
    print(f"causal ring attention fwd+bwd OK: loss={float(val):.6f}, "
          f"grads finite={all(bool(jnp.isfinite(g).all()) for g in grads)}")


if __name__ == "__main__":
    main()
