"""Measure the fused conv3x3+BN-stats Pallas kernel against XLA
(VERDICT r4 next #1b: 'prototype ONE fused conv+BN Pallas kernel for the
3x3 stride-1 case only, measure, and keep it or kill it with a number').

Both paths compute the full BN-train forward segment:
    y = conv3x3(x, w); mean/var over NHW; out = y * inv + shift
- XLA:    conv, then single-pass stats (the framework's BN), then apply —
          3 logical passes over y plus the x read.
- Pallas: conv WITH stats accumulated in the epilogue, then apply —
          the stats read pass over y disappears.

Usage: python tools/bench_fused_conv_bn.py [--n 64] [--hw 28] [--c 128]
"""
from __future__ import annotations

import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=64)
    ap.add_argument("--hw", type=int, default=28)
    ap.add_argument("--c", type=int, default=128)
    ap.add_argument("--iters", type=int, default=30)
    ap.add_argument("--dtype", default="bfloat16")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from mxnet_tpu.ops.pallas_kernels import conv3x3_bn_stats

    if not any(d.platform != "cpu" for d in jax.devices()):
        print("needs a TPU", file=sys.stderr)
        return 2

    dt = jnp.bfloat16 if args.dtype == "bfloat16" else jnp.float32
    rng = np.random.RandomState(0)
    n, hw, c = args.n, args.hw, args.c
    x = jnp.asarray(rng.randn(n, hw, hw, c), dt)
    w = jnp.asarray(rng.randn(3, 3, c, c) * 0.05, dt)
    gamma = jnp.asarray(rng.rand(c) + 0.5, jnp.float32)
    beta = jnp.asarray(rng.randn(c), jnp.float32)
    cnt = n * hw * hw

    @jax.jit
    def xla_path(x, w):
        y = jax.lax.conv_general_dilated(
            x, w, (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"))
        y32 = y.astype(jnp.float32)
        mean = jnp.mean(y32, axis=(0, 1, 2))
        var = jnp.maximum(
            jnp.mean(jnp.square(y32), axis=(0, 1, 2)) - jnp.square(mean), 0)
        inv = jax.lax.rsqrt(var + 1e-3) * gamma
        shift = beta - mean * inv
        return y * inv.astype(y.dtype) + shift.astype(y.dtype)

    @jax.jit
    def pallas_path(x, w):
        y, s, q = conv3x3_bn_stats(x, w)
        mean = s / cnt
        var = jnp.maximum(q / cnt - jnp.square(mean), 0)
        inv = jax.lax.rsqrt(var + 1e-3) * gamma
        shift = beta - mean * inv
        return y * inv.astype(y.dtype) + shift.astype(y.dtype)

    def timed(fn):
        # the data-dependency chain lives INSIDE one jitted fori_loop:
        # per-iteration eager chain ops would add a host dispatch per
        # iteration and bury the kernel time
        @jax.jit
        def many(x, w):
            def body(_, xi):
                out = fn(xi, w)  # nested jit inlines into the loop body
                return xi + out[0, 0, 0, 0].astype(xi.dtype) * 1e-12
            return jax.lax.fori_loop(0, args.iters, body, x)

        # host-read timing: the window ends when the scalar is on the host
        float(many(x, w)[0, 0, 0, 0].astype(jnp.float32))  # compile+warm
        t0 = time.perf_counter()
        float(many(x, w)[0, 0, 0, 0].astype(jnp.float32))
        return (time.perf_counter() - t0) / args.iters * 1e3

    # numeric check first
    a = np.asarray(xla_path(x, w), np.float32)
    b = np.asarray(pallas_path(x, w), np.float32)
    err = np.abs(a - b).max() / (np.abs(a).max() + 1e-9)
    ms_xla = timed(xla_path)
    ms_pl = timed(pallas_path)
    flops = 2 * 9 * cnt * c * c
    print(f"shape N{n} {hw}x{hw} C{c} {args.dtype}: rel err {err:.2e}",
          file=sys.stderr)
    print(f"xla   : {ms_xla:.3f} ms ({flops / ms_xla / 1e9:.1f} TFLOP/s)",
          file=sys.stderr)
    print(f"pallas: {ms_pl:.3f} ms ({flops / ms_pl / 1e9:.1f} TFLOP/s)",
          file=sys.stderr)
    import json

    print(json.dumps({"metric": "fused_conv3x3_bn_stats",
                      "shape": [n, hw, hw, c], "dtype": args.dtype,
                      "xla_ms": round(ms_xla, 3),
                      "pallas_ms": round(ms_pl, 3),
                      "speedup": round(ms_xla / ms_pl, 3),
                      "rel_err": float(err)}))


if __name__ == "__main__":
    main()
