"""autotune.py — measured kernel-schedule search (docs/autotune.md).

Sweeps the Pallas flash-attention forward/backward block sizes (plus
the ring-attention per-hop case — the same kernel keyed at the hop's
local shape) and the INT8 conv/FC/requantize arrangement choices,
timing every candidate with the block-on-outputs / min-of-rounds
discipline (PERF.md), REJECTING any candidate whose outputs disagree
with the reference schedule, and persisting winners into the
schema-versioned schedule table that kernel builders read at trace
time and the AOT compile-cache key folds in
(``capture.AOTCache.key``).

The flash workloads compile real Mosaic kernels and key the table
under jax's default backend, so a sweep needs the chip. ``--demo`` is
the one exception, and says so in its output: it runs the kernels in
Pallas interpret mode, keys them under ``interpret`` — emulation timings
must never steer a chip — and shrinks the candidate spaces so the whole
loop (generate -> validate -> measure -> persist -> warm skip) runs in
seconds on CPU CI; a second run does ZERO searches because the target
table is warm (``--force`` re-tunes).

The target table is ``--table`` -> ``MXNET_TPU_SCHEDULE_TABLE`` -> the
committed ``tools/schedule_table.json``.

Prints ONE JSON line (the repo-wide tool contract)::

    {"metric": "autotune_searches", "value": <n>, "unit": "searches",
     "extra": {"backend": ..., "table": ..., "results": [...],
               "skipped_warm": n, "rejected": n}}

Exit code is non-zero when any workload errored out entirely.

Run: JAX_PLATFORMS=cpu python tools/autotune.py --demo
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

DEFAULT_TABLE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "schedule_table.json")


def resolve_table(arg):
    if arg:
        return arg
    env = os.environ.get("MXNET_TPU_SCHEDULE_TABLE", "").strip()
    return env or DEFAULT_TABLE


def build_workloads(quick, interpret=False):
    """The shipped sweep: flash fwd (plain + ring-hop-shaped) and bwd,
    int8 FC / conv / requantize. Shapes are small and fixed-seed so the
    demo is cheap and reproducible; the full mode widens the candidate
    spaces and adds the benchmark cells' own shapes (which need the
    chip)."""
    from mxnet_tpu.tune import search

    kw = {"quick": quick, "interpret": interpret}
    # the GPT-2 medium training cell's attention (benchmarks/configs/
    # gpt2-medium.json under traffic/train_seq1024.json: batch 8 x 16
    # heads, 1024 positions, head 64, bf16 under the training policy) —
    # a production shape, so only outside the demo: how the committed
    # ``flash_fwd|tpu|bfloat16|bh128-t1024-d64`` entry was made
    # The backward kernel at the same shape, and at the Qwen3-Next
    # cell's (configs/qwen3-next-80b-a3b.json under train_seq8192_bs1:
    # one row, 16 query heads after the K/V repeat, 8192 positions, head
    # 256): the ``flash_bwd|tpu|bfloat16|...`` entries. That cell's
    # gated delta rule (one row, 16 key / 32 value heads of 128, 8192
    # positions, chunks of 64), each kernel alone
    production = [] if quick else [
        search.flash_fwd_workload(b=8, h=16, t=1024, d=64, causal=True,
                                  dtype="bfloat16", min_block=128,
                                  interpret=interpret,
                                  label="gpt2m_train_fwd"),
        search.flash_bwd_workload(b=8, h=16, t=1024, d=64, causal=True,
                                  dtype="bfloat16", min_block=256,
                                  interpret=interpret,
                                  label="gpt2m_train_bwd"),
        search.flash_bwd_workload(b=1, h=16, t=8192, d=256, causal=True,
                                  dtype="bfloat16", min_block=256,
                                  interpret=interpret,
                                  label="qwen3next_train_bwd")] + [
        search.delta_rule_workload(kernel, b=1, t=8192, hk=16, hv=32,
                                   dk=128, dv=128, dtype="bfloat16",
                                   interpret=interpret,
                                   label="qwen3next_" + kernel)
        for kernel in ("delta_rule_fwd", "delta_rule_bwd")]
    return production + [
        search.flash_fwd_workload(b=2, h=1, t=256, d=32, causal=True,
                                  **kw, label="flash_fwd"),
        # the ring-attention per-hop case: a rotated K/V block placed
        # one hop later in the global sequence (same kernel, keyed at
        # the hop's local shape)
        search.flash_fwd_workload(b=2, h=1, t=128, d=32, causal=True,
                                  **kw, k_offset=128,
                                  label="ring_hop"),
        search.flash_bwd_workload(b=2, h=1, t=256, d=32, causal=True,
                                  **kw, label="flash_bwd"),
        # the model-zoo transformer's attention shape (gluon/model_zoo/
        # transformer.py head_dim=64): fwd+bwd, so bench.py
        # --model=transformer and the transformer_step@tuned gate key
        # resolve tuned blocks instead of falling back to defaults
        search.flash_fwd_workload(b=2, h=1, t=128, d=64, causal=True,
                                  **kw, label="transformer_fwd"),
        search.flash_bwd_workload(b=2, h=1, t=128, d=64, causal=True,
                                  **kw, label="transformer_bwd"),
        # the serving decode step's paged-attention gather width, keyed
        # at the DecodePredictor default geometry (serving/decode.py)
        search.decode_attn_workload(b=4, pages=8, page_size=16,
                                    **kw),
        search.int8_fc_workload(m=8, k=64, n=32),
        search.int8_conv_workload(n=2, c=8, hw=8, o=16),
        search.int8_requant_workload(rows=8, cols=32),
    ]


def main(argv=None):
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--table", default=None,
                    help="target schedule table (default: "
                         "$MXNET_TPU_SCHEDULE_TABLE or the committed "
                         "tools/schedule_table.json)")
    ap.add_argument("--demo", action="store_true",
                    help="quick candidate spaces; the CPU/interpret "
                         "end-to-end proof")
    ap.add_argument("--force", action="store_true",
                    help="re-tune keys already present in the target "
                         "table")
    ap.add_argument("--rounds", type=int, default=None,
                    help="timing rounds per candidate (min-of-rounds)")
    ap.add_argument("--iters", type=int, default=None,
                    help="iterations per timing round")
    args = ap.parse_args(argv)

    import jax

    from mxnet_tpu.tune import search, stats

    table = resolve_table(args.table)
    rounds = args.rounds or (2 if args.demo else 3)
    iters = args.iters or (3 if args.demo else 8)

    results, errors = [], 0
    skipped = rejected = searches = 0
    for wl in build_workloads(quick=args.demo, interpret=args.demo):
        try:
            res = search.run_search(wl, table, rounds=rounds,
                                    iters=iters, force=args.force)
        except Exception as e:  # a broken workload must not hide others
            errors += 1
            results.append({"label": wl.label, "error": f"{type(e).__name__}: {e}"})
            continue
        results.append(res)
        if res.get("skipped"):
            skipped += 1
        else:
            searches += 1
            rejected += res.get("rejected", 0)
            print(f"autotune: {res['label']} {res['key']} -> "
                  f"{res['winner']} (+{res['margin_pct']}% vs reference, "
                  f"{res['candidates']} timed / {res['rejected']} "
                  "rejected)", file=sys.stderr)

    print(json.dumps({
        "metric": "autotune_searches",
        "value": searches,
        "unit": "searches",
        "extra": {
            "backend": "interpret (demo)" if args.demo
                       else jax.default_backend(),
            "table": table,
            "demo": bool(args.demo),
            "results": results,
            "skipped_warm": skipped,
            "rejected": rejected,
            "errors": errors,
            "counters": stats(),
        },
    }))
    return 1 if errors else 0


if __name__ == "__main__":
    sys.exit(main())
