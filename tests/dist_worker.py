"""Worker body for the multi-process kvstore test (run via tools/launch.py).

Asserts the reference's dist_sync contract (tests/nightly/
dist_sync_kvstore.py:30 pattern): after identical pushes every worker holds
identical aggregated values. Results are dumped per-rank for the parent
pytest process to cross-check.
"""
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"

import numpy as np  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import mxnet_tpu as mx  # noqa: E402


def main():
    outdir = sys.argv[1]
    kv = mx.kv.create("dist_sync")
    rank, nw = kv.rank, kv.num_workers
    assert nw == int(os.environ["DMLC_NUM_WORKER"]), (nw, os.environ)

    # 1. init: every worker converges on rank-0's value
    kv.init("w", mx.nd.array(np.full((4,), 7.0 if rank == 0 else -1.0,
                                     np.float32)))
    out = mx.nd.zeros((4,))
    kv.pull("w", out=out)
    init_val = out.asnumpy().copy()

    # 2. push without updater: store holds the cross-worker sum
    kv.push("g", mx.nd.array(np.full((3,), float(rank + 1), np.float32)))
    gout = mx.nd.zeros((3,))
    kv.pull("g", out=gout)
    g_sum = gout.asnumpy().copy()

    # 3. updater path: every worker applies sgd to the allreduced grad
    kv.set_optimizer(mx.optimizer.SGD(learning_rate=0.1))
    kv.push("w", mx.nd.array(np.full((4,), float(rank + 1), np.float32)))
    kv.pull("w", out=out)
    w_after = out.asnumpy().copy()

    kv.barrier()
    np.savez(os.path.join(outdir, f"rank{rank}.npz"),
             init_val=init_val, g_sum=g_sum, w_after=w_after, nw=nw)
    print(f"rank {rank}/{nw} done", flush=True)


if __name__ == "__main__":
    main()
