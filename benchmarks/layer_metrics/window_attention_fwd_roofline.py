"""Window attention's forward pass against its roofline: the least time
the chip needs for the window layers, by shapes, over
``window_attention_fwd_ms_per_step``.

Least time of one layer = max(FLOPs / bf16 peak, bytes / HBM peak), x
the layers ``layer_types`` calls ``sliding_attention``. FLOPs: the two
matrix products of a flash forward (Q.K^T, P.V), each B x H x D
multiply-adds a (query, key) pair the window holds: 4 B H D x pairs,
pairs = W T - W (W - 1) / 2 for ``sliding_window`` W. Bytes: Q, K, V
read and O written once, in the compute dtype, every one at the query
heads' width (as ``attention_bwd_roofline`` counts them). The same work
whatever implements it: a kernel that masks the keys behind the window
without skipping their tiles takes the causal layer's time for 44 % of
its work at T = 8192, W = 2048, and none can read over 100 %. From the
configuration and traffic files and the chip's published peaks. Layer:
kernels."""
from benchmarks import window_attention

# the scope this reader needs in the program's names: a program that
# predates it is left out of the line, not failed (harness/cell.py)
SCOPE = "window_attention"

PRODUCTS, TENSORS = 2, 4


def least_ms(config, traffic, peaks):
    """(least time in ms of the step's window-attention forward, which
    bound)."""
    return window_attention.least_ms(config, traffic, peaks, PRODUCTS,
                                     TENSORS)


def read(run):
    took = window_attention.scope_ms(run, "forward")
    if took is None:
        return None
    least, bound = least_ms(run.config, run.traffic, run.peaks())
    run.log(f"window attention forward: least time {least:.4f} ms a step "
            f"({bound}-bound), took {took:.3f} ms")
    return 100.0 * least / took if took else None
