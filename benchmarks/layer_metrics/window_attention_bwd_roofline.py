"""Window attention's backward pass against its roofline: the least time
the chip needs for the window layers' backward, by shapes, over
``window_attention_bwd_ms_per_step``.

Least time of one layer = max(FLOPs / bf16 peak, bytes / HBM peak), x
the layers ``layer_types`` calls ``sliding_attention``. FLOPs: the five
matrix products of a flash backward (the scores again, dP, dV, dK, dQ),
each B x H x D multiply-adds a (query, key) pair the window holds:
10 B H D x pairs, pairs = W T - W (W - 1) / 2 for ``sliding_window`` W.
Bytes: Q, K, V, O and dO read and dQ, dK, dV written once, in the
compute dtype, every one at the query heads' width (as
``attention_bwd_roofline`` counts them). A forward pass recomputed under
``Remat`` runs in the backward phase and is in the time but is not
needed work, so the share reads low where a cell recomputes and cannot
pass 100 %. From the configuration and traffic files and the chip's
published peaks, so the same work whatever implements it. Layer:
kernels."""
from benchmarks import window_attention

# the scope this reader needs in the program's names: a program that
# predates it is left out of the line, not failed (harness/cell.py)
SCOPE = "window_attention"

PRODUCTS, TENSORS = 5, 8


def least_ms(config, traffic, peaks):
    """(least time in ms of the step's window-attention backward, which
    bound)."""
    return window_attention.least_ms(config, traffic, peaks, PRODUCTS,
                                     TENSORS)


def read(run):
    took = window_attention.scope_ms(run, "backward")
    if took is None:
        return None
    least, bound = least_ms(run.config, run.traffic, run.peaks())
    run.log(f"window attention backward: least time {least:.4f} ms a step "
            f"({bound}-bound), took {took:.3f} ms")
    return 100.0 * least / took if took else None
