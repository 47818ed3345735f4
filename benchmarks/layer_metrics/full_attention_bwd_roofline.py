"""Of a model with window layers, the backward pass of the attention
layers that see the whole sequence, against its roofline: the least
time the chip needs for them, by shapes, over the time under the scope
``attention`` and not under ``window_attention``
(``attention_bwd_ms_per_step`` less ``window_attention_bwd_ms_per_step``).

Least time of one layer = max(FLOPs / bf16 peak, bytes / HBM peak), x
the layers ``layer_types`` does not call ``sliding_attention``. FLOPs: the
five matrix products of a flash backward (the scores again, dP, dV,
dK, dQ), each B x H x D multiply-adds a (query, key) pair of the causal
half, T (T + 1) / 2 of them. Bytes: as ``window_attention_bwd_roofline``
counts them.
``attention_bwd_roofline`` counts every attention layer as one of
these and so cannot be read where four layers in five see a window;
this is that metric for such a model. From the configuration and
traffic files and the chip's published peaks, so the same work whatever
implements it. Layer: kernels."""
from benchmarks import window_attention

# the scope this reader needs in the program's names: a program that
# predates it is left out of the line, not failed (harness/cell.py)
SCOPE = "window_attention"

PRODUCTS, TENSORS = 5, 8


def least_ms(config, traffic, peaks):
    """(least time in ms of the full layers' attention backward, which
    bound)."""
    return window_attention.least_ms(config, traffic, peaks, PRODUCTS,
                                     TENSORS, full=True)


def read(run):
    took = window_attention.full_scope_ms(run, "backward")
    if took is None:
        return None
    least, bound = least_ms(run.config, run.traffic, run.peaks())
    run.log(f"full attention backward: least time {least:.4f} ms a step "
            f"({bound}-bound), took {took:.3f} ms")
    return 100.0 * least / took if took > 0 else None
