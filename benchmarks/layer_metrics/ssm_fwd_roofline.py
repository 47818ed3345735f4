"""The Mamba-2 layers' forward pass against its roofline: the least time
the chip needs for the work under the ``ssm`` scope, by shapes, over
``ssm_fwd_ms_per_step``.

Least time of one layer = max(FLOPs / bf16 peak, bytes / HBM peak), x
the layers ``layer_types`` calls ``mamba``. FLOPs: the recurrence's 2 x
3 x head_dim x state a head a token (decay, write, read), the least any
form of it computes (the chunked form does more). Bytes, in the compute
dtype, each array once: the convolution's xBC read and written; x, B,
C and dt read and y written by the scan; y and z read by the gated norm
(``benchmarks/ssm_scope.py``). From the configuration and traffic files
and the chip's published peaks, so the same work whatever implements
it: a later kernel is judged on the same yardstick. Layer: kernels."""
from benchmarks import ssm_scope

# the scope this reader needs in the program's names: a program that
# predates it is left out of the line, not failed (harness/cell.py)
SCOPE = "ssm"


def least_ms(config, traffic, peaks):
    """(least time in ms of the step's Mamba-2 forward, which bound)."""
    return ssm_scope.least_ms(config, traffic, peaks)


def read(run):
    took = ssm_scope.scope_ms(run, "forward")
    if took is None:
        return None
    least, bound = least_ms(run.config, run.traffic, run.peaks())
    run.log(f"ssm forward: least time {least:.4f} ms a step "
            f"({bound}-bound), took {took:.3f} ms")
    return 100.0 * least / took if took else None
