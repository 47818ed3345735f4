"""Linear attention's backward pass against its roofline: the least time
the chip needs for the delta rule's backward, by shapes, over
``linear_attention_bwd_ms_per_step``.

Least time of one layer = max(FLOPs / bf16 peak, bytes / HBM peak), x
linear-attention layers. FLOPs: twice the forward recurrence's 3 x Dk x
Dv multiply-adds a value head a token (every product of the forward
gives two in the backward), the least any form of it computes. Bytes:
q, k, v, g, beta, o and do read and dq, dk, dv, dg, dbeta written once,
in the compute dtype. The forward pass a ``Remat`` layer recomputes
runs in the backward phase and is in the time but is not needed work
(as in ``attention_bwd_roofline``), so the share reads low where a cell
recomputes and cannot pass 100 %. From the configuration and traffic
files and the chip's published peaks, so the same work whatever
implements it. Layer: kernels."""
from benchmarks import scopes

# the scope this reader needs in the program's names: a program that
# predates it is left out of the line, not failed (harness/cell.py)
SCOPE = "linear_attention"

_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4, None: 4}


def least_ms(config, traffic, peaks):
    """(least time in ms of the step's linear-attention backward, which
    bound)."""
    tokens = int(traffic["batch"]) * int(traffic["seq_len"])
    hk, hv = config["linear_num_key_heads"], config["linear_num_value_heads"]
    dk, dv = config["linear_key_head_dim"], config["linear_value_head_dim"]
    flops = 2 * 2 * 3 * tokens * hv * dk * dv
    read = 2 * hk * dk + 3 * hv * dv + 2 * hv
    written = 2 * hk * dk + hv * dv + 2 * hv
    moved = tokens * (read + written) \
        * _BYTES[config["train"]["compute_dtype"]]
    by_flops = flops / peaks["bf16_flops_per_s"]
    by_bytes = moved / peaks["hbm_bytes_per_s"]
    full = config["num_layers"] // config["full_attention_interval"]
    return ((config["num_layers"] - full) * max(by_flops, by_bytes) * 1e3,
            "compute" if by_flops >= by_bytes else "memory")


def read(run):
    took = scopes.scope_ms(run, "linear_attention", "backward")
    if took is None:
        return None
    least, bound = least_ms(run.config, run.traffic, run.peaks())
    run.log(f"linear attention backward: least time {least:.4f} ms a step "
            f"({bound}-bound), took {took:.3f} ms")
    return 100.0 * least / took if took else None
