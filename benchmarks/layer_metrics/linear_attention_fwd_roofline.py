"""Linear attention's forward pass against its roofline: the least time
the chip needs for the delta rule, by shapes, over
``linear_attention_fwd_ms_per_step``.

Least time of one layer = max(FLOPs / bf16 peak, bytes / HBM peak), x
linear-attention layers. FLOPs: the recurrence's 3 x Dk x Dv
multiply-adds a value head a token (decay and read, write, output), the
least any form of it computes (the chunked form does more). Bytes: q, k,
v, g and beta read and o written once, in the compute dtype. From the
configuration and traffic files and the chip's published peaks, so the
same work whatever implements it. Layer: kernels."""
from benchmarks import scopes

# the scope this reader needs in the program's names: a program that
# predates it is left out of the line, not failed (harness/cell.py)
SCOPE = "linear_attention"

_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4, None: 4}


def least_ms(config, traffic, peaks):
    """(least time in ms of the step's linear-attention forward, which
    bound)."""
    tokens = int(traffic["batch"]) * int(traffic["seq_len"])
    hk, hv = config["linear_num_key_heads"], config["linear_num_value_heads"]
    dk, dv = config["linear_key_head_dim"], config["linear_value_head_dim"]
    flops = 2 * 3 * tokens * hv * dk * dv
    moved = tokens * (2 * hk * dk + 2 * hv * dv + 2 * hv) \
        * _BYTES[config["train"]["compute_dtype"]]
    by_flops = flops / peaks["bf16_flops_per_s"]
    by_bytes = moved / peaks["hbm_bytes_per_s"]
    full = config["num_layers"] // config["full_attention_interval"]
    return ((config["num_layers"] - full) * max(by_flops, by_bytes) * 1e3,
            "compute" if by_flops >= by_bytes else "memory")


def read(run):
    took = scopes.scope_ms(run, "linear_attention", "forward")
    if took is None:
        return None
    least, bound = least_ms(run.config, run.traffic, run.peaks())
    run.log(f"linear attention forward: least time {least:.4f} ms a step "
            f"({bound}-bound), took {took:.3f} ms")
    return 100.0 * least / took if took else None
