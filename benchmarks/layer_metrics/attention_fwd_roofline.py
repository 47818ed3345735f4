"""Attention's forward pass against its roofline: the least time the
chip needs for it, by shapes, over ``attention_fwd_ms_per_step``.

Least time of one layer = max(FLOPs / bf16 peak, bytes / HBM peak), x
layers. FLOPs: two matmuls of B x H x T x T x D multiply-adds, halved
for the causal mask: 4 B H T^2 D / 2. Bytes: Q, K, V read and O written
once, in the compute dtype. From the configuration and traffic files and
the chip's published peaks, so the same work whatever implements it.
Layer: kernels."""
from benchmarks import attribution

_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4, None: 4}


def least_ms(config, traffic, peaks):
    """(least time in ms of the step's attention forward, which bound)."""
    b, t = int(traffic["batch"]), int(traffic["seq_len"])
    heads, width = int(config["n_head"]), int(config["n_embd"])
    flops = 4 * b * heads * t * t * (width // heads) / 2
    moved = 4 * b * t * width * _BYTES[config["train"]["compute_dtype"]]
    by_flops = flops / peaks["bf16_flops_per_s"]
    by_bytes = moved / peaks["hbm_bytes_per_s"]
    return (int(config["n_layer"]) * max(by_flops, by_bytes) * 1e3,
            "compute" if by_flops >= by_bytes else "memory")


def read(run):
    took = attribution.attention_ms(run, "forward")
    if took is None:
        return None
    least, bound = least_ms(run.config, run.traffic, run.peaks())
    run.log(f"attention forward: least time {least:.4f} ms a step "
            f"({bound}-bound), took {took:.3f} ms")
    return 100.0 * least / took if took else 0.0
