"""Attention's backward pass against its roofline: the least time the
chip needs for it, by shapes, over ``attention_bwd_ms_per_step``.

Least time of one layer = max(FLOPs / bf16 peak, bytes / HBM peak), x
attention layers. FLOPs: the five matrix products of a flash backward
(the scores again, dP, dV, dK, dQ), each B x H x T x T x D multiply-adds,
halved for the causal mask: 10 B H T^2 D / 2. Bytes: Q, K, V, O and dO
read and dQ, dK, dV written once, in the compute dtype, every one at the
query heads' width. A forward pass recomputed under ``Remat`` runs in
the backward phase and is in the time but is not needed work, so the
share reads low where a cell recomputes. Heads, head size and the number
of attention layers come from the configuration under either family's
keys (``n_head`` / ``n_embd`` / ``n_layer``; ``num_attention_heads`` /
``head_dim`` / ``num_layers`` over ``full_attention_interval``). From the
configuration and traffic files and the chip's published peaks, so the
same work whatever implements it. Layer: kernels."""
from benchmarks import attribution

_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4, None: 4}


def attention_shape(config):
    """(query heads, head size, attention layers) of a configuration."""
    if "n_head" in config:
        heads = int(config["n_head"])
        return heads, int(config["n_embd"]) // heads, int(config["n_layer"])
    return (int(config["num_attention_heads"]), int(config["head_dim"]),
            int(config["num_layers"])
            // int(config.get("full_attention_interval", 1)))


def least_ms(config, traffic, peaks):
    """(least time in ms of the step's attention backward, which bound)."""
    b, t = int(traffic["batch"]), int(traffic["seq_len"])
    heads, size, layers = attention_shape(config)
    flops = 10 * b * heads * t * t * size / 2
    moved = 8 * b * t * heads * size \
        * _BYTES[config["train"]["compute_dtype"]]
    by_flops = flops / peaks["bf16_flops_per_s"]
    by_bytes = moved / peaks["hbm_bytes_per_s"]
    return (layers * max(by_flops, by_bytes) * 1e3,
            "compute" if by_flops >= by_bytes else "memory")


def read(run):
    took = attribution.attention_ms(run, "backward")
    if took is None:
        return None
    least, bound = least_ms(run.config, run.traffic, run.peaks())
    run.log(f"attention backward: least time {least:.4f} ms a step "
            f"({bound}-bound), took {took:.3f} ms")
    return 100.0 * least / took if took else 0.0
