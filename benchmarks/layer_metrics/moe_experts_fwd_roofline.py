"""The grouped expert products' forward pass against its roofline: the
least time the chip needs for them, by the tokens the program counted,
over the forward time under the ``moe_experts`` scope (sort, gather,
the grouped products, weighting and scatter).

Least time of one layer = max(FLOPs / bf16 peak, bytes / HBM peak),
summed over the expert layers. FLOPs: 2 x 3 x d x inner for each
assignment the layer's ``expert_tokens`` state counted in the last step
(gate, up and down of one expert). Bytes: the held experts' three
matrices read once, each assignment's row read once and its result
written once, in the compute dtype. Counts are the program's, shapes
the configuration's, peaks the chip's published ones. Layer: moe."""
from benchmarks import scopes

# the scope this reader needs in the program's names: a program that
# predates it is left out of the line, not failed (harness/cell.py)
SCOPE = "moe_experts"

_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4, None: 4}


def least_ms(config, assignments, peaks):
    """Least time in ms of one expert layer's grouped products for
    ``assignments`` (token, expert) pairs on the experts held."""
    d, inner = config["hidden_size"], config["moe_intermediate_size"]
    size = _BYTES[config["train"]["compute_dtype"]]
    flops = 2 * 3 * d * inner * assignments
    moved = (config["num_experts"] * 3 * d * inner
             + 2 * assignments * d) * size
    return 1e3 * max(flops / peaks["bf16_flops_per_s"],
                     moved / peaks["hbm_bytes_per_s"])


def read(run):
    took = scopes.scope_ms(run, "moe_experts", "forward")
    counts = scopes.expert_tokens(run)
    if took is None or not counts:
        return None
    least = sum(least_ms(run.config, per_expert.sum(), run.peaks())
                for per_expert, _ in counts)
    run.log(f"grouped expert products forward: least time {least:.4f} ms "
            f"a step for {[int(c[0].sum()) for c in counts]} assignments a "
            f"layer, took {took:.3f} ms")
    return 100.0 * least / took if took else None
