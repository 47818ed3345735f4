"""The grouped expert products' forward pass against its roofline: the
least time the chip needs for them, by the tokens the program counted,
over the forward time under the ``moe_experts`` scope (sort, gather,
the grouped products, weighting and scatter).

Least time of one layer = max(FLOPs / bf16 peak, bytes / HBM peak),
summed over the expert layers. An expert has m matrices of d x inner,
counted from the configuration: m = 2 where its own key says the experts
are ungated (``mlp_hidden_act`` ``relu2``, as in ``nemotron_h``: down of
relu(up x) squared, no gate), else m = 3 (gate, up and down). FLOPs:
2 x m x d x inner for each assignment the layer's ``expert_tokens``
state counted in the last step. Bytes: the held experts' m matrices
read once, each assignment's row read once and its result written once,
in the compute dtype. Counts are the program's, shapes the
configuration's, peaks the chip's published ones. Layer: moe."""
from benchmarks import scopes

# the scope this reader needs in the program's names: a program that
# predates it is left out of the line, not failed (harness/cell.py)
SCOPE = "moe_experts"

_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4, None: 4}


def matrices(config):
    """An expert's matrices of d x inner: 2 ungated, 3 gated."""
    return 2 if config.get("mlp_hidden_act") == "relu2" else 3


def least_ms(config, assignments, peaks):
    """Least time in ms of one expert layer's grouped products for
    ``assignments`` (token, expert) pairs on the experts held."""
    d, inner = config["hidden_size"], config["moe_intermediate_size"]
    size = _BYTES[config["train"]["compute_dtype"]]
    m = matrices(config)
    flops = 2 * m * d * inner * assignments
    moved = (config["num_experts"] * m * d * inner
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
