"""Of the tokens the expert layers counted for each held expert in the
last step (their ``expert_tokens`` state), the largest over experts and
layers: the straggler a grouped product waits for. The log also gives,
per layer, the assignments held (every one is computed: the layer has
no capacity factor and drops no token) and the tokens that chose no
held expert. Layer: moe."""
from benchmarks import scopes


def read(run):
    counts = scopes.expert_tokens(run)
    if not counts:
        return None
    for i, (per_expert, idle) in enumerate(counts):
        run.log(f"expert layer {i}: {int(per_expert.sum())} assignments to "
                f"the {per_expert.size} experts held (largest "
                f"{int(per_expert.max())}, smallest {int(per_expert.min())}"
                f"), all computed, none dropped; {int(idle)} tokens chose "
                "no held expert")
    return max(float(per_expert.max()) for per_expert, _ in counts)
