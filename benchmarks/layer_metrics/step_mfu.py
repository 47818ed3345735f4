"""Model FLOP/s utilisation: the model's FLOPs per item (from the
configuration's shapes, ``models/<model>.flops_per_item``: nothing
recomputed, no XLA cost analysis) x items/s/chip of this run's
untraced part / the chip's published bf16 peak. Layer: program."""


def read(run):
    rate = run.end_to_end.get("train_items_per_s_per_chip")
    if rate is None:
        return None
    flops = run.model.flops_per_item(run.config, run.traffic)
    return 100.0 * flops * rate / run.peaks()["bf16_flops_per_s"]
