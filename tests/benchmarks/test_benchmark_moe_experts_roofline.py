"""``moe_experts_fwd_roofline``: an expert's matrices are counted from
its configuration. The two accepted expert configurations (gated, three
matrices an expert) get exactly the floats they got when every expert was
counted as three; an ungated configuration (``mlp_hidden_act`` ``relu2``:
down of relu(up x) squared) gets two matrices, counted here by hand."""
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.harness import device, manifest  # noqa: E402

READER = manifest.module("layer_metrics", "moe_experts_fwd_roofline")
PEAKS = device.peaks("TPU v5 lite")

# least_ms by assignments, as the three-matrix count gave it: 2560 is
# Qwen3-Next's even share (8192 tokens x 10 x 16 / 512), 4096
# Trinity-Mini's (8192 x 8 x 8 / 128)
GATED = {
    "qwen3-next-80b-a3b": {
        1: 0.12292000976800978, 160: 0.12451039804639805,
        2560: 0.14851625885225883, 3072: 0.15363750915750915,
        4096: 0.16388000976800976, 8192: 0.2616223733604061,
        81920: 2.616223733604061, 2560.0: 0.14851625885225883},
    "trinity-mini": {
        1: 0.12292000976800978, 160: 0.12451039804639805,
        2560: 0.1635139833502538, 3072: 0.19621678002030457,
        4096: 0.2616223733604061, 8192: 0.5232447467208122,
        81920: 5.232447467208122, 2560.0: 0.1635139833502538}}

# an ungated expert layer at the widths of the next expert configuration:
# d 2688, inner 1856, 8 experts held, bf16
UNGATED = {"hidden_size": 2688, "moe_intermediate_size": 1856,
           "mlp_hidden_act": "relu2", "num_experts": 8,
           "train": {"compute_dtype": "bfloat16"}}


def _config(name):
    with open(os.path.join(ROOT, "benchmarks", "configs", name + ".json"),
              encoding="utf-8") as f:
        return json.load(f)


@pytest.mark.parametrize("name", sorted(GATED))
def test_the_accepted_expert_configurations_read_as_before(name):
    config = _config(name)
    assert READER.matrices(config) == 3
    for assignments, was in GATED[name].items():
        assert READER.least_ms(config, assignments, PEAKS) == was, \
            (name, assignments)
    assert READER.least_ms(config, np.int64(4096), PEAKS) \
        == GATED[name][4096]


def test_an_ungated_expert_counts_two_matrices():
    """3072 assignments (8192 tokens x top-6 x 8 / 128): 2 x 2 x 2688 x
    1856 = 19 955 712 FLOPs each, 61.30 GFLOP a layer, 0.3112 ms
    at 197 TFLOP/s; 2 x 2688 x 1856 x 8 weights and 2 x 3072 rows of
    2688 are 192.7 MB, 0.2353 ms at 819 GB/s: compute-bound. One
    assignment: the weights alone, 0.1949 ms, memory-bound."""
    assert READER.matrices(UNGATED) == 2
    got = READER.least_ms(UNGATED, 3072, PEAKS)
    assert got == 1e3 * (61_303_947_264 / 197e12)
    assert got == pytest.approx(0.31119, abs=1e-5)
    assert READER.least_ms(UNGATED, 1, PEAKS) \
        == 1e3 * (159_656_448 / 819e9)
    gated = dict(UNGATED, mlp_hidden_act="silu")
    assert READER.matrices(gated) == 3
    assert READER.least_ms(gated, 3072, PEAKS) == pytest.approx(1.5 * got)
