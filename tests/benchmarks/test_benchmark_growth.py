"""The accepted benchmark can grow, and grow again: one cell more is laid
over a copy of the checkout in a temporary directory by new files and
entries alone (a configuration with ``reduced`` and ``published``, a
model and a reference file that need not run, a rehearsal preset, the
balanced traffic that is there, one new per-layer entry with its reader,
the cell's name appended to every list an ungated expert cell with one
full-attention layer in seven belongs to) and meets every rule of
``rules.py``; then it is broken five ways, and each fault is refused by
the rule that owns it. The same is done over a copy that has grown once
already, the way a ``model_config`` PR lays its cell, so no test here
counts on the number of cells the checkout holds: each count is taken
from the tree it reads. What a later ``model_config`` PR will do is
rehearsed here, so that no test of the accepted benchmark has to give
way for it."""
import json
import os
import shutil
import sys
from typing import NamedTuple

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from tests.benchmarks import rules  # noqa: E402

CONFIG, CELL = "grown-lm", "grownlm_train_seq8192_balanced"
TRAFFIC = "train_seq8192_bs1_balanced"
NEW_METRIC = "grown_mixer_fwd_roofline"
# an expert cell with attention layers: the lists of every training cell,
# attention's two times (its rooflines count every layer as attention and
# are not for it), the four of the expert layers
TIMES = ("attention_fwd_ms_per_step", "attention_bwd_ms_per_step")
MOE = ("moe_fwd_ms_per_step", "moe_bwd_ms_per_step",
       "moe_experts_fwd_roofline", "moe_busiest_expert_tokens")
MOST = 24       # cells a manifest may hold

# ungated experts (relu2: down of relu(up x) squared), 8 of 128 held,
# top-6, one attention layer in seven
CONFIG_FILE = {
    "name": CONFIG, "source": "https://example.org/grown-lm/config.json",
    "model": "grown_lm", "reference": "grown_lm", "item": "token",
    "hidden_size": 2688, "moe_intermediate_size": 1856,
    "mlp_hidden_act": "relu2", "num_attention_heads": 32,
    "num_key_value_heads": 2, "head_dim": 128, "conv_kernel": 4,
    "num_experts_per_tok": 6, "num_experts": 8, "vocab_size": 16384,
    "num_layers": 7,
    "layer_types": ["moe", "mamba", "moe", "mamba", "moe", "mamba",
                    "full_attention"],
    "published": {"num_experts": 128, "vocab_size": 131072,
                  "num_hidden_layers": 52},
    "deployment": {"chips_per_layer": 16, "first_expert": 0},
    "reduced": ["num_layers", "num_experts", "vocab_size", "layer_types"],
    "train": {"compute_dtype": "bfloat16"}}
# the cell a grown copy already holds: gated experts, 8 of 32 held,
# top-4, one full-attention layer in five
EARLIER_FILE = {
    "name": "earlier-lm",
    "source": "https://example.org/earlier-lm/config.json",
    "model": "earlier_lm", "reference": "earlier_lm", "item": "token",
    "hidden_size": 2048, "intermediate_size": 7168,
    "moe_intermediate_size": 1792, "num_attention_heads": 32,
    "num_key_value_heads": 8, "head_dim": 64, "conv_L_cache": 3,
    "num_experts_per_tok": 4, "num_experts": 8, "vocab_size": 8192,
    "num_layers": 5,
    "layer_types": ["conv", "full_attention", "conv", "conv", "conv"],
    "published": {"num_experts": 32, "vocab_size": 65536,
                  "num_hidden_layers": 24},
    "deployment": {"chips_per_layer": 4, "first_expert": 0},
    "reduced": ["num_layers", "num_experts", "vocab_size", "layer_types"],
    "train": {"compute_dtype": "bfloat16"}}
MODEL_FILE = '''"""A model file that need not run: what the rules ask."""


def flops_per_item(config, traffic):
    return 6.0 * config["hidden_size"] ** 2


def build_trainer(config, traffic, seed, devices, reference):
    raise NotImplementedError("laid over the manifest by a test")


def expert_tokens():
    return None
'''
READER_FILE = '''"""The grown cell's own mixer. Layer: kernels."""


def least_ms(config, traffic, peaks):
    moved = 2 * int(traffic["batch"]) * int(traffic["seq_len"]) \\
        * config["hidden_size"] * 2
    return 1e3 * moved / peaks["hbm_bytes_per_s"], "memory"


def read(run):
    return None
'''


class Laid(NamedTuple):
    """One cell as a PR lays it: its configuration file, its name, its
    own per-layer entry and the two lines of ``why``."""
    config: dict
    cell: str
    metric: str
    config_why: str
    cell_why: str


GROWN = Laid(CONFIG_FILE, CELL, NEW_METRIC,
             "Mamba-2 mixers, ungated experts and plain GQA attention: one "
             "chip of 16 holds 8 of 128 experts, 1/8 vocabulary",
             "1 x 8192 tokens at an even load: 3072 held rows a layer, "
             "ungated experts")
EARLIER = Laid(EARLIER_FILE, "earlierlm_train_seq8192_balanced",
               "earlier_mixer_fwd_roofline",
               "short convolutions and full attention 4:1, top-4 of 32 "
               "experts: one chip of 4 holds 8, 1/8 vocabulary",
               "1 x 8192 tokens at an even load: 8192 held rows a layer, "
               "the edge of the expert layers' block rule")


def _write(root, relative, text):
    path = os.path.join(root, *relative.split("/"))
    assert not os.path.exists(path), f"{relative} is there: that is an edit"
    with open(path, "w", encoding="utf-8") as f:
        f.write(text)


def _manifest(root, change):
    path = os.path.join(root, "BENCHMARK.json")
    with open(path, encoding="utf-8") as f:
        manifest = json.load(f)
    change(manifest)
    with open(path, "w", encoding="utf-8") as f:
        json.dump(manifest, f, indent=1)


def _lists_of_every_training_cell(manifest):
    return [m for m in manifest["end_to_end"] + manifest["per_layer"]
            if set(rules.ACCEPTED_CELLS) <= set(m.get("workloads", ()))]


def _lay(root, laid):
    """New files, and entries appended to BENCHMARK.json."""
    config = laid.config
    _write(root, f"benchmarks/configs/{config['name']}.json",
           json.dumps(config, indent=1))
    _write(root, f"benchmarks/models/{config['model']}.py", MODEL_FILE)
    _write(root, f"benchmarks/references/{config['reference']}.py",
           '"""A plain reference that need not run."""\n')
    _write(root, f"benchmarks/rehearsal/{laid.cell}.json",
           json.dumps({"config": {"hidden_size": 64}, "traffic": {}}))
    _write(root, f"benchmarks/layer_metrics/{laid.metric}.py", READER_FILE)

    def entries(manifest):
        manifest["configs"].append({
            "name": config["name"], "source": config["source"],
            "file": f"benchmarks/configs/{config['name']}.json",
            "reduced": config["reduced"], "why": laid.config_why})
        manifest["workloads"].append({
            "name": laid.cell, "config": config["name"], "traffic": TRAFFIC,
            "chips": 1, "why": laid.cell_why})
        listed = {m["name"]: m for m in manifest["per_layer"]}
        for entry in _lists_of_every_training_cell(manifest) \
                + [listed[name] for name in TIMES + MOE]:
            entry["workloads"].append(laid.cell)
        manifest["per_layer"].append({
            "name": laid.metric, "unit": "%", "better": "higher",
            "source": "device_trace", "layer": "kernels",
            "moves": rules.RATE, "workloads": [laid.cell]})

    _manifest(root, entries)


def _copy_of_the_checkout(root):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copytree(
        os.path.join(ROOT, "benchmarks"), os.path.join(root, "benchmarks"),
        ignore=shutil.ignore_patterns("__pycache__", "fixtures"))
    os.makedirs(os.path.join(root, "tests", "benchmarks"))


@pytest.fixture(scope="module")
def grown(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("grown"))
    _copy_of_the_checkout(root)
    _lay(root, GROWN)
    return root


@pytest.fixture(scope="module")
def earlier(tmp_path_factory):
    """The checkout as a ``model_config`` PR leaves it: one cell more."""
    root = str(tmp_path_factory.mktemp("earlier"))
    _copy_of_the_checkout(root)
    _lay(root, EARLIER)
    return root


@pytest.fixture(scope="module")
def grown_twice(earlier, tmp_path_factory):
    root = str(tmp_path_factory.mktemp("grown_twice"))
    shutil.copytree(earlier, root, dirs_exist_ok=True)
    _lay(root, GROWN)
    return root


def _meets(root, was, rule):
    """One cell more than ``was`` holds, an expert cell, and the rule."""
    bench = rules.Bench(root)
    assert len(bench.cells) == len(rules.Bench(was).cells) + 1
    assert CELL in bench.cells_of("moe_busiest_expert_tokens")
    rule(bench)


@pytest.mark.parametrize("rule", rules.ALL, ids=lambda rule: rule.__name__)
def test_a_sixth_cell_laid_over_the_manifest_meets(grown, rule):
    """One cell more than the checkout holds (the sixth of six while
    five are accepted)."""
    _meets(grown, ROOT, rule)


@pytest.mark.parametrize("rule", rules.ALL, ids=lambda rule: rule.__name__)
def test_a_cell_laid_over_a_grown_copy_meets(earlier, grown_twice, rule):
    _meets(grown_twice, earlier, rule)


def _came_by_new_files_and_entries_alone(was, now):
    """No file under ``benchmarks/`` of ``was`` differs in ``now``, and
    in BENCHMARK.json every entry of ``was`` is as it was but for the
    names appended to its list."""
    for folder, _, files in os.walk(os.path.join(was, "benchmarks")):
        if os.path.basename(folder) in ("__pycache__", "fixtures"):
            continue
        for fname in files:
            path = os.path.join(folder, fname)
            with open(path, "rb") as f, open(os.path.join(
                    now, os.path.relpath(path, was)), "rb") as g:
                assert f.read() == g.read(), path
    before_all, after_all = rules.Bench(was).manifest, \
        rules.Bench(now).manifest
    for key in ("command", "paths", "run_seconds"):
        assert after_all[key] == before_all[key]
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        assert len(after_all[key]) >= len(before_all[key])
        for before, after in zip(before_all[key], after_all[key]):
            before, after = dict(before), dict(after)
            cells, more = before.pop("workloads", []), \
                after.pop("workloads", [])
            assert after == before and more[:len(cells)] == cells


def test_the_sixth_cell_came_by_new_files_and_entries_alone(grown):
    _came_by_new_files_and_entries_alone(ROOT, grown)


def test_the_cell_laid_over_a_grown_copy_came_by_new_files_and_entries(
        earlier, grown_twice):
    _came_by_new_files_and_entries_alone(earlier, grown_twice)


def _four_chip_cells_past_a_quarter(root):
    """Cells on four chips until they are one more than a quarter of the
    cells allows (one always may): new ones while the manifest has room,
    then one-chip cells laid after the accepted ones moved to four chips.
    Returns the number of cells the rule's message names."""
    def entries(manifest):
        cells = manifest["workloads"]
        movable = [w for w in cells if w["chips"] == 1
                   and w["name"] not in rules.ACCEPTED_CELLS]
        while sum(w["chips"] == 4 for w in cells) \
                <= max(1, len(cells) // 4):
            if len(cells) == MOST:
                movable.pop()["chips"] = 4
                continue
            name = f"grownlm_train_dp4_{len(cells)}"
            cells.append({
                "name": name, "config": CONFIG,
                "traffic": f"train_dp4_bs1024_{len(cells)}", "chips": 4,
                "why": "one more cell that asks for four chips"})
            for entry in _lists_of_every_training_cell(manifest):
                entry["workloads"].append(name)

    _manifest(root, entries)
    return len(rules.Bench(root).cells)


def _a_width_in_reduced(root):
    path = os.path.join(root, "benchmarks", "configs", CONFIG + ".json")
    cut = dict(CONFIG_FILE, moe_intermediate_size=928, reduced=CONFIG_FILE[
        "reduced"] + ["moe_intermediate_size"])
    with open(path, "w", encoding="utf-8") as f:
        json.dump(cut, f)
    _manifest(root, lambda manifest: manifest["configs"][-1].update(
        reduced=cut["reduced"]))


def _an_accepted_cell_dropped_from_a_list(root):
    def drop(manifest):
        entry = next(m for m in manifest["per_layer"]
                     if m["name"] == "attention_bwd_roofline")
        entry["workloads"].remove(rules.GPT2)

    _manifest(root, drop)


def _a_cell_without_expert_tokens_under_moe(root):
    def add(manifest):
        for entry in manifest["per_layer"]:
            if entry["name"] in MOE:
                entry["workloads"].append(rules.GPT2)

    _manifest(root, add)


def _a_reader_file_no_entry_lists(root):
    _write(root, "benchmarks/layer_metrics/orphan_ms_per_step.py",
           READER_FILE)


# (fault, the rule that owns it, what the rule says: ``{cells}`` is the
# count the fault returns)
FAULTS = [
    (_four_chip_cells_past_a_quarter,
     rules.accepted_cells_stand_and_few_take_four_chips,
     "take four chips, of {cells} cells"),
    (_a_width_in_reduced,
     rules.configurations_have_their_files_and_cut_no_width,
     "reduced names the width"),
    (_an_accepted_cell_dropped_from_a_list,
     rules.accepted_entries_keep_their_fields_and_their_cells,
     "attention_bwd_roofline no longer lists"),
    (_a_cell_without_expert_tokens_under_moe, rules.families_stay_whole,
     "offer expert_tokens"),
    (_a_reader_file_no_entry_lists,
     rules.every_reader_file_is_listed_and_has_a_read,
     "orphan_ms_per_step.py is in neither"),
]


def _fault_name(case):
    return getattr(case, "__name__", None)


def _refused(tree, root, fault, owner, says):
    shutil.copytree(tree, root)
    owner(rules.Bench(root))        # sound before the fault
    cells = fault(root)
    with pytest.raises(AssertionError, match=says.format(cells=cells)):
        owner(rules.Bench(root))


@pytest.mark.parametrize("fault,owner,says", FAULTS, ids=_fault_name)
def test_a_fault_is_refused_by_the_rule_that_owns_it(grown, tmp_path, fault,
                                                     owner, says):
    _refused(grown, str(tmp_path / "broken"), fault, owner, says)


@pytest.mark.parametrize("fault,owner,says", FAULTS, ids=_fault_name)
def test_a_fault_of_a_grown_copy_is_refused_by_the_rule_that_owns_it(
        grown_twice, tmp_path, fault, owner, says):
    _refused(grown_twice, str(tmp_path / "broken"), fault, owner, says)


@pytest.mark.parametrize("count", [12, MOST - 1, MOST])
def test_too_many_four_chip_cells_are_refused_at_any_count(grown, tmp_path,
                                                          count):
    """The grown copy filled with one-chip cells up to ``count`` (or as
    it is, where it holds more), then the four-chip fault: its count, and
    the rule's message, follow the tree."""
    root = str(tmp_path / "filled")
    shutil.copytree(grown, root)

    def fill(manifest):
        cells = manifest["workloads"]
        while len(cells) < count:
            cells.append({"name": f"filler_{len(cells)}", "config": CONFIG,
                          "traffic": f"filler_{len(cells)}", "chips": 1,
                          "why": "a one-chip cell that fills the manifest"})

    _manifest(root, fill)
    _refused(root, str(tmp_path / "broken"),
             _four_chip_cells_past_a_quarter,
             rules.accepted_cells_stand_and_few_take_four_chips,
             "take four chips, of {cells} cells")


def test_a_cell_listed_under_a_reader_that_cannot_count_it_fails_here(
        grown, tmp_path):
    """``attention_fwd_roofline`` counts by GPT-2's keys: the grown
    configuration has none of them, and the rule says so by name."""
    root = str(tmp_path / "miscounted")
    shutil.copytree(grown, root)

    def add(manifest):
        next(m for m in manifest["per_layer"]
             if m["name"] == "attention_fwd_roofline")[
                 "workloads"].append(CELL)

    _manifest(root, add)
    with pytest.raises(AssertionError,
                       match=f"attention_fwd_roofline cannot count {CELL}"):
        rules.lists_name_cells_their_readers_can_count(rules.Bench(root))
