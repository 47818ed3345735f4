"""BENCHMARK.json against the rules a later PR has to keep when it adds a
cell: every name resolves to a file of its own, every cell reports what
the contract asks, nothing is found by editing the harness."""
import json
import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.harness import manifest  # noqa: E402

MANIFEST = manifest.load()
WITH_PENDING = manifest.load(pending=True)
NAME = re.compile(r"^[A-Za-z0-9][A-Za-z0-9_.\-]{0,63}$")
LAYER = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
# a width; the catalog's own key for the depth, ``num_hidden_layers``, is
# not one, and ``reduced`` may name it
WIDTH = re.compile(r"(hidden(?!_layers$)|intermediate|latent|state|proj|"
                   r"head_dim|_dim$|_rank$|expansion|experts_per_tok|"
                   r"n_embd|n_inner)")
CELLS = [w["name"] for w in MANIFEST["workloads"]]
ALL_CELLS = [w["name"] for w in WITH_PENDING["workloads"]]


def _bench(*parts):
    return os.path.join(ROOT, "benchmarks", *parts)


def test_top_level_keys_and_sizes():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert isinstance(MANIFEST["run_seconds"], int)
    assert 1 <= MANIFEST["run_seconds"] <= 51
    assert 1 <= len(MANIFEST["command"]) <= 32
    assert 1 <= len(MANIFEST["paths"]) <= 16
    for path in MANIFEST["paths"]:
        assert os.path.isdir(os.path.join(ROOT, path))
        assert not path.startswith("/") and ".." not in path.split("/")
    for arg in MANIFEST["command"]:
        if os.path.exists(os.path.join(ROOT, arg)):
            assert any(arg.startswith(p + "/") for p in MANIFEST["paths"])


def test_names_are_plain_and_used_once():
    names = ([c["name"] for c in MANIFEST["configs"]] + CELLS
             + [m["name"] for m in MANIFEST["end_to_end"]]
             + [m["name"] for m in MANIFEST["per_layer"]])
    for name in names:
        assert NAME.match(name), name
    assert len(names) == len(set(names))
    for entry in MANIFEST["configs"] + MANIFEST["workloads"]:
        assert len(entry["why"]) <= 200, entry["name"]


def test_configurations_have_their_files_and_cut_no_width():
    files = [c["file"] for c in MANIFEST["configs"]]
    assert len(files) == len(set(files))
    used = {w["config"] for w in MANIFEST["workloads"]}
    for cfg in MANIFEST["configs"]:
        assert cfg["name"] in used, f"{cfg['name']} has no cell"
        assert any(cfg["file"].startswith(p + "/")
                   for p in MANIFEST["paths"])
        with open(os.path.join(ROOT, cfg["file"]), encoding="utf-8") as f:
            body = json.load(f)
        assert body["reduced"] == cfg["reduced"]
        assert not [k for k in cfg["reduced"] if WIDTH.search(k)]
        assert os.path.exists(_bench("models", body["model"] + ".py"))
        assert os.path.exists(_bench("references",
                                     body["reference"] + ".py"))
        assert cfg["source"].startswith("http")


def test_reduced_may_give_the_depth_under_the_catalogs_key():
    assert not WIDTH.search("num_hidden_layers")
    assert not WIDTH.search("num_layers")
    for width in ("hidden_size", "intermediate_size", "kv_lora_rank",
                  "moe_intermediate_size", "head_dim", "v_head_dim",
                  "num_experts_per_tok", "ssm_state_size",
                  "hidden_layers_dim"):
        assert WIDTH.search(width), width


def test_five_cells_one_on_four_chips_and_every_configuration_has_one():
    assert len(CELLS) == 5
    assert [w["name"] for w in MANIFEST["workloads"]
            if w["chips"] == 4] == ["resnet50_train_dp4"]
    assert "trinitymini_train_seq8192_balanced" in CELLS
    assert "trinitymini_train_seq8192" not in CELLS
    assert {w["config"] for w in MANIFEST["workloads"]} \
        == {c["name"] for c in MANIFEST["configs"]}


def test_only_the_serving_cell_waits_under_pending():
    assert sorted(os.listdir(_bench("pending"))) \
        == ["gpt2m_serve_chat_steady.json"]
    assert set(ALL_CELLS) - set(CELLS) == {"gpt2m_serve_chat_steady"}


def test_cells_are_unique_pairs_and_few_take_four_chips():
    assert 2 <= len(CELLS) <= 24
    pairs = [(w["config"], w["traffic"]) for w in MANIFEST["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert all(w["chips"] in (1, 4) for w in MANIFEST["workloads"])
    four = sum(w["chips"] == 4 for w in MANIFEST["workloads"])
    assert four <= max(1, len(CELLS) // 4)


@pytest.mark.parametrize("name", ALL_CELLS)
def test_cell_resolves_to_files_and_reports_what_the_contract_asks(name):
    cell = manifest.Cell(WITH_PENDING, name)
    assert os.path.exists(_bench("loops", cell.traffic["loop"] + ".py"))
    e2e = [m["name"] for m in cell.end_to_end]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell.per_layer, "a cell reports at least one per-layer metric"
    for metric in cell.per_layer:
        assert metric["moves"] in e2e
        assert os.path.exists(_bench("layer_metrics",
                                     metric["name"] + ".py"))


def test_metrics_are_well_formed():
    e2e = {m["name"]: m for m in MANIFEST["end_to_end"]}
    assert 1 <= len(e2e) <= 16 and len(MANIFEST["per_layer"]) <= 128
    assert e2e["setup_s"]["bound"] <= 0.1 and "workloads" not in e2e["setup_s"]
    for m in MANIFEST["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
        assert m["better"] in ("lower", "higher")
    for m in MANIFEST["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["source"] in SOURCES and m["moves"] in e2e
        assert m["better"] in ("lower", "higher")
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
        for cell in m.get("workloads", []):
            assert cell in CELLS
    for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]:
        assert not set(m.get("workloads", CELLS)) - set(CELLS)


def test_layers_are_plain_names_that_perf_md_lists():
    """The driver refuses a ``layer`` with a space in it (PR 22's first
    check did); the pending cells are held to it before they are moved."""
    with open(os.path.join(ROOT, "PERF.md"), encoding="utf-8") as f:
        text = f.read()
    layers = text[text.index("## 3. Layers"):text.index("## 4. Cells")]
    for m in WITH_PENDING["per_layer"]:
        assert LAYER.match(m["layer"]), (m["name"], m["layer"])
        assert re.search(r"(?<![A-Za-z0-9_.\-])" + re.escape(m["layer"])
                         + r"(?![A-Za-z0-9_\-])", layers), m["layer"]


def test_pending_cells_are_laid_over_the_manifest_and_run_py_skips_them():
    assert set(CELLS) <= set(ALL_CELLS)
    for name in set(ALL_CELLS) - set(CELLS):
        with pytest.raises(manifest.ManifestError):
            manifest.Cell(MANIFEST, name)
        cell = manifest.Cell(WITH_PENDING, name)
        assert cell.per_layer and len(cell.end_to_end) >= 2
    names = [m["name"] for key in ("end_to_end", "per_layer")
             for m in WITH_PENDING[key]]
    assert len(names) == len(set(names))
    with open(_bench("run.py"), encoding="utf-8") as f:
        assert "pending" not in f.read()


def test_every_reader_file_is_listed_and_has_a_read():
    listed = {m["name"] for m in WITH_PENDING["per_layer"]}
    for fname in sorted(os.listdir(_bench("layer_metrics"))):
        if not fname.endswith(".py"):
            continue
        assert fname[:-3] in listed, \
            f"{fname} is in neither BENCHMARK.json nor benchmarks/pending/"
        mod = manifest.module("layer_metrics", fname[:-3])
        assert callable(mod.read) and mod.__doc__


def test_unknown_names_are_refused():
    with pytest.raises(manifest.ManifestError):
        manifest.Cell(MANIFEST, "no_such_cell")
    with pytest.raises(manifest.ManifestError):
        manifest.module("loops", "../run")
    with pytest.raises(manifest.ManifestError):
        manifest.module("loops", "no_such_loop")


def test_a_rehearsal_preset_exists_for_every_cell_and_run_py_ignores_it():
    for name in ALL_CELLS:
        assert os.path.exists(_bench("rehearsal", name + ".json"))
    with open(_bench("run.py"), encoding="utf-8") as f:
        assert "rehearsal" not in f.read()
