"""BENCHMARK.json -> one cell, and the files a cell is made of.

A cell is one entry of ``workloads``. Everything that belongs to one
configuration, one traffic mix, one loop or one per-layer metric sits in
a file of its own, found here by name:

    configs[].file                     the configuration as it is run
    benchmarks/traffic/<traffic>.json  the job or traffic mix; names its loop
    benchmarks/loops/<loop>.py         how the system is driven
    benchmarks/models/<model>.py       how the configuration is built
    benchmarks/references/<ref>.py     its plain float32 reference
    benchmarks/layer_metrics/<m>.py    one per-layer metric's reader

so a later PR adds cells by adding files and entries, never by editing.
"""
from __future__ import annotations

import importlib.util
import json
import os
import re

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
_PLAIN = re.compile(r"^[A-Za-z0-9][A-Za-z0-9_.\-]{0,63}$")


class ManifestError(ValueError):
    pass


def _read_json(path):
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def load(root=ROOT, pending=False):
    """BENCHMARK.json. With ``pending`` (the rehearsal, the sweep and the
    compile check ask for it; ``run.py`` never does) the cells under
    ``benchmarks/pending/`` are laid over it: a pending file has
    BENCHMARK.json's four lists for a cell that is written and has run on
    the chip but is not admitted yet, and a benchmark PR admits it by
    moving its entries across."""
    manifest = _read_json(os.path.join(root, "BENCHMARK.json"))
    folder = os.path.join(root, "benchmarks", "pending")
    if not pending or not os.path.isdir(folder):
        return manifest
    for fname in sorted(os.listdir(folder)):
        extra = _read_json(os.path.join(folder, fname))
        for key in ("configs", "workloads", "end_to_end", "per_layer"):
            have = {e["name"]: e for e in manifest[key]}
            for entry in extra.get(key, []):
                if entry["name"] not in have:
                    manifest[key].append(entry)
                elif "workloads" in have[entry["name"]]:
                    have[entry["name"]]["workloads"] += entry["workloads"]
    return manifest


def module(kind, name):
    """Import ``benchmarks/<kind>/<name>.py`` by path (the directories are
    data, not packages: a new file needs no ``__init__`` edit)."""
    if not _PLAIN.match(name):
        raise ManifestError(f"{kind} name {name!r} is not a plain name")
    path = os.path.join(BENCH_DIR, kind, name + ".py")
    if not os.path.exists(path):
        raise ManifestError(f"no {kind} file {path}")
    spec = importlib.util.spec_from_file_location(
        f"benchmarks_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _applies(metric, cell_name):
    return "workloads" not in metric or cell_name in metric["workloads"]


class Cell:
    """One entry of ``workloads`` with its files read."""

    def __init__(self, manifest, name, root=ROOT):
        entry = next((w for w in manifest["workloads"]
                      if w["name"] == name), None)
        if entry is None:
            known = ", ".join(w["name"] for w in manifest["workloads"])
            raise ManifestError(f"no workload {name!r}; BENCHMARK.json has: "
                                f"{known}")
        cfg = next((c for c in manifest["configs"]
                    if c["name"] == entry["config"]), None)
        if cfg is None:
            raise ManifestError(f"workload {name!r} names configuration "
                                f"{entry['config']!r}, which is not listed")
        if not _PLAIN.match(entry["traffic"]):
            raise ManifestError(f"traffic name {entry['traffic']!r} is not "
                                "a plain name")
        self.name = name
        self.chips = int(entry["chips"])
        self.config = _read_json(os.path.join(root, cfg["file"]))
        self.traffic = _read_json(os.path.join(
            root, "benchmarks", "traffic", entry["traffic"] + ".json"))
        # for what a model file has to say about it by name
        self.traffic.setdefault("name", entry["traffic"])
        self.end_to_end = [m for m in manifest["end_to_end"]
                           if _applies(m, name)]
        self.per_layer = [m for m in manifest["per_layer"]
                          if _applies(m, name)]
        e2e = {m["name"] for m in self.end_to_end}
        stray = [m["name"] for m in self.per_layer if m["moves"] not in e2e]
        if stray:
            raise ManifestError(
                f"per-layer metric(s) {stray} apply to {name!r} but the "
                "end-to-end metric they move is not reported there")

    def rehearse(self, root=ROOT):
        """Lay ``benchmarks/rehearsal/<cell>.json``, the tiny CPU preset,
        over the cell's sizes: never called by a chip run."""
        preset = _read_json(os.path.join(root, "benchmarks", "rehearsal",
                                         self.name + ".json"))
        self.config = {**self.config, **preset.get("config", {})}
        self.traffic = {**self.traffic, **preset.get("traffic", {})}
        return self
