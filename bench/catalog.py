"""Finds a cell's parts by name: ``BENCHMARK.json`` names the cell, its
configuration and its traffic mix; each of those is a file of its own
(``configs/<config>.json``, ``mixes/<traffic>.json``), and each per-layer
metric is a reader of its own (``metrics/<metric>.py``).  A later cell,
mix, configuration or metric is a new file and a new entry, never an edit.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def cell(name: str, root: Path = ROOT) -> dict:
    """The cell's entry with its configuration entry and mix loaded:
    ``{"workload": ..., "config_entry": ..., "config": {...}, "mix": {...},
    "end_to_end": [...], "per_layer": [...]}`` — the metric lists hold only
    the metrics this cell reports."""
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json (have {sorted(cells)})")
    w = cells[name]
    conf_entry = next(c for c in bench["configs"] if c["name"] == w["config"])
    conf = json.loads((root / conf_entry["file"]).read_text())
    mix = load_mix(w["traffic"], root)

    def mine(metrics):
        return [m for m in metrics if name in m.get("workloads", [name])]

    return {"workload": w, "config_entry": conf_entry, "config": conf, "mix": mix,
            "end_to_end": mine(bench["end_to_end"]), "per_layer": mine(bench["per_layer"])}


def load_mix(name: str, root: Path = ROOT) -> dict:
    return json.loads((root / "bench" / "mixes" / f"{name}.json").read_text())


def load_limits(workload: str, root: Path = ROOT) -> dict:
    """The cell's correctness limits (``limits/<workload>.json``)."""
    return json.loads((root / "bench" / "limits" / f"{workload}.json").read_text())


def metric_reader(name: str, root: Path = ROOT):
    """The ``read(ctx)`` function of ``metrics/<name>.py``.  A metric name
    may hold dots (``grouped_mlp_ms.decode``); the file is named as is."""
    path = root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def expand_layers(conf: dict) -> list:
    """The configuration's layers in order, one dict each, from its
    ``segments`` (pattern x repeats) with the file's defaults filled in."""
    layers = []
    for seg in conf["segments"]:
        for _ in range(seg["repeats"]):
            for ls in seg["pattern"]:
                d = {"ffn": ls["ffn"], "act": conf["act"], "d_ff": conf["intermediate_size"],
                     "experts": 0, "residual": False, "top_k": 1}
                d.update(ls)
                layers.append(d)
    return layers
