"""``BENCHMARK.json`` and the files the harness finds by name.

A cell names a configuration and a traffic mix; everything else belongs
to one of them and sits in a file of its own:

    bench/configs/<config>.json   sizes, quantization, engine sizes, source
    bench/models/<model>.py       weights from the seed and the reference
    bench/traffic/<mix>.json      loop, arrivals, lengths, sampling
    bench/cells/<cell>.json       the cell's own numbers (its fixed rate)
    bench/metrics/<metric>.py     one reader per per-layer metric
    bench/limits/<config>.json    the limits of the correctness check
    bench/peaks.json              chip peaks keyed by device kind

Adding a cell, configuration, mix or metric adds files and entries;
nothing here changes.
"""
from __future__ import annotations

import importlib.util
import json
import os
import re
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    name: str
    config: str
    traffic: str
    chips: int


class Manifest:
    """The benchmark as data: ``BENCHMARK.json`` plus the bench files."""

    def __init__(self, root: str = ROOT, bench: str = BENCH,
                 doc: Optional[dict] = None):
        self.root = root
        self.bench = bench
        self.doc = doc if doc is not None else _json(
            os.path.join(root, "BENCHMARK.json"))

    # ------------------------------------------------------------ lookup
    def cell(self, name: str) -> Cell:
        for w in self.doc["workloads"]:
            if w["name"] == name:
                return Cell(w["name"], w["config"], w["traffic"],
                            int(w["chips"]))
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def path(self, kind: str, name: str, ext: str) -> str:
        return os.path.join(self.bench, kind, name + ext)

    def config(self, name: str) -> dict:
        return _json(self.path("configs", name, ".json"))

    def traffic(self, name: str) -> dict:
        return _json(self.path("traffic", name, ".json"))

    def cell_params(self, name: str) -> dict:
        p = self.path("cells", name, ".json")
        return _json(p) if os.path.exists(p) else {}

    def limits(self, config: str) -> dict:
        return _json(self.path("limits", config, ".json"))

    def peaks(self) -> dict:
        return _json(os.path.join(self.bench, "peaks.json"))

    def kernels(self) -> Dict[str, List[str]]:
        """Kernel label -> the names the chip prints for it in a trace."""
        return _json(os.path.join(self.bench, "kernels.json"))

    def model(self, cfg: dict):
        name = cfg["reference"]
        return _module(self.path("models", name, ".py"), f"bench_model_{name}")

    def metric_reader(self, name: str) -> Callable:
        mod = _module(self.path("metrics", name, ".py"),
                      "bench_metric_" + re.sub(r"\W", "_", name))
        return mod.read

    # ------------------------------------------------------------ metrics
    def end_to_end(self, cell: str) -> List[dict]:
        return [m for m in self.doc["end_to_end"]
                if cell in m.get("workloads", [cell])]

    def per_layer(self, cell: str) -> List[dict]:
        """The per-layer metrics this cell reports: those listing it, and
        those without a list whose end-to-end metric the cell reports."""
        e2e = {m["name"] for m in self.end_to_end(cell)}
        return [m for m in self.doc["per_layer"]
                if (cell in m["workloads"] if "workloads" in m
                    else m["moves"] in e2e)]


def check_names(doc: dict) -> List[str]:
    """Every name and unit against the allowed characters; returns the
    problems found."""
    bad = []
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        seen = set()
        for e in doc.get(key, []):
            n = e.get("name", "")
            if not NAME.match(n):
                bad.append(f"{key}: bad name {n!r}")
            if n in seen:
                bad.append(f"{key}: duplicate {n!r}")
            seen.add(n)
            if "unit" in e and not UNIT.match(e["unit"]):
                bad.append(f"{key}: bad unit {e['unit']!r}")
            for k in ("config", "traffic"):
                if k in e and not NAME.match(e[k]):
                    bad.append(f"{key}: bad {k} {e[k]!r}")
            for k in e.get("reduced", []):
                if not NAME.match(k):
                    bad.append(f"{key}: bad reduced key {k!r}")
    return bad

