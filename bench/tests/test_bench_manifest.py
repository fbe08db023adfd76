"""The benchmark as data: BENCHMARK.json, its files, names and units."""
import json
import os
import re

import pytest

import benchtest
from bench import manifest as mf

DOC = mf.Manifest().doc
CELLS = [w["name"] for w in DOC["workloads"]]


def test_top_level_keys_and_command():
    assert set(DOC) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert DOC["paths"] == ["bench"]
    assert 1 <= DOC["run_seconds"] <= 51
    assert len(DOC["command"]) <= 32
    for word in DOC["command"]:
        assert not word.startswith("/") and ".." not in word
        if "/" in word:
            assert word.split("/")[0] in DOC["paths"]


def test_names_and_units_use_allowed_characters():
    assert mf.check_names(DOC) == []
    for key in ("end_to_end", "per_layer"):
        for m in DOC[key]:
            assert m["better"] in ("lower", "higher")
            assert m["source"] in ("device_trace", "program_span",
                                   "program_counter", "host_clock")
    for m in DOC["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_to_its_files(cell):
    man = mf.Manifest()
    c = man.cell(cell)
    cfg = man.config(c.config)
    assert os.path.exists(man.path("configs", c.config, ".json"))
    assert man.traffic(c.traffic)["loop"] in ("open", "backlog")
    assert os.path.exists(man.path("models", cfg["reference"], ".py"))
    assert "max_logit_gap" in man.limits(c.config)
    if man.traffic(c.traffic)["loop"] == "open":
        assert man.cell_params(cell)["rate_rps"] > 0
    for m in man.end_to_end(cell) + man.per_layer(cell):
        assert callable(man.metric_reader(m["name"]))


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_reports_setup_another_e2e_and_a_layer_metric(cell):
    man = mf.Manifest()
    e2e = {m["name"] for m in man.end_to_end(cell)}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert man.per_layer(cell)


def test_moves_targets_are_reported_where_listed():
    man = mf.Manifest()
    e2e = {m["name"] for m in DOC["end_to_end"]}
    layers = {m["layer"] for m in DOC["per_layer"]}
    for m in DOC["per_layer"]:
        assert m["moves"] in e2e
        for cell in m.get("workloads", CELLS):
            assert m["moves"] in {x["name"] for x in man.end_to_end(cell)}
    assert all("\n" not in lay for lay in layers)


def test_configs_are_files_under_paths_and_used():
    used = {w["config"] for w in DOC["workloads"]}
    files = set()
    for c in DOC["configs"]:
        assert c["file"].startswith("bench/configs/")
        assert c["file"] not in files
        files.add(c["file"])
        with open(os.path.join(benchtest.ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"]
        assert c["name"] in used


def test_roofline_names_and_units():
    for m in DOC["per_layer"]:
        if "roofline" in m["name"] or "mfu" in m["name"]:
            assert m["unit"] == "%"
        if "roofline" in m["name"]:
            assert m["name"].endswith("_roofline")


def test_a_new_cell_config_mix_and_metric_resolve_by_name(tmp_path):
    """Adding files and entries is all a later change needs."""
    bench = tmp_path / "bench"
    for sub in ("configs", "traffic", "cells", "metrics", "limits"):
        (bench / sub).mkdir(parents=True)
    (bench / "configs" / "new-model.json").write_text(
        json.dumps({"reference": "qwen_dense"}))
    (bench / "traffic" / "new-mix.json").write_text(
        json.dumps({"loop": "open"}))
    (bench / "cells" / "new-model.new-mix.json").write_text(
        json.dumps({"rate_rps": 2.5}))
    (bench / "limits" / "new-model.json").write_text(
        json.dumps({"max_logit_gap": 0.5}))
    (bench / "metrics" / "new.metric_ms.py").write_text(
        "def read(run):\n    return 42.0\n")
    doc = json.loads(json.dumps(DOC))
    doc["workloads"].append({"name": "new-model.new-mix",
                             "config": "new-model", "traffic": "new-mix",
                             "chips": 1, "why": "x"})
    doc["per_layer"].append({"name": "new.metric_ms", "unit": "ms",
                             "better": "lower", "source": "program_span",
                             "layer": "x", "moves": "tpot_p90_ms",
                             "workloads": ["new-model.new-mix"]})
    man = mf.Manifest(root=str(tmp_path), bench=str(bench), doc=doc)
    c = man.cell("new-model.new-mix")
    assert man.config(c.config)["reference"] == "qwen_dense"
    assert man.traffic(c.traffic)["loop"] == "open"
    assert man.cell_params(c.name) == {"rate_rps": 2.5}
    assert man.limits(c.config)["max_logit_gap"] == 0.5
    assert [m["name"] for m in man.per_layer(c.name)] == ["new.metric_ms"]
    assert man.metric_reader("new.metric_ms")(None) == 42.0


def test_file_names_come_from_names():
    for root, _, files in os.walk(os.path.join(benchtest.ROOT, "bench")):
        for f in files:
            if "__pycache__" in root:
                continue
            assert re.match(r"^[A-Za-z0-9_.\-]+$", f), f
