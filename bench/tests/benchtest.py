"""A miniature of the benchmark for the CPU tests: the real harness,
metric readers and reference, with a tiny same-family configuration and
tiny traffic mixes kept in ``fixtures``."""
from __future__ import annotations

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import manifest as mf  # noqa: E402

FIX = os.path.join(HERE, "fixtures")
# limit of the tiny configuration's check (readings in test_bench_check)
TINY_LIMIT = 0.02


def fixture(name: str) -> dict:
    with open(os.path.join(FIX, name)) as f:
        return json.load(f)


OPEN_LOOP_METRICS = [
    ("end_to_end", {"name": "ttft_p90_ms", "unit": "ms", "better": "lower",
                    "bound": 0.25, "source": "host_clock"}),
    ("per_layer", {"name": "sched.queue_wait_p90_ms", "unit": "ms",
                   "better": "lower", "source": "program_counter",
                   "layer": "host scheduler", "moves": "ttft_p90_ms"})]


class TinyManifest(mf.Manifest):
    """The real manifest plus the tiny cells ``tiny.chat`` and
    ``tiny.offline``."""

    def __init__(self, limit: float = TINY_LIMIT):
        super().__init__()
        self.doc = json.loads(json.dumps(self.doc))
        self.doc["workloads"] += [
            {"name": "tiny.chat", "config": "tiny", "traffic": "tiny-chat",
             "chips": 1, "why": "test"},
            {"name": "tiny.offline", "config": "tiny",
             "traffic": "tiny-offline", "chips": 1, "why": "test"}]
        for m in self.doc["end_to_end"]:
            cells = m.get("workloads", [])
            if any(c.endswith(".offline-batch") for c in cells):
                cells.append("tiny.offline")
        for m in self.doc["per_layer"]:
            m["workloads"] = m.get("workloads", []) + ["tiny.chat",
                                                      "tiny.offline"]
        # an open loop's metrics, whether or not a cell of the benchmark
        # reports them yet
        names = {m["name"] for k in ("end_to_end", "per_layer")
                 for m in self.doc[k]}
        for key, m in OPEN_LOOP_METRICS:
            if m["name"] not in names:
                self.doc[key].append(dict(m, workloads=["tiny.chat"]))
        self.limit = limit

    def config(self, name):
        return fixture("tiny.json") if name == "tiny" else super().config(name)

    def traffic(self, name):
        if name.startswith("tiny-"):
            return fixture(name + ".json")
        return super().traffic(name)

    def cell_params(self, name):
        return {"rate_rps": 12.0} if name == "tiny.chat" \
            else super().cell_params(name)

    def limits(self, config):
        return {"max_logit_gap": self.limit} if config == "tiny" \
            else super().limits(config)


def run_tiny(cell: str, seed: int = 3, seconds: float = 2.0,
             limit: float = TINY_LIMIT, **kw):
    """One run of a tiny cell on the CPU with the chip check skipped and
    no persistent compilation cache; returns (result line, the run's
    ``bench:`` summary)."""
    from bench import harness
    logs = []
    saved = harness.enable_compile_cache
    harness.enable_compile_cache = lambda root: "off"
    try:
        out = harness.run(cell, seed, seconds, False,
                          t_start=harness.clock(), man=TinyManifest(limit),
                          require_chip=False, log=logs.append, **kw)
    finally:
        harness.enable_compile_cache = saved
    info = json.loads(logs[0][len("bench: "):])
    return out, info
