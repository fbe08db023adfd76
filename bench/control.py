#!/usr/bin/env python3
"""Readings that the limits of the correctness check are set from.

    python3 bench/control.py --workload <cell> --seeds 11,12,13 --seconds 15

Runs the cell once per seed in this one process (set-up is paid once for
the process, not per seed), with a short window at the cell's own load,
and prints one JSON line per seed: the program's reading (the widest
gap by which a served greedy token's reference logit lies below the
reference's best) and the control's (the same gap for the top tokens of
the reference computed one precision below the configuration's, as the
configuration's ``control`` states: float8 matmul inputs, and float8 or
int4 K/V).  The control's tokens take the served tokens' place in the
run's own check, so ``correct`` is the control's verdict and has to read
false.  The benchmark's own runs never run the control; this script and
``bench/tests/test_bench_check.py`` do.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    args = ap.parse_args(argv)
    from bench import harness, manifest
    man = manifest.Manifest()
    cell = man.cell(args.workload)
    control = man.config(cell.config)["control"]
    for seed in (int(s) for s in args.seeds.split(",")):
        logs = []
        out = harness.run(args.workload, seed, args.seconds, False,
                          t_start=harness.clock(), man=man, control=control,
                          log=lambda line: logs.append(line))
        info = json.loads(logs[0][len("bench: "):])
        rows = info["rows"]
        print(json.dumps({
            "workload": args.workload, "seed": seed,
            "program": max(r["served"] for r in rows),
            "control": max(r["control"] for r in rows),
            "tokens": sum(r["tokens"] for r in rows),
            "per_sequence": rows, "correct": out["correct"],
            "device": out["device"]["kind"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
