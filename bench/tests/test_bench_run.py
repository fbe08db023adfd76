"""The command off the chip: it fails, names the platform and prints no
result; and it fails where only the benchmark's own files are present."""
import json
import os
import shutil
import subprocess
import sys

import benchtest

ENV = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
CMD = ["--workload", "qwen2-1.5b-w4a16.offline-batch", "--seed",
       str(2 ** 31 + 1), "--seconds", "1", "--trace", "0"]


def _run(root):
    return subprocess.run([sys.executable, os.path.join(root, "bench",
                                                        "run.py"), *CMD],
                          cwd=root, env=ENV, capture_output=True, text=True,
                          timeout=240)


def _no_result(stdout):
    for line in stdout.splitlines():
        try:
            assert "correct" not in json.loads(line)
        except ValueError:
            pass


def test_fails_off_a_tpu_naming_the_platform():
    p = _run(benchtest.ROOT)
    assert p.returncode == 2
    assert "platform 'cpu'" in p.stderr
    _no_result(p.stdout)


def test_fails_with_only_the_benchmark_files(tmp_path):
    shutil.copy(os.path.join(benchtest.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(benchtest.ROOT, "bench"),
                    tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(str(tmp_path))
    assert p.returncode != 0
    _no_result(p.stdout)
