"""Without a TPU, or without the program beside it, the benchmark command
fails and prints no result."""

import json
import os
import shutil
import subprocess
import sys

from _bench_path import ROOT

CMD = json.loads((ROOT / "BENCHMARK.json").read_text())["command"]
ARGS = ["--workload", "slim-arxivstat-il8", "--seed", "2147483659",
        "--seconds", "1", "--trace", "0"]


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    return subprocess.run([sys.executable, *CMD[1:], *ARGS], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def _no_result(p):
    assert p.returncode != 0
    assert not any(line.lstrip().startswith("{")
                   for line in p.stdout.splitlines())
    assert "nodes_per_s" not in p.stdout


def test_fails_without_a_tpu():
    p = _run(ROOT)
    _no_result(p)
    assert "TPU" in p.stderr


def test_fails_with_only_the_benchmark_files(tmp_path):
    man = json.loads((ROOT / "BENCHMARK.json").read_text())
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    for p in man["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    _no_result(_run(tmp_path))
