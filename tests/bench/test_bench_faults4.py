"""A four-chip cell on four virtual CPU devices: the harness's sharded path
runs and is correct, and each fault such a cell can have (the all-to-all
between chips left out among them) turns ``correct`` false. All runs
share one process, started once for the module."""

import json
import os
import subprocess
import sys

import pytest

from _bench_path import ROOT

HERE = ROOT / "tests" / "bench"
FAULTS = ["none", "exchange", "stale", "half_batch", "token"]


@pytest.fixture(scope="module")
def runs():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, str(HERE / "_bench_four.py"),
                        *FAULTS], cwd=HERE, env=env, capture_output=True,
                       text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    out = [json.loads(line) for line in p.stdout.splitlines()
           if line.startswith("{")]
    return {o["fault"]: o["result"] for o in out}


@pytest.mark.parametrize("fault", FAULTS)
def test_four_device_run(runs, fault):
    res = runs[fault]
    assert res["device"]["count"] == 4
    assert res["correct"] is (fault == "none"), res["checks"]
