"""A whole benchmark run on the host (the chip check skipped, a small
graph), with the timed path broken underneath: ``correct`` comes out
false for every fault a one-chip cell can have, and true without one."""

import time

import pytest

from _bench_path import BENCH  # noqa: F401
from _bench_faults import plant

import cell

NODES = 200


@pytest.mark.parametrize("workload", ["slim-arxivstat-il8", "gt-arxivstat-il8"])
@pytest.mark.parametrize("fault", [None, "stale", "half_batch", "token"])
def test_fault_turns_correct_false(monkeypatch, workload, fault):
    plant(monkeypatch, fault)
    res, checks, _ = cell.run(workload, 2**31 + 17, 0.0, False,
                              t_start=time.perf_counter(),
                              require_tpu=False, nodes=NODES)
    assert res["attempted"] == 8 and list(res) == [
        "correct", "attempted", "failed", "metrics", "device", "checks"]
    assert res["correct"] is (fault is None), checks
