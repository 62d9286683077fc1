"""The tool that reads a cell's limits (bench/readings.py) on one seed at a
size a test run can hold: one line per kind, every compared number in
it, and the planted faults and the control apart from the reference."""

import json

from _bench_path import BENCH  # noqa: F401

import cell
import correct
import readings

NODES = 200


def test_readings_one_seed(monkeypatch, tmp_path):
    load = cell.load_cell

    def small(name):
        spec = load(name)
        spec["config"]["nodes"] = NODES
        return spec

    monkeypatch.setattr(cell, "load_cell", small)
    out = tmp_path / "r.jsonl"
    assert readings.main(["--workload", "slim-arxivstat-il8",
                          "--seeds", str(2**31 + 61),
                          "--out", str(out)]) == 0
    lines = [json.loads(x) for x in out.read_text().splitlines()]
    got = {x["kind"]: x["numbers"] for x in lines}
    assert list(got) == list(readings.KINDS)
    for nums in got.values():
        assert set(nums) == set(correct.NUMBERS) - {"layout"}
    assert got["stale"]["update"] == 1.0
    for kind in ("control", "half_batch", "token"):
        assert max(got[kind].values()) > 1e-3, kind
    assert set(lines[0]["leaves"]) == {"gdiff.dense", "gdiff.sparse"}
