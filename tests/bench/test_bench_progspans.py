"""The program's spans in a benchmark run (bench/progspans.py): idle time
split by the innermost ``repro.*`` span on a hand-made trace, host time
per step and compile seconds from a hand-made recording, None on missing
input, and a whole recorded and traced run on the host."""

import time

import pytest

from _bench_path import BENCH  # noqa: F401

import progspans as ps
import spanrun

from repro.runtime.spans import Recording

NS = 1e-9
WINDOW = [["bench:traced_window", 0, 1000]]
# two steps; the device runs 40-420 and 560-880
DEVICE = {"0": [["%fusion.1", 40, 180, ""], ["%fusion.2", 220, 200, ""],
                ["%fusion.3", 560, 320, ""]]}
SPANS = [["repro.trainer.step", 0, 500],
         ["repro.task.batches", 0, 20],
         ["repro.trainer.dispatch", 20, 30],
         ["repro.trainer.wait", 60, 360],
         ["repro.trainer.rescue", 430, 50],
         ["repro.trainer.step", 500, 390],
         ["repro.trainer.dispatch", 510, 30],
         ["repro.trainer.wait", 545, 335],
         ["repro.ckpt.write", 895, 60]]     # writer thread, after the step


def test_idle_split_by_innermost_span():
    got = ps.idle_by_program_span(
        {"devices": DEVICE, "spans": WINDOW}, SPANS)
    # gaps: 0-40 (batches 0-20, dispatch 20-40), 420-560 (the step's own
    # time 420-430, rescue 430-480, the step 480-510, dispatch 510-540,
    # wait 545-560 and the step between), 880-1000 (the step to 890,
    # untracked, ckpt.write 895-955 on the writer thread)
    assert got["idle_s"] == pytest.approx({
        "repro.task.batches": 20 * NS,
        "repro.trainer.dispatch": 50 * NS,
        "repro.trainer.rescue": 50 * NS,
        "repro.trainer.step": 55 * NS,
        "repro.trainer.wait": 15 * NS,
        "repro.ckpt.write": 60 * NS,
        "untracked": 50 * NS})
    assert sum(got["idle_s"].values()) == pytest.approx(300 * NS)
    assert got["named_share"] == pytest.approx(100 * 195 / 300)


def test_idle_two_devices_averaged_and_untracked():
    dev = {"0": DEVICE["0"], "1": [["%fusion.1", 0, 1000, ""]]}
    got = ps.idle_by_program_span({"devices": dev, "spans": WINDOW},
                                  SPANS[:1])
    # device 0: 0-40 and 420-500 under the step, 500-560 and 880-1000
    # under no span; device 1 never idle
    assert got["idle_s"] == pytest.approx({"repro.trainer.step": 60 * NS,
                                           "untracked": 90 * NS})
    assert got["named_share"] == 0.0


def _rec():
    rec = Recording()
    rec.spans = [
        {"id": 1, "name": "repro.trainer.step", "start_ns": 0,
         "end_ns": 10_000_000, "parent": None, "thread": "m",
         "attrs": {"step": 3}},
        {"id": 2, "name": "repro.trainer.wait", "start_ns": 1_000_000,
         "end_ns": 9_000_000, "parent": 1, "thread": "m", "attrs": {}},
        {"id": 3, "name": "repro.trainer.step", "start_ns": 10_000_000,
         "end_ns": 14_000_000, "parent": None, "thread": "m",
         "attrs": {"step": 4}},
        {"id": 4, "name": "repro.trainer.wait", "start_ns": 11_000_000,
         "end_ns": 13_000_000, "parent": 3, "thread": "m", "attrs": {}}]
    return rec


def test_step_host_ms_and_compile_s():
    rec = _rec()
    assert ps.step_host_ms(rec, [3]) == pytest.approx(2.0)
    assert ps.step_host_ms(rec, [3, 4]) == pytest.approx(2.0)
    assert ps.step_host_ms(rec, [7]) is None
    by_step = {None: {"compile.trace_s": 1.0, "compile.xla_s": 2.0,
                      "compile.cache_read_s": 1.5},
               0: {"compile.lower_s": 0.5}, 9: {"compile.xla_s": 4.0}}
    # the cache read is inside the XLA compile event: not added twice
    assert ps.compile_s(by_step, 8) == pytest.approx(3.5)
    assert ps.setup_spans(rec, 4)["repro.trainer.step"]["count"] == 1
    assert ps.setup_named_s(rec, 4) == pytest.approx(0.010)


@pytest.mark.parametrize("call", [
    lambda: ps.idle_by_program_span({"devices": DEVICE, "spans": []}, SPANS),
    lambda: ps.idle_by_program_span({"devices": {}, "spans": WINDOW}, SPANS),
    lambda: ps.idle_by_program_span({"devices": DEVICE, "spans": WINDOW},
                                    []),
    lambda: ps.step_host_ms(None, [1]),
    lambda: ps.step_host_ms(Recording(), [1]),
    lambda: ps.compile_s(None, 8),
    lambda: ps.compile_s({}, 8),
    lambda: ps.setup_spans(None, 8),
    lambda: ps.setup_spans(Recording(), 8),
    lambda: ps.setup_named_s(None, 8),
    lambda: ps.setup_named_s(Recording(), 8)])
def test_missing_input_reads_none(call):
    assert call() is None


def test_recorded_traced_run_on_host():
    out = spanrun.run("gt-arxivstat-il8", 2**31 + 17, 0.0, True, True,
                      t_start=time.perf_counter(), require_tpu=False,
                      nodes=200)
    assert len(out["step_s"]) == len(out["wait_s"]) == 8
    assert all(0 < w <= s for w, s in zip(out["wait_s"], out["step_s"]))
    # both programs compile in set-up (steps 0 and 1), none after
    assert out["compiles_in_window"] == []
    assert {"0", "1"} <= set(out["compiles_by_step"])
    assert out["compile_s"] > 0
    assert out["step_host_ms"]["window"] > 0
    assert out["step_host_ms"]["traced"] > 0
    assert out["setup_spans"]["repro.task.prep"]["count"] == 1
    assert 0 < out["setup_named_s"] < out["setup_s"]
    assert out["span_table"]["repro.trainer.step"]["count"] == 24
