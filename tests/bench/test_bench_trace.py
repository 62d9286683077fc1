"""The reduction from a trace to device numbers: on a hand-made trace whose
busy, idle, kernel and exposed all-to-all times are known, and on one
sparse step recorded on a TPU v5 lite, checked against a plain sweep."""

import json

import pytest

from _bench_path import ROOT

import devtrace as tr

NS = 1e-9
FWD = "(bf16[8,64,128]{2,1,0:T(8,128)(2,1)}, f32[8,64,1]{2,1,0:T(8,128)})"
DQ = "(bf16[8,64,128]{2,1,0:T(8,128)(2,1)}, f32[1,8,2,1,3]{4,3,2,1,0})"
DKV = "(bf16[8,64,128]{2,1,0:T(8,128)(2,1)}, bf16[8,64,128]{2,1,0:T(8,128)})"
SPANS = [["bench:traced_window", 0, 1000],
         ["bench:step.sparse", 0, 560], ["bench:between_steps", 560, 60],
         ["bench:step.sparse", 620, 330], ["bench:between_steps", 940, 60]]
RECORDED = ROOT / "tests" / "bench" / "data" / "trace_gt_sparse_step.json"


def _dev(a2a_overlap: bool):
    f3 = ["%fusion.3", 450, 100, ""] if a2a_overlap else \
        ["%fusion.3", 500, 100, ""]
    return [["%fusion.1", 0, 100, ""],
            ["%while.2", 100, 200, ""],             # holds the kernel
            ["%cluster_attention.3", 120, 120, FWD],
            ["%fusion.2", 250, 50, ""],
            ["%all-to-all.1", 400, 100, ""], f3,
            ["%_cluster_bwd.7", 600, 150, DQ],
            ["%_cluster_bwd.8", 800, 100, DKV],
            ["%fusion.9", 1100, 50, ""]]            # after the window


def test_one_device_known_times():
    r = tr.reduce({"devices": {"0": _dev(True)}, "spans": SPANS})
    assert r["window_s"] == pytest.approx(1000 * NS)
    assert r["busy_s"] == pytest.approx(700 * NS)
    assert r["kernel_s"] == pytest.approx(
        {"fwd": 120 * NS, "dq": 150 * NS, "dkv": 100 * NS})
    assert r["kernel_calls"] == {"fwd": 1, "dq": 1, "dkv": 1}
    assert r["a2a_exposed_s"] == pytest.approx(50 * NS)
    gaps = dict(r["idle_gaps"])
    assert gaps["bench:step.sparse"] == pytest.approx(150 * NS)
    assert gaps["bench:between_steps"] == pytest.approx(150 * NS)
    own = dict(r["device_ops"])
    # the while's own time leaves out the kernel and the fusion inside it
    assert own["while"] == pytest.approx(30 * NS)
    assert own["cluster_attention"] == pytest.approx(120 * NS)
    assert own["_cluster_bwd.dq"] == pytest.approx(150 * NS)
    assert own["_cluster_bwd.dkv"] == pytest.approx(100 * NS)
    assert own["fusion"] == pytest.approx(250 * NS)
    assert sum(own.values()) == pytest.approx(r["busy_s"])
    assert r["a2a_seen"]


def test_devices_averaged_a2a_worst():
    r = tr.reduce({"devices": {"0": _dev(True), "1": _dev(False)},
                   "spans": SPANS})
    assert r["busy_s"] == pytest.approx(725 * NS)
    assert r["a2a_exposed_s"] == pytest.approx(100 * NS)
    assert r["devices"] == 2


def test_no_window_no_numbers():
    assert tr.reduce({"devices": {"0": _dev(True)},
                      "spans": SPANS[1:]}) == {}


@pytest.mark.parametrize("name,sig,kind", [
    ("%cluster_attention.16", FWD, "fwd"),
    ("%_cluster_bwd.4", DQ, "dq"),
    ("%_cluster_bwd.5", DKV, "dkv"),
    ("%_cluster_bwd.6", "bf16[2,6944,128]{2,1,0}", "dq"),
    ("%fusion.4", "", None),
    ("%custom-call.51", "bf16[4,1,6944,64]{2,3,1,0}", None)])
def test_kernel_names(name, sig, kind):
    assert tr.kernel_of(name, sig) == kind


def test_split_hlo_keeps_name_and_custom_call_result():
    text = ("%_cluster_bwd.3 = (bf16[8,2048,128]{2,1,0:T(8,128)(2,1)S(1)}, "
            "bf16[8,2048,128]{2,1,0:T(8,128)(2,1)S(1)}) custom-call(s32[4096]"
            "{0:T(1024)S(1)} %get-tuple-element.9), custom_call_target="
            "\"tpu_custom_call\"")
    name, sig = tr.split_hlo(text)
    assert name == "%_cluster_bwd.3" and tr.kernel_of(name, sig) == "dkv"
    assert tr.split_hlo("%fusion.2 = f32[] fusion(), kind=kLoop") == \
        ("%fusion.2", "")


def _sweep(evs, w0, w1):
    """Busy time by brute force: every elementary interval between event
    edges is busy if any event covers it."""
    edges = sorted({w0, w1} | {min(max(x, w0), w1) for _, s, d, _ in evs
                               for x in (s, s + d)})
    busy = 0.0
    for a, b in zip(edges, edges[1:]):
        if any(s <= a and b <= s + d for _, s, d, _ in evs):
            busy += b - a
    return busy


def test_recorded_sparse_step():
    rec = json.loads(RECORDED.read_text())
    r = tr.reduce(rec)
    w = rec["spans"][0]
    evs = rec["devices"]["0"]
    assert r["window_s"] == pytest.approx(w[2] * NS)
    assert r["busy_s"] == pytest.approx(_sweep(evs, w[1], w[1] + w[2]) * NS)
    # four layers: forward and its recomputation, one dQ and one dK/dV
    assert r["kernel_calls"] == {"fwd": 8, "dq": 4, "dkv": 4}
    for k, base in (("fwd", "%cluster_attention"), ("dq", "%_cluster_bwd"),
                    ("dkv", "%_cluster_bwd")):
        want = sum(d for n, s, d, sig in evs if n.startswith(base + ".")
                   and tr.kernel_of(n, sig) == k)
        assert r["kernel_s"][k] == pytest.approx(want * NS)
    idle = sum(v for _, v in r["idle_gaps"])
    assert idle == pytest.approx(r["window_s"] - r["busy_s"])
    assert not r["a2a_seen"] and r["a2a_exposed_s"] == 0.0
