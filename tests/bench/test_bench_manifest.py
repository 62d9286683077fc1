"""BENCHMARK.json keeps to the benchmark's contract, and every name in it
resolves to a file under bench/."""

import json
import re

import pytest

from _bench_path import BENCH, ROOT

import correct

MAN_PATH = ROOT / "BENCHMARK.json"
MAN = json.loads(MAN_PATH.read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
CELLS = {w["name"]: w for w in MAN["workloads"]}
E2E = {m["name"]: m for m in MAN["end_to_end"]}


def _line(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def test_top_level_shape():
    assert set(MAN) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert MAN_PATH.stat().st_size <= 64 * 1024
    assert 1 <= len(MAN["paths"]) <= 16
    for p in MAN["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert (ROOT / p).is_dir()
    cmd = MAN["command"]
    assert 1 <= len(cmd) <= 32 and all(_line(w) for w in cmd)
    for word in cmd[1:]:
        assert not word.startswith("/") and ".." not in word
        assert any(word.startswith(p + "/") for p in MAN["paths"])


def test_names_units_and_keys():
    names = []
    for c in MAN["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and _line(c["why"])
        assert _line(c["source"]) and len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
        names.append(c["name"])
    for w in MAN["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and _line(w["why"])
        names.append(w["name"])
    for m in MAN["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in MAN["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert _line(m["layer"])
    for m in MAN["end_to_end"] + MAN["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        names.append(m["name"])
    assert len(names) == len(set(names))
    assert "setup_s" in E2E and E2E["setup_s"]["bound"] <= 0.25


def test_cells_configs_and_chips():
    used = {w["config"] for w in MAN["workloads"]}
    assert used == {c["name"] for c in MAN["configs"]}
    pairs = [(w["config"], w["traffic"]) for w in MAN["workloads"]]
    assert len(pairs) == len(set(pairs)) and 1 <= len(pairs) <= 24
    four = sum(1 for w in MAN["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(pairs) // 2)


def test_every_name_has_its_file():
    files = [c["file"] for c in MAN["configs"]]
    assert len(files) == len(set(files))
    for c in MAN["configs"]:
        f = ROOT / c["file"]
        assert any(c["file"].startswith(p + "/") for p in MAN["paths"])
        body = json.loads(f.read_text())
        for k in c["reduced"]:
            assert k in body and k in body["published"]
            assert body[k] != body["published"][k]
    for w in MAN["workloads"]:
        assert (BENCH / "traffic" / f"{w['traffic']}.json").is_file()
        lim = json.loads((BENCH / "limits" / f"{w['name']}.json")
                         .read_text())
        assert set(lim) <= set(correct.NUMBERS) and "layout" in lim
        assert lim["layout"] == 0
        assert all(isinstance(v, (int, float)) for v in lim.values())
    for m in MAN["per_layer"]:
        assert (BENCH / "metrics" / f"{m['name']}.py").is_file()


def test_moves_names_an_end_to_end_metric_of_the_same_cells():
    def reports(metric, cell):
        return cell in metric.get("workloads", CELLS)

    for m in MAN["per_layer"]:
        assert m["moves"] in E2E
        for cell in m.get("workloads", CELLS):
            assert cell in CELLS
            assert reports(E2E[m["moves"]], cell)
    for cell in CELLS:
        rep = [e for e in E2E.values() if reports(e, cell)]
        assert any(e["name"] == "setup_s" for e in rep) and len(rep) >= 2
        assert any(reports(m, cell) for m in MAN["per_layer"])


def test_run_seconds_fits_a_full_check():
    rs = MAN["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


@pytest.mark.parametrize("layer", sorted({m["layer"]
                                          for m in MAN["per_layer"]}))
def test_layers_named_in_perf_md(layer):
    assert f"| {layer} |" in (ROOT / "PERF.md").read_text()
