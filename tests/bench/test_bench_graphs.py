"""The traffic mixes' graph generators: deterministic by seed, and on
their stated statistics."""

import json

import numpy as np
import pytest

from _bench_path import BENCH

import graphs

MIXES = ("arxivstat-il8",)


def _mix(name):
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


@pytest.mark.parametrize("mix", MIXES)
def test_same_seed_same_graph(mix):
    t = _mix(mix)
    a = graphs.make_graph(600, t, 2**31 + 5)
    b = graphs.make_graph(600, t, 2**31 + 5)
    c = graphs.make_graph(600, t, 2**31 + 6)
    for x, y in zip(a[1:], b[1:]):
        np.testing.assert_array_equal(x, y)
    assert not np.array_equal(a[3], c[3])


@pytest.mark.parametrize("mix", MIXES)
def test_edges_symmetric_no_self_loops(mix):
    n, src, dst, feat, labels = graphs.make_graph(800, _mix(mix), 3)
    assert not np.any(src == dst)
    fwd = set(zip(src.tolist(), dst.tolist()))
    assert fwd == set(zip(dst.tolist(), src.tolist()))
    assert len(fwd) == src.size                      # deduplicated
    assert feat.shape == (n, 128) and labels.min() >= 0


@pytest.mark.parametrize("seed", [1, 2**31 + 3])
def test_arxivstat_hits_published_statistics(seed):
    t = _mix("arxivstat-il8")
    n, src, dst, feat, labels = graphs.make_graph(6912, t, seed)
    assert src.size / n == pytest.approx(t["mean_degree"], rel=1e-3)
    assert graphs.edge_homophily(src, dst, labels) == pytest.approx(
        0.65, abs=0.015)
    assert len(np.unique(labels)) == t["communities"]
    deg = np.bincount(src, minlength=n)
    assert deg.max() > 20 * t["mean_degree"]         # heavy tail
