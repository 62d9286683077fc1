"""The benchmark's own layout (bench/layout.py) against the program's: the
same buckets on every pair at the mixes' threshold and where nothing is
reformed, a count of every pair a faulty layout drops or adds, and the
reformation rule on a graph small enough to count by hand."""

import json

import numpy as np
import pytest

from _bench_path import BENCH

import cell
import graphs
import layout

NODES = 300


def _cell(config="graphormer_slim", traffic="arxivstat-il8"):
    c = json.loads((BENCH / "configs" / f"{config}.json").read_text())
    t = json.loads((BENCH / "traffic" / f"{traffic}.json").read_text())
    return dict(c, nodes=NODES), t


def _program(c, t, g, rung):
    from repro.configs import get_config
    from repro.core.graph import Graph
    from repro.tasks import NodeTask

    task = NodeTask(Graph(*g), get_config(c["arch"]).replace(**c["model"]),
                    bq=c["block"]["bq"], bk=c["block"]["bk"])
    task.tuner.load_state_dict(dict(task.tuner.state_dict(), pos=rung))
    lay = task.layout
    return task.prep.perm, layout.program_dense(
        lay.block_idx, lay.buckets, lay.seq_len, lay.bq, lay.bk)


@pytest.mark.parametrize("config", ["graphormer_slim", "gt"])
@pytest.mark.parametrize("rung,over", [(3, 5), (1, 1), (0, 0)])
def test_own_layout_is_the_programs(config, rung, over):
    # rung 3 (the mix's 5 x beta_G) reforms the one cluster into fill
    # tiles; rungs 1 (beta_G) and 0 keep every pair of the pattern exact
    c, t = _cell(config)
    t = dict(t, beta_thre_over_beta_g=over)
    g = graphs.make_graph(NODES, t, 2**31 + 41)
    perm, prog = _program(c, t, g, rung)
    own = cell.own_layout(c, t, g, perm)
    assert cell.layout_mismatches(own, prog) == 0
    assert (own[np.arange(NODES + 1), np.arange(NODES + 1)] == 0).all()
    # only the mix's threshold lies above the one cluster's share
    assert bool((own == 2).any()) is (rung == 3)
    assert bool((own == 1).any()) is (rung != 3)


def test_a_faulty_program_layout_is_counted():
    c, t = _cell()
    g = graphs.make_graph(NODES, t, 2**31 + 43)
    perm, prog = _program(c, t, g, 3)
    own = cell.own_layout(c, t, g, perm)
    bad = prog.copy()
    fill = np.argwhere(bad == 2)
    bad[tuple(fill[len(fill) // 2])] = -1        # a fill pair dropped
    free = np.argwhere(bad == -1)
    bad[tuple(free[len(free) // 3])] = 2         # a pair added
    bad[5, 5] = 1                                # a self pair mislabelled
    assert cell.layout_mismatches(own, bad) == 3
    assert cell.layout_mismatches(own, bad[:-32, :-32]) == own.size


def test_reformation_keeps_the_densest_tiles():
    # 12 nodes, no global token: pattern = 12 self loops, 22 chain pairs
    # and edges 0-9, 0-10, 0-11, 1-9 both ways, 42 pairs in one cluster of
    # side 16; tile 4, so ceil(42 / 16) = 3 tiles are kept
    src = np.array([0, 9, 0, 10, 0, 11, 1, 9])
    dst = np.array([9, 0, 10, 0, 11, 0, 9, 1])
    g = (12, src, dst)
    out = layout.admitted(g, np.arange(12), 0, 16, 1.0, 1, 4, 16)
    # the three diagonal tiles hold 10 pairs each (4 self, 6 chain), the
    # tiles of the edges 4 each: the diagonal ones are kept
    assert sorted(map(tuple, np.argwhere(out == 2) // 4)) == sorted(
        [(i, i) for i in range(3)] * 12)
    assert (np.diag(out)[:12] == 0).all() and (out[:, 12:] == -1).all()
    assert (out[12:] == -1).all()
    assert (out == 1).sum() == 0                  # all pairs reformed
    kept = layout.admitted(g, np.arange(12), 0, 16, 0.0, 1, 4, 16)
    assert (kept == 1).sum() == 22 + 8 and (kept == 2).sum() == 0
