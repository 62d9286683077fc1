"""The control: the plain reference with its matmul operands rounded to
float8 (the precision below the configurations' bfloat16) in the program's
place fails the cell's limits, at a size a test run can hold."""

import time

import jax.numpy as jnp
import pytest

from _bench_path import BENCH  # noqa: F401

import cell
import correct
import graphs
import reference

NODES = 300


@pytest.mark.parametrize("workload", ["slim-arxivstat-il8", "gt-arxivstat-il8"])
def test_control_fails_the_limits(workload):
    from repro.configs import get_config
    from repro.core.graph import Graph
    from repro.tasks import NodeTask

    spec = cell.load_cell(workload)
    c, t = spec["config"], spec["traffic"]
    seed = 2**31 + 29
    graph = graphs.make_graph(NODES, t, seed)
    cfg = get_config(c["arch"]).replace(**c["model"])
    task = NodeTask(Graph(*graph), cfg, bq=c["block"]["bq"],
                    bk=c["block"]["bk"])
    perm = task.prep.perm
    c = dict(c, nodes=NODES)
    x = cell.reference_inputs(c, graph, perm,
                              cell.own_layout(c, t, graph, perm))
    x = {k: jnp.asarray(v) for k, v in x.items()}
    var = cell.variants(3, int(t["interleave_period"]))
    s32 = cell.weight_seed(seed)
    t0 = time.perf_counter()
    ref = reference.train(c, t["optimizer"], x, s32, var)
    ctl = reference.train(c, t["optimizer"], x, s32, var,
                          quant=jnp.float8_e4m3fn)
    ok, checks = correct.judge(correct.numbers(ctl, ref), spec["limits"])
    assert not ok, checks
    assert time.perf_counter() - t0 < 240
