"""Runtime: trainer fault tolerance, checkpointing, optimizer, data."""

import shutil
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.ckpt.checkpoint import Checkpointer
from repro.configs import get_smoke_config
from repro.data.lm_pipeline import LMDataConfig, lm_batch
from repro.models import build
from repro.optim.adamw import AdamW, warmup_cosine
from repro.runtime.trainer import Trainer, TrainerConfig


def _mk_trainer(tmpdir, steps=8, fail_at=-1, seq=48, batch=4):
    cfg = get_smoke_config("smollm_135m")
    model = build(cfg)
    dc = LMDataConfig(vocab_size=cfg.vocab_size, seq_len=seq,
                      global_batch=batch)
    tc = TrainerConfig(steps=steps, ckpt_every=4, ckpt_dir=str(tmpdir),
                       fail_at_step=fail_at, lr=1e-3, warmup=2)
    return Trainer(model, tc, lambda s: lm_batch(dc, s)), model


def test_checkpoint_restart_resumes_exactly(tmp_path):
    d = tmp_path / "ck"
    tr, model = _mk_trainer(d, steps=8, fail_at=6)
    with pytest.raises(RuntimeError, match="injected"):
        tr.run()
    # crash-consistent checkpoint was written
    ck = Checkpointer(str(d))
    assert ck.latest_step() is not None

    tr2, _ = _mk_trainer(d, steps=8)
    state, status = tr2.run()
    assert status == "done"
    assert int(state["step"]) == 8
    # the resumed run trained only the remaining steps
    assert tr2.history[0]["step"] > 1

    # bitwise determinism: a run with no failure gives identical params
    d2 = tmp_path / "ck2"
    tr3, _ = _mk_trainer(d2, steps=8)
    state3, _ = tr3.run()
    flat_a = jax.tree.leaves(state["params"])
    flat_b = jax.tree.leaves(state3["params"])
    for a, b in zip(flat_a, flat_b):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_loss_decreases(tmp_path):
    tr, _ = _mk_trainer(tmp_path / "ck", steps=30, seq=64, batch=8)
    tr.run()
    first = np.mean([h["loss"] for h in tr.history[:5]])
    last = np.mean([h["loss"] for h in tr.history[-5:]])
    assert last < first - 0.1, (first, last)


def test_checkpointer_gc_and_atomicity(tmp_path):
    ck = Checkpointer(str(tmp_path), keep=2)
    for step in (1, 2, 3, 4):
        ck.save(step, {"x": jnp.full((4,), step)}, blocking=True)
    assert ck.all_steps() == [3, 4]
    got = ck.restore(4)
    np.testing.assert_array_equal(np.asarray(got["x"]), np.full((4,), 4.0))


def test_checkpoint_bf16_roundtrip(tmp_path):
    ck = Checkpointer(str(tmp_path))
    x = jnp.arange(16, dtype=jnp.bfloat16) / 3
    ck.save(1, {"x": x}, blocking=True)
    got = ck.restore(1)
    assert got["x"].dtype == np.dtype("bfloat16")
    np.testing.assert_array_equal(np.asarray(got["x"], np.float32),
                                  np.asarray(x, np.float32))


@pytest.mark.parametrize("state_dtype", ["float32", "bfloat16", "int8"])
def test_adamw_state_dtypes_converge(state_dtype):
    opt = AdamW(lr=0.1, state_dtype=state_dtype, weight_decay=0.0)
    params = {"w": jnp.array([5.0, -3.0])}
    st = opt.init(params)

    @jax.jit
    def step(p, s):
        g = jax.grad(lambda q: ((q["w"] - 1.0) ** 2).sum())(p)
        return opt.update(g, s, p)

    for _ in range(150):
        params, st = step(params, st)
    np.testing.assert_allclose(np.asarray(params["w"]), [1.0, 1.0],
                               atol=0.15)


def test_warmup_cosine_schedule():
    s = warmup_cosine(1.0, 10, 100)
    assert float(s(jnp.int32(5))) == pytest.approx(0.5)
    assert float(s(jnp.int32(10))) == pytest.approx(1.0, abs=0.01)
    assert float(s(jnp.int32(100))) == pytest.approx(0.1, abs=0.01)


def test_data_pipeline_deterministic_and_seekable():
    dc = LMDataConfig(vocab_size=512, seq_len=32, global_batch=4)
    b1 = lm_batch(dc, 7)
    b2 = lm_batch(dc, 7)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    b3 = lm_batch(dc, 8)
    assert not np.array_equal(b1["tokens"], b3["tokens"])
    # labels are next-token shifted
    np.testing.assert_array_equal(b1["tokens"][:, 1:], b1["labels"][:, :-1])


def test_straggler_detection(tmp_path):
    import time

    cfg = get_smoke_config("smollm_135m")
    model = build(cfg)
    dc = LMDataConfig(vocab_size=cfg.vocab_size, seq_len=32, global_batch=2)

    calls = {"n": 0}

    def slow_batch(step):
        calls["n"] += 1
        if step == 10:
            time.sleep(1.0)  # injected straggler
        return lm_batch(dc, step)

    tc = TrainerConfig(steps=14, ckpt_every=100, ckpt_dir=str(tmp_path),
                       lr=1e-3, warmup=2, straggler_factor=3.0)
    tr = Trainer(model, tc, slow_batch)
    tr.run()
    assert any(r.step == 10 for r in tr.stragglers), tr.stragglers


def _codec_roundtrip(tmp_path, codec):
    ck = Checkpointer(str(tmp_path / codec), codec=codec)
    tree = {"w": jnp.arange(24.0).reshape(4, 6),
            "n": {"b": jnp.ones((3,), jnp.bfloat16)},
            "step": jnp.int32(3)}
    ck.save(3, tree, blocking=True)
    import json
    import os
    d = str(tmp_path / codec / "step_00000003")
    with open(os.path.join(d, "manifest.json")) as f:
        manifest = json.load(f)
    assert manifest["codec"] == codec  # restore-side codec selection
    tree2 = ck.restore(3)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(tree2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_codec_zlib_roundtrip(tmp_path):
    """zlib is the stdlib fallback codec — must always work."""
    _codec_roundtrip(tmp_path, "zlib")


@pytest.mark.optional_dep("zstandard")
def test_checkpoint_codec_zstd_roundtrip(tmp_path):
    _codec_roundtrip(tmp_path, "zstd")


def test_checkpoint_unknown_codec_rejected(tmp_path):
    with pytest.raises(ValueError, match="codec"):
        Checkpointer(str(tmp_path), codec="lz9").save(
            1, {"x": jnp.ones(2)}, blocking=True)


def test_checkpoint_extra_manifest_roundtrip(tmp_path):
    """The manifest's `extra` dict (elastic tuner/layout state) must
    round-trip verbatim and default to None when absent."""
    ck = Checkpointer(str(tmp_path))
    extra = {"elastic": {"tuner": {"pos": 3, "ladder": [0.0, 0.1, 1.0]},
                         "layout_stats": {"density": 0.25}}}
    ck.save(1, {"x": jnp.ones(2)}, blocking=True, extra=extra)
    ck.save(2, {"x": jnp.ones(2)}, blocking=True)
    assert ck.load_extra(1) == extra
    assert ck.load_extra(2) is None


# ---------------------------------------------------------------- spans

def _spans_node_run(tmp_path, steps=6):
    from repro.core.graph import sbm_graph
    from repro.runtime import spans
    from repro.tasks import NodeTask

    cfg = get_smoke_config("graphormer_slim")
    g = sbm_graph(96, 4, p_in=0.05, p_out=0.003, feat_dim=cfg.feat_dim,
                  n_classes=cfg.n_classes, seed=0)
    tc = TrainerConfig(steps=steps, ckpt_every=3,
                       ckpt_dir=str(tmp_path / "ck"), lr=1e-3, warmup=2,
                       interleave_period=2)
    with spans.recording() as rec:
        task = NodeTask(g, cfg, bq=16, bk=16, d_b=8)
        tr = Trainer(build(cfg), tc, task=task)
        tr.run()
    return rec, task, tr


def test_trainer_spans_steps_children_and_compiles(tmp_path):
    from repro.runtime import spans

    rec, task, tr = _spans_node_run(tmp_path)
    by_id = {s["id"]: s for s in rec.spans}
    steps = [s for s in rec.spans if s["name"] == "repro.trainer.step"]
    assert [s["attrs"]["step"] for s in steps] == list(range(6))
    assert [s["attrs"]["variant"] for s in steps] == \
        ["dense", "sparse"] * 3
    kids = {s["id"]: [] for s in steps}
    for s in rec.spans:
        if s["parent"] in kids:
            kids[s["parent"]].append(s["name"])
    for s in steps:
        k = kids[s["id"]]
        for want in ("repro.task.batches", "repro.trainer.dispatch",
                     "repro.trainer.wait", "repro.trainer.rescue"):
            assert k.count(want) == 1, (s["attrs"], k)
    # saves after steps 3 (async: the write runs on its own thread) and
    # 6 (the final blocking save, outside every step)
    saves = [s for s in rec.spans if s["name"] == "repro.ckpt.save"]
    assert [by_id[s["parent"]]["attrs"]["step"]
            for s in saves if s["parent"] is not None] == [2]
    writes = [s for s in rec.spans if s["name"] == "repro.ckpt.write"]
    assert len(writes) == 2
    assert any(w["thread"] != threading.current_thread().name and
               w["parent"] is None for w in writes)
    init = [s for s in rec.spans if s["name"] == "repro.trainer.init"]
    assert len(init) == 1 and init[0]["parent"] is None
    # each variant compiles at its first call and never again
    comp = spans.compiles_by_step(rec)
    compiled = sorted(k for k, v in comp.items() if k is not None and
                      any(v.get(n, 0) > 0 for n in spans.COMPILE_SECONDS))
    assert compiled == [0, 1], comp
    for h in tr.history:
        assert 0 < h["wait_s"] <= h["seconds"]
    wait = [s for s in rec.spans if s["name"] == "repro.trainer.wait"]
    assert [h["wait_s"] for h in tr.history] == pytest.approx(
        [(s["end_ns"] - s["start_ns"]) * 1e-9 for s in wait])


def test_task_prep_seconds_is_its_span(tmp_path):
    rec, task, _ = _spans_node_run(tmp_path, steps=1)
    prep = [s for s in rec.spans if s["name"] == "repro.task.prep"]
    assert len(prep) == 1
    assert task.prep_seconds == pytest.approx(
        (prep[0]["end_ns"] - prep[0]["start_ns"]) * 1e-9)
    kids = [s["name"] for s in rec.spans if s["parent"] == prep[0]["id"]]
    assert sorted(set(kids)) == ["repro.prep.conditions",
                                 "repro.prep.encodings",
                                 "repro.prep.layout", "repro.prep.pack",
                                 "repro.prep.reorder"]
    # one layout span per ladder rung, each naming its threshold
    rungs = [s["attrs"]["beta_thre"] for s in rec.spans
             if s["name"] == "repro.prep.layout"]
    assert rungs == list(dict.fromkeys(task.tuner.ladder))


def test_graph_level_prep_seconds_is_its_span():
    """GraphLevelTask: one ``repro.task.prep`` over every mini-batch's
    ladder, the per-graph work inside it once per graph and the pad pass
    once per mini-batch."""
    from repro.runtime import spans
    from repro.tasks import GraphLevelTask, synthetic_graph_level_dataset

    cfg = get_smoke_config("gt")
    graphs = synthetic_graph_level_dataset(4, cfg, seed=1, n_lo=20,
                                           n_hi=40)
    with spans.recording() as rec:
        task = GraphLevelTask(graphs, cfg, batch_graphs=2, delta=2)
    prep = [s for s in rec.spans if s["name"] == "repro.task.prep"]
    assert len(prep) == 1
    assert task.prep_seconds == pytest.approx(
        (prep[0]["end_ns"] - prep[0]["start_ns"]) * 1e-9)
    n = {}
    for s in rec.spans:
        if s["parent"] == prep[0]["id"]:
            n[s["name"]] = n.get(s["name"], 0) + 1
    rungs = len(dict.fromkeys(task.tuner.ladder))
    assert n == {"repro.prep.reorder": 4, "repro.prep.conditions": 4,
                 "repro.prep.encodings": 4, "repro.prep.layout": 2 * rungs,
                 "repro.prep.pack": 2, "repro.prep.pad": 2}
    assert task.batches(0)["lap_pe"].shape[-1] == 8


@pytest.mark.parametrize("kind", ["node", "graph_level"])
def test_layout_slot_counters_equal_the_kernels_bound(kind):
    """``layout.live_slots`` / ``layout.live_slots_t`` under each rung's
    ``repro.prep.layout`` span are the grid bounds the cluster kernels
    compute in-trace from that rung's (padded) batch, and
    ``layout.rect_slots`` under ``repro.prep.pad`` the rectangle."""
    from repro.core.graph import sbm_graph
    # the kernels' own stream builders   # repro-lint: disable=REP002
    from repro.kernels.cluster_attention import dkv_stream, fwd_stream
    from repro.runtime import spans
    from repro.tasks import (GraphLevelTask, NodeTask,
                             synthetic_graph_level_dataset)

    with spans.recording() as rec:
        if kind == "node":
            cfg = get_smoke_config("graphormer_slim")
            task = NodeTask(sbm_graph(96, 4, p_in=0.05, p_out=0.003,
                                      feat_dim=cfg.feat_dim,
                                      n_classes=cfg.n_classes, seed=0),
                            cfg, bq=16, bk=16, d_b=8)
        else:
            cfg = get_smoke_config("gt")
            graphs = synthetic_graph_level_dataset(3, cfg, seed=1, n_lo=20,
                                                   n_hi=60)
            task = GraphLevelTask(graphs, cfg, delta=2)

    def counter(span_, name):
        return rec.counters[(span_["id"], name)]

    layouts = [s for s in rec.spans if s["name"] == "repro.prep.layout"]
    assert len(layouts) == len(dict.fromkeys(task.tuner.ladder))
    lives = set()
    for s in layouts:
        b = task._preps[s["attrs"]["beta_thre"]][0].batch
        n = int(fwd_stream(jnp.asarray(b["block_idx"]), interpret=True)[1])
        n_t = int(dkv_stream(jnp.asarray(b["block_idx_t"]),
                             interpret=True)[1])
        assert counter(s, "layout.live_slots") == n
        assert counter(s, "layout.live_slots_t") == n_t
        lives.add(n)
    nq, mb = task.prep.batch["block_idx"].shape[-2:]
    pad = [s for s in rec.spans if s["name"] == "repro.prep.pad"]
    assert [counter(s, "layout.rect_slots") for s in pad] == [nq * mb]
    assert max(lives) < nq * mb
