"""The elastic training loop: AutoTuner-driven re-reformation + the
dual-interleave schedule wired into the Trainer (paper §III-B/D).

Covers: ladder moves from the trainer's epoch boundary, re-layout with
ZERO retraces (two jitted steps for the whole run), the interleave
cadence, tuner-state round-trip through the checkpoint manifest, the
donated-buffer-safe crash rescue, and rung-layout compatibility with the
sharded path."""

import jax
import numpy as np
import pytest

from repro.ckpt.checkpoint import Checkpointer
from repro.configs import get_smoke_config
from repro.core.dual_attention import use_dense_step
from repro.core.graph import sbm_graph
from repro.core.reformation import grid_steps
from repro.models import build
from repro.parallel.cluster_parallel import can_shard_cluster
from repro.runtime.elastic import ElasticGraphTask
from repro.tasks import NodeTask
from repro.runtime.trainer import Trainer, TrainerConfig


def _mk_task(n=128, delta=2, seed=0):
    cfg = get_smoke_config("graphormer_slim")
    g = sbm_graph(n, 4, p_in=0.05, p_out=0.003, feat_dim=cfg.feat_dim,
                  n_classes=cfg.n_classes, seed=seed)
    return cfg, ElasticGraphTask(g, cfg, bq=16, bk=16, d_b=8, delta=delta)


def test_elastic_graph_task_is_node_task():
    """The pre-Task spelling must stay importable and BE the NodeTask."""
    assert ElasticGraphTask is NodeTask


def _mk_trainer(cfg, task, ckpt_dir, steps=24, *, interleave=5,
                elastic_every=2, fail_at=-1, ckpt_every=8):
    tc = TrainerConfig(steps=steps, ckpt_every=ckpt_every,
                       ckpt_dir=str(ckpt_dir), lr=2e-3, warmup=2,
                       fail_at_step=fail_at, interleave_period=interleave,
                       elastic_every=elastic_every)
    return Trainer(build(cfg), tc, elastic=task)


def test_tuner_moves_on_synthetic_plateau():
    """Epoch-boundary protocol without a trainer: improving LDR walks the
    ladder up, a loss plateau walks it back down."""
    _, task = _mk_task(n=96, delta=2)
    start = task.tuner.pos
    for i in range(8):  # steady descent at constant speed -> moves up
        task.on_epoch(5.0 - 0.4 * i, 1.0, step=i + 1)
    assert task.tuner.pos > start
    assert len(task.moves) >= 1
    peak = task.tuner.pos
    for i in range(6):  # plateau: LDR -> 0, worse than delta ago -> down
        task.on_epoch(2.0, 1.0, step=9 + i)
    assert task.tuner.pos < peak
    # every recorded move matches a real position change
    assert all(m.beta_thre == task.tuner.ladder[m.pos] for m in task.moves)


def test_elastic_run_ladder_interleave_and_zero_retraces(tmp_path,
                                                         monkeypatch):
    # the launch counter below the dispatch layer   # repro-lint: disable=REP002
    from repro.kernels import cluster_attention as _ca

    # the sparse step runs the Pallas cluster kernels (interpret mode),
    # whose grid bound is the layout's live count
    monkeypatch.setenv("REPRO_FORCE_PALLAS_CLUSTER", "interpret")
    cfg, task = _mk_task()
    tr = _mk_trainer(cfg, task, tmp_path / "ck")
    launches = _ca.pallas_call_count()
    state, status = tr.run()
    assert status == "done"
    assert _ca.pallas_call_count() > launches   # forward, dQ, dK/dV traced
    # >= 1 AutoTuner ladder move happened inside the trainer loop and the
    # served layout followed it
    assert len(task.moves) >= 1
    betas = {h["beta_thre"] for h in tr.history}
    assert len(betas) >= 2
    # >= 1 dense interleave step; cadence = the host-side schedule
    for h in tr.history:
        want = use_dense_step(h["step"] - 1, 5, task.conditions_ok)
        assert h["dense"] == want, h
    assert sum(1 for h in tr.history if h["dense"]) >= 1
    # the sparse steps ran on layouts whose live counts differ, so the
    # kernels' grid bound changed value between steps
    live = {grid_steps(task._preps[h["beta_thre"]][0].layout.block_idx)[0]
            for h in tr.history if not h["dense"]}
    assert len(live) >= 2, live
    # exactly two traces for the whole run (sparse + dense), despite the
    # re-layouts: shapes never changed, and neither did the grid's shape
    assert tr._step._cache_size() == 1
    assert tr._step_dense._cache_size() == 1


def test_tuner_state_survives_restart(tmp_path):
    d = tmp_path / "ck"
    cfg, task = _mk_task()
    tr = _mk_trainer(cfg, task, d, fail_at=18)
    with pytest.raises(RuntimeError, match="injected"):
        tr.run()
    saved_pos = task.tuner.pos
    saved_moves = len(task.moves)
    assert saved_moves >= 1  # the run must have moved before dying

    # fresh process: new task starts at the ladder default...
    cfg2, task2 = _mk_task()
    assert task2.tuner.pos == 1
    tr2 = _mk_trainer(cfg2, task2, d)
    state, status = tr2.run()
    # ...and the restore resumed the ladder instead of resetting it
    assert status == "done"
    assert int(state["step"]) == 24
    assert task2.moves[:saved_moves] == task.moves
    ck = Checkpointer(str(d))
    extra = ck.load_extra(ck.latest_step())
    assert extra["task"]["tuner"]["pos"] == task2.tuner.pos
    assert "layout_stats" in extra["task"]
    assert extra["task"]["tuner"]["ladder"][saved_pos] == pytest.approx(
        task.tuner.ladder[saved_pos])


def test_crash_save_survives_donated_buffers(tmp_path):
    """A step that dies mid-call deletes its donated inputs; the rescue
    checkpoint must come from the undonated host copy and restore."""
    cfg, task = _mk_task(n=96)
    tr = _mk_trainer(cfg, task, tmp_path, steps=6, interleave=0,
                     elastic_every=0, ckpt_every=100)
    real_step = tr._step
    calls = {"n": 0}

    def dying_step(state, batch, fault):
        calls["n"] += 1
        if calls["n"] == 4:
            for leaf in jax.tree.leaves(state):  # simulate donation
                leaf.delete()
            raise RuntimeError("boom inside step")
        return real_step(state, batch, fault)

    tr._step = dying_step
    with pytest.raises(RuntimeError, match="boom"):
        tr.run()
    ck = Checkpointer(str(tmp_path))
    latest = ck.latest_step()
    assert latest == 3  # last completed step, not a corrupted one
    st = ck.restore(latest)
    assert int(np.asarray(st["step"])) == 3
    for leaf in jax.tree.leaves(st):
        assert np.isfinite(np.asarray(leaf, np.float32)).all()


def test_relayout_rungs_compose_with_sharded_path():
    """Every ladder rung must keep the invariants the Ulysses-sharded
    attention needs: constant whole-block S and a fixed mb capacity."""
    cfg, task = _mk_task()
    seqs = set()
    for (prep,) in task._preps.values():
        lay = prep.layout
        seqs.add(lay.seq_len)
        assert lay.mb == task.mb_cap
        assert lay.seq_len % lay.bq == 0 and lay.seq_len % lay.bk == 0
        assert can_shard_cluster(cfg.n_heads, cfg.kv_heads, lay.seq_len,
                                 2, lay.bq, lay.bk)
        assert prep.batch["block_idx"].shape == (1, lay.nq, task.mb_cap)
        assert prep.batch["dense_buckets"].shape == \
            (1, lay.seq_len, lay.seq_len)
    assert len(seqs) == 1
