"""Kernel dispatch layer (repro/kernels/ops.py): mode resolution, the
legality/fallback rules (warn + ref, never raise), lane padding, and the
composed sharded path — cluster parallelism with the Pallas kernel
(interpret mode) as ``attn_fn``, selected purely via env/config with no
call-site edits (ISSUE 2 acceptance criterion).

Gradient oracle-equivalence (ISSUE 5): ``jax.grad`` through the
dispatcher in interpret mode must match the ref-path gradients (dQ, dK,
dV, ``bias_table``) to fp32 tolerance — direct, per-graph-batched (one
``pallas_call``, no Python loop over B) and inside the 4-way shard_map
mesh — with zero RuntimeWarning fallbacks on legal shapes; and the
trainer's two-traced-steps invariant must survive the residual-emitting
forward."""

import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _subproc import run_code as _run

from repro.core.dual_attention import cluster_sparse_attention
from repro.core.graph import sbm_graph
from repro.core.reformation import build_layout, lm_local_global_layout
from repro.kernels import ops as kops

KEY = jax.random.PRNGKey(3)


@pytest.fixture(autouse=True)
def _clean_dispatch_state(monkeypatch):
    """Each test starts from 'auto' with no REPRO_FORCE_PALLAS* env."""
    for var in [kops._ENV_GLOBAL, *kops._ENV_PER_OP.values()]:
        monkeypatch.delenv(var, raising=False)
    yield
    kops.set_mode("auto")
    for op in kops.OPS:
        kops.set_mode("auto", op)


def _graph_case(B=2, H=4, KV=2, Dh=32, bq=32):
    g = sbm_graph(250, 2, 0.06, 0.004, seed=1)
    lay = build_layout(g, bq=bq, bk=bq, k_clusters=2, d_b=8, n_global=1)
    S = lay.seq_len
    q = jax.random.normal(KEY, (B, S, H, Dh))
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (B, S, KV, Dh))
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (B, S, KV, Dh))
    bi = jnp.broadcast_to(jnp.asarray(lay.block_idx),
                          (B,) + lay.block_idx.shape)
    bu = jnp.broadcast_to(jnp.asarray(lay.buckets), (B,) + lay.buckets.shape)
    bt = jax.random.normal(jax.random.fold_in(KEY, 3),
                           (H, lay.n_buckets)) * 0.2
    return lay, q, k, v, bi, bu, bt


def _bit(lay, B=None):
    """The host-built transposed layout, optionally batch-broadcast."""
    t = jnp.asarray(lay.block_idx_t)
    return t if B is None else jnp.broadcast_to(t, (B,) + t.shape)


def _assert_grads_close(got, want, names="q k v bias".split()):
    for name, a, b in zip(names, got, want):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-4,
            err_msg=f"d{name} mismatch vs ref")


# ------------------------------------------------------------- resolution

def test_mode_resolution_precedence(monkeypatch):
    # CPU default: everything auto -> ref
    assert kops.dispatch_table() == {op: "ref" for op in kops.OPS}
    # global programmatic override
    kops.set_mode("interpret")
    assert kops.resolve_mode("cluster_attention") == "interpret"
    assert kops.resolve_mode("flash_attention") == "interpret"
    # per-op programmatic beats global programmatic
    kops.set_mode("ref", "flash_attention")
    assert kops.resolve_mode("flash_attention") == "ref"
    assert kops.resolve_mode("cluster_attention") == "interpret"
    # global env beats programmatic
    monkeypatch.setenv(kops._ENV_GLOBAL, "ref")
    assert kops.resolve_mode("cluster_attention") == "ref"
    # per-op env beats global env
    monkeypatch.setenv(kops._ENV_PER_OP["cluster_attention"], "interpret")
    assert kops.resolve_mode("cluster_attention") == "interpret"
    assert kops.resolve_mode("ssd") == "ref"
    # "auto" clears a programmatic override
    kops.set_mode("auto", "flash_attention")
    monkeypatch.delenv(kops._ENV_GLOBAL)
    assert kops.resolve_mode("flash_attention") == "interpret"  # global set


def test_set_mode_validates():
    with pytest.raises(ValueError):
        kops.set_mode("fast")
    with pytest.raises(ValueError):
        kops.set_mode("ref", "not_an_op")


def test_trainer_config_routes_dispatch(tmp_path):
    """TrainerConfig.attn_impl is the config-side selector (no call-site
    edits): constructing a Trainer applies it process-wide."""
    from repro.runtime.trainer import Trainer, TrainerConfig

    class _Dummy:
        def loss(self, p, b):  # never called during __init__
            raise NotImplementedError

    cfg = TrainerConfig(ckpt_dir=str(tmp_path), attn_impl="interpret")
    Trainer(_Dummy(), cfg, lambda s: {})
    assert kops.resolve_mode("cluster_attention") == "interpret"
    # and auto resets it
    Trainer(_Dummy(), TrainerConfig(ckpt_dir=str(tmp_path)), lambda s: {})
    assert kops.resolve_mode("cluster_attention") == "ref"


# ----------------------------------------------------- kernel == oracle

def test_interpret_matches_oracle_batched_gqa_bias(monkeypatch):
    """Per-graph (3-D) block_idx + GQA + bias + non-lane-aligned Dh (the
    padding path), selected via env only."""
    lay, q, k, v, bi, bu, bt = _graph_case()
    ref = cluster_sparse_attention(q, k, v, bi, bu, bt, bq=lay.bq, bk=lay.bk)
    monkeypatch.setenv(kops._ENV_GLOBAL, "interpret")
    with warnings.catch_warnings():
        warnings.simplefilter("error")  # a silent fallback would hide a bug
        out = kops.cluster_attention(q, k, v, bi, bu, bt)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_interpret_matches_oracle_shared_layout_causal(monkeypatch):
    """2-D (batch-shared) LM local+global layout, causal, no buckets."""
    S = 256
    lay = lm_local_global_layout(S, bq=32, bk=32, window=64, n_global=32)
    q = jax.random.normal(KEY, (2, S, 4, 16))
    bi = jnp.asarray(lay.block_idx)
    ref = kops._cluster_ref(q, q, q, bi, None, None, causal=True,
                            row_chunk=8, bq=None, bk=None)
    monkeypatch.setenv(kops._ENV_GLOBAL, "interpret")
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        out = kops.cluster_attention(q, q, q, bi, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_buckets_without_bias_table_under_jit(monkeypatch):
    """bias_table=None with buckets must work under tracing (the dispatcher
    substitutes a zero table; bucket lookups clamp)."""
    lay, q, k, v, bi, bu, _ = _graph_case()
    ref = cluster_sparse_attention(q, k, v, bi, bu, None,
                                   bq=lay.bq, bk=lay.bk)
    monkeypatch.setenv(kops._ENV_GLOBAL, "interpret")
    out = jax.jit(lambda *a: kops.cluster_attention(*a))(q, k, v, bi, bu)
    assert not bool(jnp.isnan(out).any())
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


# ------------------------------------------------------------- fallback

def test_fallback_illegal_block_shape_warns_never_raises(monkeypatch):
    """bq=12 violates the fp32 sublane (8): the dispatcher must warn and
    return oracle numbers, not raise."""
    S, bq = 96, 12
    lay = lm_local_global_layout(S, bq=bq, bk=bq, window=24, n_global=bq)
    q = jax.random.normal(KEY, (1, S, 2, 16))
    bi = jnp.asarray(lay.block_idx)
    ref = kops._cluster_ref(q, q, q, bi, None, None, causal=True,
                            row_chunk=8, bq=None, bk=None)
    monkeypatch.setenv(kops._ENV_GLOBAL, "interpret")
    with pytest.warns(RuntimeWarning, match="sublane"):
        out = kops.cluster_attention(q, q, q, bi, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_fallback_causal_with_buckets(monkeypatch):
    lay, q, k, v, bi, bu, bt = _graph_case()
    monkeypatch.setenv(kops._ENV_GLOBAL, "interpret")
    with pytest.warns(RuntimeWarning, match="causal"):
        out = kops.cluster_attention(q, k, v, bi, bu, bt, causal=True)
    ref = cluster_sparse_attention(q, k, v, bi, bu, bt, bq=lay.bq,
                                   bk=lay.bk, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_explicit_bk_not_bq_consistent_across_modes(monkeypatch):
    """Without buckets the kernel cannot honor bk != bq (it derives
    bk = bq): the dispatcher must fall back with a warning and return the
    SAME numbers as ref mode — and the sharded path must forward bq/bk
    into its default attn_fn (PR1 parity)."""
    from repro import compat
    from repro.parallel.cluster_parallel import sharded_cluster_attention

    S, bq, bk = 256, 64, 32
    lay = lm_local_global_layout(S, bq=bq, bk=bk, window=64, n_global=bk)
    q = jax.random.normal(KEY, (1, S, 2, 16))
    bi = jnp.asarray(lay.block_idx)
    ref = cluster_sparse_attention(q, q, q, bi[None], bq=bq, bk=bk,
                                   causal=True)
    monkeypatch.setenv(kops._ENV_GLOBAL, "interpret")
    with pytest.warns(RuntimeWarning, match="bk"):
        out = kops.cluster_attention(q, q, q, bi, causal=True, bq=bq, bk=bk)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
    # p == 1 short-circuit of the sharded path uses the same default
    # attn_fn partial — bq/bk must reach it
    mesh = compat.make_mesh((1,), ("model",))
    with pytest.warns(RuntimeWarning, match="bk"):
        outs = sharded_cluster_attention(q, q, q, bi[None], mesh=mesh,
                                         bq=bq, bk=bk, causal=True)
    np.testing.assert_allclose(np.asarray(outs), np.asarray(ref), atol=2e-5)


def test_fallback_compiled_without_tpu(monkeypatch):
    """mode=compiled on a CPU backend: every op raises instead of
    running the oracle on the host under a device label."""
    lay, q, k, v, bi, bu, bt = _graph_case()
    monkeypatch.setenv(kops._ENV_GLOBAL, "compiled")
    with pytest.raises(RuntimeError, match="no TPU"):
        kops.cluster_attention(q, k, v, bi, bu, bt)
    with pytest.raises(RuntimeError, match="no TPU"):
        kops.flash_attention(q, k, v, causal=False)
    x = jax.random.normal(KEY, (1, 64, 2, 16)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(KEY, 1),
                                           (1, 64, 2))) * 0.2
    a = -jnp.exp(jax.random.normal(jax.random.fold_in(KEY, 2), (2,)) * 0.3)
    b = jax.random.normal(jax.random.fold_in(KEY, 3), (1, 64, 8)) * 0.5
    c = jax.random.normal(jax.random.fold_in(KEY, 4), (1, 64, 8)) * 0.5
    with pytest.raises(RuntimeError, match="no TPU"):
        kops.ssd(x, dt, a, b, c, chunk=16)


# ---------------------------------------------- gradient == ref gradient

def test_grad_interpret_matches_ref_batched_gqa_bias(monkeypatch):
    """ISSUE 5 acceptance: jax.grad through ops.cluster_attention in
    interpret mode == ref-path gradients (dQ/dK/dV/d-bias_table) on the
    per-graph batched + GQA + non-lane-aligned case, with the host-built
    transposed layout AND with the in-trace derived one — zero fallback
    warnings either way."""
    lay, q, k, v, bi, bu, bt = _graph_case()
    bit = _bit(lay, B=q.shape[0])

    def loss_ref(q, k, v, bt):
        return (cluster_sparse_attention(q, k, v, bi, bu, bt, bq=lay.bq,
                                         bk=lay.bk) ** 2).sum()

    gref = jax.grad(loss_ref, argnums=(0, 1, 2, 3))(q, k, v, bt)
    monkeypatch.setenv(kops._ENV_GLOBAL, "interpret")
    with warnings.catch_warnings():
        warnings.simplefilter("error")  # a fallback would hide the kernel

        def loss_k(q, k, v, bt):
            return (kops.cluster_attention(q, k, v, bi, bu, bt, bit)
                    .astype(jnp.float32) ** 2).sum()

        def loss_k_derived(q, k, v, bt):
            return (kops.cluster_attention(q, k, v, bi, bu, bt)
                    .astype(jnp.float32) ** 2).sum()

        gk = jax.grad(loss_k, argnums=(0, 1, 2, 3))(q, k, v, bt)
        gd = jax.jit(jax.grad(loss_k_derived, argnums=(0, 1, 2, 3)))(
            q, k, v, bt)
    _assert_grads_close(gk, gref)
    _assert_grads_close(gd, gref)


def test_grad_interpret_matches_ref_shared_causal(monkeypatch):
    """2-D batch-shared LM local+global layout, causal, no buckets: the
    grads of the unbiased kernel pair (dQ via forward layout, dK/dV via
    the transposed one) match ref."""
    S = 256
    lay = lm_local_global_layout(S, bq=32, bk=32, window=64, n_global=32)
    q = jax.random.normal(KEY, (2, S, 4, 16))
    bi = jnp.asarray(lay.block_idx)

    def loss_ref(q):
        return (kops._cluster_ref(q, q, q, bi, None, None, causal=True,
                                  row_chunk=8, bq=None, bk=None) ** 2).sum()

    gref = jax.grad(loss_ref)(q)
    monkeypatch.setenv(kops._ENV_GLOBAL, "interpret")
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        gk = jax.grad(lambda q: (kops.cluster_attention(
            q, q, q, bi, None, None, _bit(lay), causal=True) ** 2).sum())(q)
    np.testing.assert_allclose(np.asarray(gk), np.asarray(gref),
                               atol=1e-4, rtol=1e-4)


def test_grad_flash_interpret_matches_ref(monkeypatch):
    """flash_attention grads (recomputation backward, GQA + ragged seq
    tail) match the chunked-attention oracle."""
    q = jax.random.normal(KEY, (2, 100, 4, 128))
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (2, 100, 2, 128))
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (2, 100, 2, 128))
    # oracle-equivalence test: the reference is deliberately the raw
    # oracle, not the dispatcher under test.
    from repro.kernels.ref import flash_attention_ref  # repro-lint: disable=REP002

    gref = jax.grad(lambda *a: (flash_attention_ref(
        *a, causal=True) ** 2).sum(), argnums=(0, 1, 2))(q, k, v)
    monkeypatch.setenv(kops._ENV_GLOBAL, "interpret")
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        gk = jax.grad(lambda *a: (kops.flash_attention(
            *a, causal=True, block_q=32, block_k=32) ** 2).sum(),
            argnums=(0, 1, 2))(q, k, v)
    _assert_grads_close(gk, gref, names="q k v".split())


def test_batched_per_graph_single_pallas_call(monkeypatch):
    """The per-graph (3-D block_idx) path must batch the scalar-prefetch
    grid into ONE pallas_call — not a Python loop over B."""
    # introspects the kernel module's pallas_call counter on purpose.
    from repro.kernels import cluster_attention as _ca  # repro-lint: disable=REP002

    lay, q, k, v, bi, bu, bt = _graph_case(B=3, Dh=24)  # unique shapes:
    monkeypatch.setenv(kops._ENV_GLOBAL, "interpret")   # forces a fresh
    before = _ca.pallas_call_count()                    # jit trace
    out = kops.cluster_attention(q, k, v, bi, bu, bt, _bit(lay, 3))
    assert _ca.pallas_call_count() - before == 1, \
        "batched forward built more than one pallas_call"
    ref = cluster_sparse_attention(q, k, v, bi, bu, bt, bq=lay.bq,
                                   bk=lay.bk)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_grad_fallback_malformed_transposed_layout(monkeypatch):
    """vjp-aware legality: a transposed layout the dK/dV kernel cannot
    consume warns and falls back to ref AT CALL TIME — jax.grad then
    differentiates the oracle instead of raising mid-trace."""
    lay, q, k, v, bi, bu, bt = _graph_case()
    bad = jnp.zeros((q.shape[0], 3, 4, 2), jnp.int32)  # wrong nk rows
    monkeypatch.setenv(kops._ENV_GLOBAL, "interpret")
    with pytest.warns(RuntimeWarning, match="transposed layout"):
        gk = jax.grad(lambda q: (kops.cluster_attention(
            q, k, v, bi, bu, bt, bad) ** 2).sum())(q)
    gref = jax.grad(lambda q: (cluster_sparse_attention(
        q, k, v, bi, bu, bt, bq=lay.bq, bk=lay.bk) ** 2).sum())(q)
    np.testing.assert_allclose(np.asarray(gk), np.asarray(gref),
                               atol=1e-4, rtol=1e-4)


def test_grad_fallback_duplicate_row_without_transposed_layout(monkeypatch):
    """A q-row visiting the same k-block twice cannot be represented by
    the derived (one-visitor-per-pair) transposed layout: concrete
    layouts without block_idx_t must warn-and-fall-back to ref, and the
    fallback grads must equal the oracle's (which double-counts the slot
    exactly like the forward does)."""
    S, bq = 128, 32
    bi = jnp.asarray(np.array([[0, 1, 0, -1], [1, 2, -1, -1],
                               [2, 3, -1, -1], [3, 0, -1, -1]], np.int32))
    q = jax.random.normal(KEY, (1, S, 2, 16))
    monkeypatch.setenv(kops._ENV_GLOBAL, "interpret")
    with pytest.warns(RuntimeWarning, match="twice"):
        gk = jax.grad(lambda q: (kops.cluster_attention(
            q, q, q, bi) ** 2).sum())(q)
    gref = jax.grad(lambda q: (kops._cluster_ref(
        q, q, q, bi, None, None, causal=False, row_chunk=8, bq=bq,
        bk=bq) ** 2).sum())(q)
    np.testing.assert_allclose(np.asarray(gk), np.asarray(gref),
                               atol=1e-4, rtol=1e-4)


def test_grad_under_shard_map_matches_ref():
    """ISSUE 5 acceptance: grads through the sharded path (4-way mesh,
    Ulysses a2a, interpret kernel, GQA + head-sharded bias + transposed
    layout threaded through shard_map) == single-device ref grads."""
    out = _run("""
        import os, warnings
        import jax, jax.numpy as jnp, numpy as np
        from repro import compat
        from repro.core.dual_attention import cluster_sparse_attention
        from repro.core.graph import sbm_graph
        from repro.core.reformation import build_layout
        from repro.parallel.cluster_parallel import sharded_cluster_attention

        mesh = compat.make_mesh((4,), ("model",))
        B, H, KV, Dh, bq = 1, 8, 4, 16, 64
        g = sbm_graph(500, 4, p_in=0.08, p_out=0.002, seed=0)
        lay = build_layout(g, bq=bq, bk=bq, k_clusters=4, d_b=8, n_global=1)
        S = lay.seq_len
        key = jax.random.PRNGKey(0)
        q = jax.random.normal(key, (B, S, H, Dh))
        k = jax.random.normal(jax.random.fold_in(key, 1), (B, S, KV, Dh))
        v = jax.random.normal(jax.random.fold_in(key, 2), (B, S, KV, Dh))
        bidx = jnp.broadcast_to(jnp.asarray(lay.block_idx),
                                (B,) + lay.block_idx.shape)
        bkts = jnp.broadcast_to(jnp.asarray(lay.buckets),
                                (B,) + lay.buckets.shape)
        bit = jnp.broadcast_to(jnp.asarray(lay.block_idx_t),
                               (B,) + lay.block_idx_t.shape)
        bias = jax.random.normal(jax.random.fold_in(key, 3),
                                 (H, lay.n_buckets)) * 0.2

        def loss_ref(q, k, v, bias):
            return (cluster_sparse_attention(q, k, v, bidx, bkts, bias,
                                             bq=bq, bk=bq) ** 2).sum()
        gref = jax.grad(loss_ref, argnums=(0, 1, 2, 3))(q, k, v, bias)

        os.environ["REPRO_FORCE_PALLAS"] = "interpret"
        def loss_sh(q, k, v, bias):
            return (sharded_cluster_attention(
                q, k, v, bidx, bkts, bias, bit, mesh=mesh, axis="model",
                dp_axes=(), bq=bq, bk=bq) ** 2).sum()
        with warnings.catch_warnings():
            warnings.simplefilter("error")      # fallback would be a bug
            with compat.use_mesh(mesh):
                gk = jax.jit(jax.grad(loss_sh, argnums=(0, 1, 2, 3)))(
                    q, k, v, bias)
        for name, a, b in zip("q k v bias".split(), gk, gref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-4, rtol=1e-4,
                                       err_msg=f"d{name}")
        print("OK")
    """)
    assert "OK" in out


def test_trainer_two_traces_with_interpret_kernel(tmp_path):
    """The trainer's two-traced-steps invariant (one sparse + one dense
    jitted step for the whole elastic run) survives the residual-emitting
    differentiable kernel forward: attn_impl='interpret' trains through
    the Pallas kernels, value_and_grad included."""
    from repro.configs import get_smoke_config
    from repro.core.graph import sbm_graph
    from repro.models import build
    from repro.runtime.trainer import Trainer, TrainerConfig
    from repro.tasks import NodeTask

    cfg = get_smoke_config("graphormer_slim").replace(dtype="float32")
    g = sbm_graph(64, 2, p_in=0.2, p_out=0.02, feat_dim=cfg.feat_dim,
                  n_classes=cfg.n_classes, seed=0)
    task = NodeTask(g, cfg, bq=8, bk=8, d_b=8)
    tcfg = TrainerConfig(steps=5, ckpt_every=100, ckpt_dir=str(tmp_path),
                         attn_impl="interpret", interleave_period=3,
                         elastic_every=2, log_every=100)
    tr = Trainer(build(cfg), tcfg, task=task)
    with warnings.catch_warnings():
        warnings.simplefilter("error", RuntimeWarning)  # no silent ref
        state, status = tr.run()
    assert status == "done"
    assert tr._step._cache_size() == 1
    assert tr._step_dense._cache_size() == 1
    assert all(np.isfinite(r["loss"]) for r in tr.history)


# ------------------------------------------------- composed sharded path

def test_sharded_path_with_interpret_kernel_matches_oracle():
    """ISSUE 2 acceptance: sharded cluster attention on the 4-way CPU mesh
    with attn_fn = Pallas kernel (interpret), incl. GQA + head-sharded
    bias, matches the jnp oracle within fp32 tolerance — selected purely
    via env, zero call-site edits."""
    out = _run("""
        import os, warnings
        import jax, jax.numpy as jnp, numpy as np
        from repro import compat
        from repro.core.dual_attention import cluster_sparse_attention
        from repro.core.graph import sbm_graph
        from repro.core.reformation import build_layout
        from repro.parallel.cluster_parallel import (can_shard_cluster,
                                                     sharded_cluster_attention)

        mesh = compat.make_mesh((2, 4), ("data", "model"))
        B, H, KV, Dh, bq = 2, 8, 4, 16, 64
        g = sbm_graph(500, 4, p_in=0.08, p_out=0.002, seed=0)
        lay = build_layout(g, bq=bq, bk=bq, k_clusters=4, d_b=8, n_global=1)
        S = lay.seq_len
        assert S == 512 and can_shard_cluster(H, KV, S, 4, bq, bq)
        key = jax.random.PRNGKey(0)
        q = jax.random.normal(key, (B, S, H, Dh))
        k = jax.random.normal(jax.random.fold_in(key, 1), (B, S, KV, Dh))
        v = jax.random.normal(jax.random.fold_in(key, 2), (B, S, KV, Dh))
        bidx = jnp.broadcast_to(jnp.asarray(lay.block_idx),
                                (B,) + lay.block_idx.shape)
        bkts = jnp.broadcast_to(jnp.asarray(lay.buckets),
                                (B,) + lay.buckets.shape)
        bias = jax.random.normal(jax.random.fold_in(key, 3),
                                 (H, lay.n_buckets)) * 0.2
        ref = cluster_sparse_attention(q, k, v, bidx, bkts, bias,
                                       bq=bq, bk=bq)

        os.environ["REPRO_FORCE_PALLAS"] = "interpret"  # the only knob
        fn = jax.jit(lambda *a: sharded_cluster_attention(
            *a, mesh=mesh, axis="model", bq=bq, bk=bq))
        with warnings.catch_warnings():
            warnings.simplefilter("error")      # fallback would be a bug
            with compat.use_mesh(mesh):
                out = fn(q, k, v, bidx, bkts, bias)
        err = float(jnp.abs(out - ref).max())
        assert err <= 1e-5, err

        # GQA down to 2 kv heads (r=2 replication inside the a2a)
        kg, vg = k[:, :, :2], v[:, :, :2]
        refg = cluster_sparse_attention(q, kg, vg, bidx, bkts, bias,
                                        bq=bq, bk=bq)
        with compat.use_mesh(mesh):
            outg = fn(q, kg, vg, bidx, bkts, bias)
        errg = float(jnp.abs(outg - refg).max())
        assert errg <= 1e-5, errg

        # the kernel path must still move data with all-to-all
        with compat.use_mesh(mesh):
            txt = fn.lower(q, k, v, bidx, bkts, bias).compile().as_text()
        assert "all-to-all" in txt, "no a2a in HLO"
        print("OK", err, errg)
    """)
    assert "OK" in out


def test_sharded_path_fallback_under_shard_map():
    """No fallback inside shard_map either: compiled-without-TPU raises
    at trace time out of the jitted sharded call."""
    out = _run("""
        import os
        import jax, jax.numpy as jnp, numpy as np
        import pytest
        from repro import compat
        from repro.core.reformation import lm_local_global_layout
        from repro.parallel.cluster_parallel import sharded_cluster_attention

        mesh = compat.make_mesh((4,), ("model",))
        B, S, H, Dh, bq = 1, 512, 8, 32, 64
        lay = lm_local_global_layout(S, bq=bq, bk=bq, window=128,
                                     n_global=bq)
        key = jax.random.PRNGKey(0)
        q = jax.random.normal(key, (B, S, H, Dh))
        bidx = jnp.asarray(lay.block_idx)[None]
        os.environ["REPRO_FORCE_PALLAS_CLUSTER"] = "compiled"  # no TPU here
        with pytest.raises(RuntimeError, match="no TPU"):
            with compat.use_mesh(mesh):
                jax.jit(lambda a, b: sharded_cluster_attention(
                    a, a, a, b, mesh=mesh, axis="model", dp_axes=(),
                    bq=bq, bk=bq, causal=True))(q, bidx)
        print("OK")
    """)
    assert "OK" in out


def test_graph_model_distributed_kernel_in_the_loop():
    """Full model: distributed graph loss (Ulysses a2a + cluster-sparse +
    head-sharded bias) equals single-device, with the oracle AND with the
    interpret kernel — the three paper levels composed."""
    out = _run("""
        import os
        import jax, jax.numpy as jnp
        from repro import compat
        from repro.configs import get_smoke_config
        from repro.configs.base import ShapeConfig
        from repro.core.graph import sbm_graph
        from repro.core.graph_model import graph_loss
        from repro.data.graph_pipeline import prepare_node_task
        from repro.models import build
        from repro.parallel.axes import axis_rules
        from repro.parallel.sharding import recipe_for

        cfg = get_smoke_config("graphormer_slim").replace(dtype="float32")
        g = sbm_graph(500, 4, p_in=0.04, p_out=0.002, feat_dim=cfg.feat_dim,
                      n_classes=cfg.n_classes, seed=0)
        prep = prepare_node_task(g, cfg, bq=64, bk=64, d_b=8)
        batch = {k: jnp.asarray(v) for k, v in prep.batch.items()}
        S = batch["feat"].shape[1]
        model = build(cfg)
        params = model.init(jax.random.PRNGKey(0))
        loss1, _ = jax.jit(lambda p, b: graph_loss(p, cfg, b))(params, batch)

        mesh = compat.make_mesh((2, 4), ("data", "model"))
        recipe = recipe_for(ShapeConfig("t", "train", S, 1), mesh)
        def f(p, b):
            with axis_rules(recipe, mesh):
                return graph_loss(p, cfg, b)
        with compat.use_mesh(mesh):
            loss_d, _ = jax.jit(f)(params, batch)
        assert abs(float(loss1) - float(loss_d)) < 1e-5, \
            (float(loss1), float(loss_d))
        os.environ["REPRO_FORCE_PALLAS"] = "interpret"
        with compat.use_mesh(mesh):
            loss_k, _ = jax.jit(f)(params, batch)
        assert abs(float(loss1) - float(loss_k)) < 1e-5, \
            (float(loss1), float(loss_k))
        print("OK", float(loss1), float(loss_d), float(loss_k))
    """)
    assert "OK" in out
