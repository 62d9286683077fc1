"""Pallas kernels vs pure-jnp oracles (interpret mode), swept over shapes
and dtypes (deliverable c)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.graph import sbm_graph
from repro.core.reformation import build_layout, lm_local_global_layout
# this file IS the kernel unit-test suite: it compares the kernel bodies
# against the oracles directly, below the ops.py dispatch layer.
from repro.kernels.cluster_attention import cluster_attention  # repro-lint: disable=REP002
from repro.kernels.flash_attention import flash_attention  # repro-lint: disable=REP002
from repro.kernels.ref import (cluster_attention_ref,  # repro-lint: disable=REP002
                               flash_attention_ref, ssd_ref)
from repro.kernels.ssd import ssd  # repro-lint: disable=REP002

KEY = jax.random.PRNGKey(7)


def _tol(dt):
    return 2e-2 if dt == jnp.bfloat16 else 2e-5


@pytest.mark.parametrize("B,S,H,KV,Dh", [
    (2, 256, 4, 2, 64),
    (1, 128, 8, 8, 32),
    (2, 192, 4, 1, 64),     # padding path (192 % 64 != 0 for bq=128)
    (1, 512, 2, 2, 128),
])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(B, S, H, KV, Dh, causal, dtype):
    q = jax.random.normal(KEY, (B, S, H, Dh)).astype(dtype)
    k = jax.random.normal(jax.random.fold_in(KEY, 1),
                          (B, S, KV, Dh)).astype(dtype)
    v = jax.random.normal(jax.random.fold_in(KEY, 2),
                          (B, S, KV, Dh)).astype(dtype)
    out = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64,
                          interpret=True)
    ref = flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=_tol(dtype), rtol=_tol(dtype))


@pytest.mark.parametrize("window,n_global", [(128, 64), (256, 0)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_cluster_attention_lm_layout(window, n_global, dtype):
    B, S, H, KV, Dh = 2, 512, 4, 2, 32
    q = jax.random.normal(KEY, (B, S, H, Dh)).astype(dtype)
    k = jax.random.normal(jax.random.fold_in(KEY, 1),
                          (B, S, KV, Dh)).astype(dtype)
    v = jax.random.normal(jax.random.fold_in(KEY, 2),
                          (B, S, KV, Dh)).astype(dtype)
    lay = lm_local_global_layout(S, bq=64, bk=64, window=window,
                                 n_global=n_global)
    bi = jnp.asarray(lay.block_idx)
    out = cluster_attention(q, k, v, bi, causal=True, interpret=True)
    ref = cluster_attention_ref(q, k, v, bi, causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=_tol(dtype), rtol=_tol(dtype))


@pytest.mark.parametrize("n,k_clusters,db", [(448, 4, 16), (320, 2, 8)])
def test_cluster_attention_graph_layout(n, k_clusters, db):
    g = sbm_graph(n, k_clusters, 0.05, 0.001, seed=1)
    lay = build_layout(g, bq=64, bk=64, k_clusters=k_clusters, d_b=db,
                       n_global=1)
    S, H, Dh = lay.seq_len, 4, 32
    q = jax.random.normal(KEY, (1, S, H, Dh), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(KEY, 3), (1, S, H, Dh))
    v = jax.random.normal(jax.random.fold_in(KEY, 4), (1, S, H, Dh))
    bt = jax.random.normal(jax.random.fold_in(KEY, 5),
                           (H, lay.n_buckets)) * 0.2
    bi = jnp.asarray(lay.block_idx)
    bu = jnp.asarray(lay.buckets)
    out = cluster_attention(q, k, v, bi, bu, bt, causal=False,
                            interpret=True)
    ref = cluster_attention_ref(q, k, v, bi, bu, bt, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5,
                               rtol=2e-5)


def test_cluster_full_layout_equals_dense():
    """Full block layout must reproduce dense attention exactly — the
    kernel's correctness anchor."""
    B, S, H, Dh = 1, 256, 4, 32
    q = jax.random.normal(KEY, (B, S, H, Dh), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (B, S, H, Dh))
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (B, S, H, Dh))
    nq = S // 64
    bi = jnp.tile(jnp.arange(nq, dtype=jnp.int32)[None], (nq, 1))
    out = cluster_attention(q, k, v, bi, causal=False, interpret=True)
    ref = flash_attention_ref(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


# ----------------------------------------- the compacted grid, fwd and bwd

_NQ = 8          # S = 64 in blocks of 8


def _rows(*rows, mb=None):
    """A (nq, mb) layout from per-q-row k-block lists, -1 padded."""
    mb = mb or max(4, max(len(r) for r in rows))
    out = np.full((len(rows), mb), -1, np.int32)
    for i, r in enumerate(rows):
        out[i, :len(r)] = r
    return out


def _diag(i):
    return [0, i] if i else [0]           # the global column, the diagonal


_LAYOUTS = {
    # the global row lists every k-block, every row visits block 0
    "global_row": [_rows([*range(_NQ)], *(_diag(i) for i in range(1, _NQ)))],
    # q-row 3 visits nothing: its output and lse are written as zeros
    "empty_row": [_rows(*(_diag(i) if i != 3 else [] for i in range(_NQ)))],
    # nothing visits k-block 5: its dK and dV are exactly zero
    "unvisited_kblock": [_rows(*(_diag(i) if i != 5 else [0, 4]
                                 for i in range(_NQ)))],
    # two graphs whose live counts differ (the grid runs to the larger)
    "ragged_batch": [_rows([*range(_NQ)], *(_diag(i) for i in range(1, _NQ))),
                     _rows(*([i] for i in range(_NQ)))],
    # capacity far above the live count: the padding is never visited
    "wide_capacity": [_rows(*(_diag(i) for i in range(_NQ)), mb=32)],
}


@pytest.mark.parametrize("case,rewrite", [
    ("global_row", "plain"), ("global_row", "fuse_bias+hoist_scale"),
    ("empty_row", "biased"), ("empty_row", "hoist_scale"),
    ("unvisited_kblock", "plain"),
    ("unvisited_kblock", "fuse_bias+hoist_scale"),
    ("ragged_batch", "biased"), ("wide_capacity", "hoist_scale"),
])
def test_compacted_grid_matches_reference(case, rewrite):
    """The forward, dQ and dK/dV kernels walk only the live slots (plus
    one dead entry per empty q-row or unvisited k-block), and agree with
    the jnp reference and its autodiff on every layout shape the
    compaction has to get right."""
    # the reference of the kernels' math, below the dispatch layer
    from repro.core.dual_attention import cluster_sparse_attention
    from repro.core.reformation import grid_steps, transpose_block_idx
    from repro.kernels.cluster_attention import (  # repro-lint: disable=REP002
        dkv_stream, fwd_stream)
    from repro.kernels.cluster_attention_bwd import (  # repro-lint: disable=REP002
        cluster_attention_vjp)

    graphs = _LAYOUTS[case]
    B, S, H, KV, Dh, bq = len(graphs), 8 * _NQ, 4, 2, 16, 8
    mb = max(g.shape[1] for g in graphs)
    bi = np.stack([np.pad(g, ((0, 0), (0, mb - g.shape[1])),
                          constant_values=-1) for g in graphs])
    ts = [transpose_block_idx(g, _NQ) for g in bi]
    mt = 32 if case == "wide_capacity" else max(t.shape[1] for t in ts)
    bit = np.stack([np.pad(t, ((0, 0), (0, mt - t.shape[1]), (0, 0)),
                           constant_values=-1) for t in ts])
    biased = "bias" in rewrite
    rng = np.random.default_rng(3)
    bu = bt = None
    if biased:
        bu = jnp.asarray(np.where(bi[..., None, None] >= 0, rng.integers(
            -1, 3, bi.shape + (bq, bq)), -1).astype(np.int8))
        bt = jax.random.normal(jax.random.fold_in(KEY, 9), (H, 3)) * 0.3
    q, k, v = (jax.random.normal(jax.random.fold_in(KEY, i), shape)
               for i, shape in enumerate([(B, S, H, Dh), (B, S, KV, Dh),
                                          (B, S, KV, Dh)]))
    g = jax.random.normal(jax.random.fold_in(KEY, 4), (B, S, H, Dh))
    bi_j, bit_j = jnp.asarray(bi), jnp.asarray(bit)

    # the grid bounds are the live counts, far under the rectangle, and
    # the streams visit each graph's live slots in the layout's order
    n, n_t = grid_steps(bi, bit)
    fwd, n_f = fwd_stream(bi_j, interpret=True)
    dkv, n_b = dkv_stream(bit_j, interpret=True)
    assert int(n_f) == n <= _NQ * _NQ and int(n_b) == n_t
    if case == "wide_capacity":
        assert n < _NQ * mb // 4 and n_t < _NQ * mt // 4
    for g in range(B):
        want = [(r, m) for r in range(_NQ)
                for m in (np.flatnonzero(bi[g, r] >= 0) if (bi[g, r] >= 0).any()
                          else [0])]
        got = np.asarray(fwd).reshape(B, -1)[g, :len(want)]
        assert [(w >> 13 & 8191, w & 8191) for w in got.tolist()] == want
        want = [tuple(v) for j in range(_NQ)
                for v in (bit[g, j][bit[g, j, :, 0] >= 0].tolist() or [[j, 0]])]
        got = np.asarray(dkv).reshape(B, -1)[g, :len(want)]
        assert [(w >> 13 & 8191, w & 8191) for w in got.tolist()] == want

    def kernel(q, k, v, bt):
        o = cluster_attention_vjp(
            q, k, v, bi_j, bu, bt, bit_j, interpret=True,
            hoist_scale="hoist" in rewrite, fuse_bias="fuse" in rewrite)
        return (o * g).sum()

    def reference(q, k, v, bt):
        o = cluster_sparse_attention(q, k, v, bi_j, bu, bt, bq=bq, bk=bq)
        return (o * g).sum()

    argnums = (0, 1, 2, 3) if biased else (0, 1, 2)
    val, got = jax.value_and_grad(kernel, argnums)(q, k, v, bt)
    ref, want = jax.value_and_grad(reference, argnums)(q, k, v, bt)
    np.testing.assert_allclose(val, ref, rtol=2e-5, atol=2e-4)
    for name, a, b in zip(("dq", "dk", "dv", "dbias"), got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4,
                                   rtol=2e-4, err_msg=name)
    if case == "unvisited_kblock":
        blk = slice(5 * bq, 6 * bq)
        assert not np.asarray(got[1])[:, blk].any()
        assert not np.asarray(got[2])[:, blk].any()
    if case == "empty_row":
        o, lse = cluster_attention(q, k, v, bi_j, bu, bt, interpret=True,
                                   return_residuals=True)
        row = slice(3 * bq, 4 * bq)
        assert not np.asarray(o)[:, row].any()
        assert not np.asarray(lse).reshape(B, H, S)[:, :, row].any()


@pytest.mark.parametrize("B,S,H,dh,N,Q", [
    (2, 128, 3, 32, 16, 32),
    (1, 64, 2, 16, 8, 16),
    (1, 256, 5, 64, 32, 64),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_sweep(B, S, H, dh, N, Q, dtype):
    x = (jax.random.normal(KEY, (B, S, H, dh)) * 0.5).astype(dtype)
    dt = jax.nn.softplus(
        jax.random.normal(jax.random.fold_in(KEY, 1), (B, S, H))) * 0.2
    a = -jnp.exp(jax.random.normal(jax.random.fold_in(KEY, 2), (H,)) * 0.3)
    b = (jax.random.normal(jax.random.fold_in(KEY, 3), (B, S, N))
         * 0.5).astype(dtype)
    c = (jax.random.normal(jax.random.fold_in(KEY, 4), (B, S, N))
         * 0.5).astype(dtype)
    y, s = ssd(x, dt, a, b, c, chunk=Q, interpret=True)
    yr, sr = ssd_ref(x, dt, a, b, c, Q)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32),
                               atol=_tol(dtype) * 4, rtol=_tol(dtype) * 4)
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr),
                               atol=_tol(dtype) * 4, rtol=_tol(dtype) * 4)


def test_ssd_matches_sequential_recurrence():
    """Chunked SSD == naive per-token recurrence (independent oracle)."""
    from repro.models.ssm import ssd_decode_step

    B, S, H, dh, N = 1, 32, 2, 8, 4
    x = jax.random.normal(KEY, (B, S, H, dh)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(jax.random.fold_in(KEY, 1),
                                           (B, S, H))) * 0.3
    a = -jnp.exp(jax.random.normal(jax.random.fold_in(KEY, 2), (H,)) * 0.2)
    b = jax.random.normal(jax.random.fold_in(KEY, 3), (B, S, N)) * 0.5
    c = jax.random.normal(jax.random.fold_in(KEY, 4), (B, S, N)) * 0.5
    y_chunk, s_chunk = ssd_ref(x, dt, a, b, c, 8)
    state = jnp.zeros((B, H, dh, N))
    ys = []
    for t in range(S):
        y_t, state = ssd_decode_step(state, x[:, t], dt[:, t], a,
                                     b[:, t], c[:, t])
        ys.append(y_t)
    y_seq = jnp.stack(ys, axis=1)
    np.testing.assert_allclose(np.asarray(y_chunk), np.asarray(y_seq),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(s_chunk), np.asarray(state),
                               atol=1e-4, rtol=1e-4)
