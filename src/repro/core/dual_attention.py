"""Dual-interleaved Attention (paper §III-B) — jit-side compute.

* ``cluster_sparse_attention``: blocked-gather attention over a
  ClusterLayout (topology-induced pattern, post-reformation). This is the
  jnp oracle for the Pallas kernel and the CPU execution path. FLOPs are
  O(active_blocks * bq * bk) = O(E) rather than O(S^2).
* ``use_dense_step``: the interleave schedule — fully-connected attention
  every `period` steps, or forced when the C1-C3 condition check failed.

Score tensor layout throughout: (B, rc, KV, G, bq, mb, bk) where rc is the
q-block row chunk, mb the selected-k-block axis.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32


def use_dense_step(step: int, period: int, conditions_ok: bool) -> bool:
    """Host-side schedule: dense every `period` steps; always dense if the
    sparse pattern failed the universality conditions (C1-C3)."""
    if not conditions_ok:
        return True
    if period <= 0:
        return False
    return step % period == 0


@functools.partial(jax.jit, static_argnames=("bq", "bk", "causal",
                                             "row_chunk"))
def cluster_sparse_attention(q, k, v, block_idx, buckets=None,
                             bias_table=None, *, bq: int = 128,
                             bk: int = 128, causal: bool = False,
                             row_chunk: int = 8):
    """q: (B,S,H,Dh); k/v: (B,S,KV,Dh); block_idx: (B, nq, mb) int32
    (-1 padded); buckets: (B, nq, mb, bq, bk) int8 or None;
    bias_table: (H, n_buckets) or None. Returns (B,S,H,Dh)."""
    B, S, H, Dh = q.shape
    KV = k.shape[2]
    G = H // KV
    nq, mb = block_idx.shape[1], block_idx.shape[2]
    nk = S // bk
    scale = Dh ** -0.5

    qb = q.reshape(B, nq, bq, KV, G, Dh)
    kb = k.reshape(B, nk, bk, KV, Dh)
    vb = v.reshape(B, nk, bk, KV, Dh)

    rc = min(row_chunk, nq)
    while nq % rc:  # largest divisor of nq not exceeding row_chunk
        rc -= 1
    n_chunks = nq // rc

    @jax.checkpoint  # recompute block scores in backward (memory parity
    def chunk(ci):    # with the Pallas kernel's flash-style backward)
        def sl(x):
            return jax.lax.dynamic_slice_in_dim(x, ci * rc, rc, axis=1)

        qc = sl(qb)                       # (B, rc, bq, KV, G, Dh)
        ic = sl(block_idx)                # (B, rc, mb)
        safe = jnp.maximum(ic, 0)
        ksel = jax.vmap(lambda kk_, ii: jnp.take(kk_, ii, axis=0))(kb, safe)
        vsel = jax.vmap(lambda vv_, ii: jnp.take(vv_, ii, axis=0))(vb, safe)
        # ksel/vsel: (B, rc, mb, bk, KV, Dh)
        s = jnp.einsum("brqkgd,brmckd->brkgqmc", qc, ksel,
                       preferred_element_type=F32) * scale
        valid = (ic >= 0)[:, :, None, None, None, :, None]
        if buckets is not None:
            bc = sl(buckets)              # (B, rc, mb, bq, bk)
            bvalid = (bc >= 0).transpose(0, 1, 3, 2, 4)  # (B,rc,bq,mb,bk)
            valid = valid & bvalid[:, :, None, None, :, :, :]
            if bias_table is not None:
                bt = bias_table.astype(F32).reshape(KV, G, -1)
                bias = bt[:, :, jnp.maximum(bc, 0)]  # (KV,G,B,rc,mb,bq,bk)
                s = s + jnp.transpose(bias, (2, 3, 0, 1, 5, 4, 6))
        if causal:
            qpos = (ci * rc + jnp.arange(rc))[:, None] * bq \
                + jnp.arange(bq)[None, :]                 # (rc, bq)
            kpos = safe[..., None] * bk + jnp.arange(bk)  # (B, rc, mb, bk)
            cm = qpos[None, :, :, None, None] >= kpos[:, :, None, :, :]
            valid = valid & cm[:, :, None, None, :, :, :]
        s = jnp.where(valid, s, -jnp.inf)
        sf = s.reshape(B, rc, KV, G, bq, mb * bk)
        m = sf.max(-1, keepdims=True)
        dead = jnp.isneginf(m)
        p = jnp.where(dead, 0.0,
                      jnp.exp(sf - jnp.where(dead, 0.0, m)))
        l = p.sum(-1, keepdims=True)
        p = p / jnp.maximum(l, 1e-30)
        pv = p.reshape(B, rc, KV, G, bq, mb, bk)
        o = jnp.einsum("brkgqmc,brmckd->brqkgd", pv.astype(vsel.dtype), vsel,
                       preferred_element_type=F32)
        return o  # (B, rc, bq, KV, G, Dh)

    outs = jax.lax.map(chunk, jnp.arange(n_chunks))
    out = jnp.moveaxis(outs, 0, 1)        # (B, n_chunks, rc, bq, KV, G, Dh)
    out = out.reshape(B, S, H, Dh)
    return out.astype(q.dtype)


def dense_buckets_from_layout(layout):
    """Static (S, S) int8 bucket matrix scattered from the block layout
    (-1 where the sparse pattern has no entry). Host-side numpy."""
    import numpy as np
    S = layout.seq_len
    out = np.full((S, S), -1, np.int8)
    if layout.buckets is None:
        return out
    for i in range(layout.nq):
        for m_, j in enumerate(layout.block_idx[i]):
            if j < 0:
                continue
            out[i * layout.bq:(i + 1) * layout.bq,
                j * layout.bk:(j + 1) * layout.bk] = layout.buckets[i, m_]
    return out


def dense_bias_from_buckets(dense_buckets, bias_table, n_heads: int):
    """(S, S) or (B, S, S) int8 bucket matrix -> (B, H, S, S) additive
    bias for the dense interleave step: structural bias kept where the
    sparse pattern defines it, zero elsewhere (fully-connected attention).
    jit-safe both ways: ``bias_table`` may be a traced parameter and
    ``dense_buckets`` is an *array input*, so elastic re-layout swaps its
    contents without retracing the dense step."""
    bk = jnp.asarray(dense_buckets)
    if bk.ndim == 2:
        bk = bk[None]
    if bias_table is None:
        return jnp.zeros((bk.shape[0], n_heads) + bk.shape[1:], F32)
    # one compare-select per (static, small) bucket keeps S minor; a
    # gather along the bucket axis yields an (S, S, H) layout whose H
    # minor dim the TPU pads to 128 lanes (16x the bias at H = 8)
    table = bias_table.astype(F32)
    bk = bk[:, None]                                        # (B, 1, S, S)
    out = jnp.zeros(bk.shape[:1] + table.shape[:1] + bk.shape[2:], F32)
    for j in range(table.shape[1]):
        out = jnp.where(bk == j, table[None, :, j, None, None], out)
    return out                                              # (B, H, S, S)


def dense_bias_from_layout(layout, bias_table, n_heads: int):
    """(1, H, S, S) additive bias from a host-side ClusterLayout (see
    dense_bias_from_buckets for the array-input form)."""
    bk = dense_buckets_from_layout(layout)                  # np (S,S) int8
    if bias_table is None or layout.buckets is None:
        return jnp.zeros((1, n_heads) + bk.shape, F32)
    return dense_bias_from_buckets(bk, bias_table, n_heads)
