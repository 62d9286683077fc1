"""FlashAttention-style recomputation backward for the cluster-sparse
Pallas kernel, wired through ``jax.custom_vjp``.

The forward (kernels/cluster_attention.py) additionally emits per-row
``logsumexp`` residuals; the backward never materializes probabilities —
each kernel rebuilds its block's scores from q/k and the residual:

* **dQ kernel** — walks the *forward* stream (the compacted q-row
  layout, ``cluster_attention.fwd_stream``): grid ``(B, H, n)``,
  accumulating ``scale * ds @ k`` over the visited k-blocks of each q-row.
  The biased variant also emits per-(b, h, q-row) bucket sums of ``ds`` —
  the raw material of the ``bias_table`` gradient.
* **dK/dV kernel** — walks the *transposed* stream: the compacted
  ``block_idx_t`` (per k-block the ``(q-row, forward slot)`` pairs that
  visit it, emitted by ``core/reformation.transpose_block_idx`` alongside
  the forward one; ``cluster_attention.dkv_stream``): grid
  ``(B, H, n_t)``, accumulating ``p^T @ dO`` and ``scale * ds^T @ q`` over
  the visiting q-blocks. When the caller did not thread a transposed
  layout through (``block_idx_t=None``), one is derived in-trace with the
  dense bound ``mt = nq`` — correct, but the production path threads the
  tight host-built one. Both streams are built in-trace, so re-reformation
  swaps layouts (and the grids' bounds) with zero retraces.
* **epilogue** — GQA head groups reduce onto the KV heads, and the
  in-kernel bucketed ``dS`` partials (one masked reduction of ``dS`` per
  bucket and block) collapse over graphs and q-rows to the
  ``(H, n_buckets)`` ``bias_table`` gradient.

Residuals ``lse``/``delta`` are ``(B*H, S, 1)`` and the dbias partials
``(B, H, nq, 1, n_buckets)``: every block's last two dims are either
tile-aligned or the array's own, which the TPU lowering requires.

``ds = p * (dp - delta)`` with ``delta = rowsum(dO * O)`` — the standard
flash backward identity; ``p = exp(s - lse)`` is already normalized
because ``lse = m + log(l)``. Dead rows carry ``lse = 0`` so their
``NEG_INF`` scores underflow to ``p = 0`` (see ``_finalize_row``).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import cluster_attention as _ca
from repro.kernels.policy import F32, NEG_INF


# ------------------------------------------------------ transposed layout

def derive_block_idx_t(block_idx, nk: int):
    """In-trace transposed layout with the dense bound ``mt = nq``:
    ``(nq, mb) -> (nk, nq, 2)`` int32, -1 padded — each k-block row lists
    the (q-row, forward slot) pairs that visit it, q-rows ascending. The
    jnp twin of ``core/reformation.transpose_block_idx`` for callers that
    only hold a traced ``block_idx``.

    Precondition: no q-row lists the same k-block twice (the one-slot-per
    (q-row, k-block) scatter below keeps only the last duplicate, and the
    dense ``mt = nq`` bound could not hold both anyway). The layout
    builders never emit duplicates, and the dispatcher's vjp-aware
    legality check rejects concrete duplicate layouts; traced callers
    with duplicate rows must thread the host-built ``block_idx_t``."""
    nq, mb = block_idx.shape
    valid = block_idx >= 0
    rows = jnp.repeat(jnp.arange(nq, dtype=jnp.int32), mb)
    cols = jnp.where(valid, block_idx, nk).reshape(-1)
    slots = jnp.where(valid.reshape(-1),
                      jnp.tile(jnp.arange(mb, dtype=jnp.int32), nq), -1)
    slot_of = jnp.full((nq, nk + 1), -1, jnp.int32).at[rows, cols].set(slots)
    slot_of = slot_of[:, :nk].T                       # (nk, nq)
    has = slot_of >= 0
    key = jnp.where(has, jnp.arange(nq, dtype=jnp.int32)[None, :], nq)
    order = jnp.argsort(key, axis=1)                  # stable: q-rows first
    qrow = jnp.where(jnp.take_along_axis(has, order, axis=1),
                     order.astype(jnp.int32), -1)
    slot = jnp.where(qrow >= 0,
                     jnp.take_along_axis(slot_of, order, axis=1), -1)
    return jnp.stack([qrow, slot], axis=-1)


# ------------------------------------------------------------- dQ kernel

def _recompute_scores(q_ref, k_ref, sm_scale, block_q, block_k,
                      hoist_scale=False):
    """Rebuild the block's scores EXACTLY as the forward did (the lse
    residual bakes in the forward's op order, so the backward must mirror
    the ``hoist_scale`` rewrite). The returned ``q`` is always UNSCALED:
    the dK accumulation applies ``sm_scale`` explicitly — contracting
    against a scaled q would double it to ``sm_scale**2``."""
    q = q_ref[0].astype(F32)
    k = k_ref[0].astype(F32)
    if hoist_scale:
        s = jax.lax.dot_general(q * sm_scale, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=F32)
    else:
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=F32) * sm_scale
    return q, k, s


def _causal_mask(s, qi, ki, block_q, block_k):
    qpos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    kpos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    return jnp.where(qpos >= kpos, s, NEG_INF)


def _bucket_sums(bkt, ds, n_buckets):
    """``(1, n_buckets)`` per-bucket sums of a ``(bq, bk)`` ``dS`` tile,
    bucket ids clipped into ``[0, n_buckets)`` like the forward lookup:
    one masked two-axis reduction per bucket (``n_buckets`` is static and
    small), placed into its lane by a compare-select."""
    bc = jnp.clip(bkt, 0, n_buckets - 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, n_buckets), 1)
    out = jnp.zeros((1, n_buckets), F32)
    for j in range(n_buckets):
        part = jnp.where(bc == j, ds, 0.0).sum(axis=0, keepdims=True)
        out = jnp.where(lane == j, part.sum(axis=1, keepdims=True), out)
    return out


def _dq_kernel(idx_ref, lay_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref,
               *rest,
               sm_scale, causal, block_q, block_k, stream, biased,
               n_buckets=None, width=None, hoist_scale=False,
               fuse_bias=False):
    """dQ over the forward stream, one entry a grid step. ``biased``: the
    bucket block and bias table follow ``dl``, and a second output takes
    the q-row's bucket sums of ``dS`` (no causal branch: the biased
    FORWARD kernel has none, masking lives in the buckets, and the
    backward must recompute scores under exactly the forward's
    masking)."""
    if biased:
        bkt_ref, bias_ref, dq_ref, db_ref, acc_s, db_s = rest
    else:
        dq_ref, acc_s = rest
    b, h = pl.program_id(0), pl.program_id(1)
    w = stream.word(b, pl.program_id(2), idx_ref)

    @pl.when((w & _ca.FIRST) != 0)
    def _init():
        acc_s[...] = jnp.zeros_like(acc_s)
        if biased:
            db_s[...] = jnp.zeros_like(db_s)

    @pl.when((w & _ca.LIVE) != 0)
    def _compute():
        q, k, s = _recompute_scores(q_ref, k_ref, sm_scale, block_q,
                                    block_k, hoist_scale)
        if biased:
            bkt, s = _ca.apply_bucket_bias(s, bkt_ref, bias_ref, h, block_q,
                                           block_k, width, fuse_bias)
        elif causal:
            s = _causal_mask(s, stream.qrow(w), stream.kblk(b, w, lay_ref),
                             block_q, block_k)
        do = do_ref[0].astype(F32)
        p = jnp.exp(s - lse_ref[0])
        dp = jax.lax.dot_general(do, v_ref[0].astype(F32),
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=F32)
        ds = p * (dp - dl_ref[0])
        acc_s[...] += sm_scale * jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=F32)
        if biased:
            # bucket the raw dS (masked entries have p = 0 => ds = 0) at
            # the ORIGINAL n_buckets width — under fuse_bias the bias
            # OPERAND is one sentinel column wider, but the sentinel never
            # receives gradient (masked ds = 0) and the returned dbias
            # keeps the caller's table width
            db_s[...] += _bucket_sums(bkt, ds, n_buckets)

    @pl.when((w & _ca.LAST) != 0)
    def _finalize():
        dq_ref[0] = acc_s[...].astype(dq_ref.dtype)
        if biased:
            db_ref[0, 0, 0] = db_s[...]


# ---------------------------------------------------------- dK/dV kernel

def _dkv_kernel(idxt_ref, lay_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                dl_ref, *rest, sm_scale, causal, block_q, block_k, stream, biased,
                width=None, hoist_scale=False, fuse_bias=False):
    """dK/dV over the transposed stream, one visiting ``(q-row, slot)``
    a grid step (no causal branch when ``biased`` — see
    :func:`_dq_kernel`)."""
    if biased:
        bkt_ref, bias_ref, dk_ref, dv_ref, dk_s, dv_s = rest
    else:
        dk_ref, dv_ref, dk_s, dv_s = rest
    b, h = pl.program_id(0), pl.program_id(1)
    w = stream.word(b, pl.program_id(2), idxt_ref)

    @pl.when((w & _ca.FIRST) != 0)
    def _init():
        dk_s[...] = jnp.zeros_like(dk_s)
        dv_s[...] = jnp.zeros_like(dv_s)

    @pl.when((w & _ca.LIVE) != 0)
    def _compute():
        q, k, s = _recompute_scores(q_ref, k_ref, sm_scale, block_q,
                                    block_k, hoist_scale)
        if biased:
            _, s = _ca.apply_bucket_bias(s, bkt_ref, bias_ref, h, block_q,
                                         block_k, width, fuse_bias)
        elif causal:
            s = _causal_mask(s, stream.qrow(w), stream.kblk(b, w, lay_ref),
                             block_q, block_k)
        do = do_ref[0].astype(F32)
        p = jnp.exp(s - lse_ref[0])
        dv_s[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())), preferred_element_type=F32)
        dp = jax.lax.dot_general(do, v_ref[0].astype(F32),
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=F32)
        ds = p * (dp - dl_ref[0])
        dk_s[...] += sm_scale * jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=F32)

    @pl.when((w & _ca.LAST) != 0)
    def _finalize():
        dk_ref[0] = dk_s[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_s[...].astype(dv_ref.dtype)


# ------------------------------------------------------------ bwd driver

class Streams(NamedTuple):
    """Both compacted streams of a layout with their grid bounds: ``fwd``
    and ``n`` for the forward and dQ calls, ``dkv`` and ``n_t`` for the
    dK/dV call."""
    fwd: jax.Array
    n: jax.Array
    dkv: jax.Array
    n_t: jax.Array


def build_streams(block_idx, block_idx_t, nk: int, *,
                  interpret: bool = False) -> Streams:
    """The streams of a ``block_idx`` (``(nq, mb)`` shared or
    ``(B, nq, mb)`` per-graph) and its transposed layout; without one,
    the transposed layout is derived in-trace
    (:func:`derive_block_idx_t`). A caller that runs several layers on
    one layout builds them once and hands them to every call."""
    per_graph = block_idx.ndim == 3
    if block_idx_t is None:
        bi = block_idx.astype(jnp.int32)
        bit = jax.vmap(lambda x: derive_block_idx_t(x, nk))(
            bi if per_graph else bi[None])
    else:
        bit = block_idx_t.astype(jnp.int32)
        bit = bit if per_graph else bit[None]
    return Streams(*_ca.layout_stream(block_idx, interpret=interpret),
                   *_ca.dkv_stream(bit, interpret=interpret))


@functools.partial(jax.jit, static_argnames=("causal", "interpret",
                                             "with_bias", "hoist_scale",
                                             "fuse_bias"))
def _cluster_bwd(q, k, v, g, out, lse, block_idx, buckets, bias_table,
                 streams, *, causal, interpret, with_bias,
                 hoist_scale=False, fuse_bias=False):
    B, S, H, Dh = q.shape
    KV = k.shape[2]
    G = H // KV
    nq, mb = block_idx.shape[-2:]
    bq = S // nq
    bk = buckets.shape[-1] if buckets is not None else bq
    sm_scale = Dh ** -0.5

    qt = jnp.moveaxis(q, 2, 1).reshape(B * H, S, Dh)
    kt = jnp.moveaxis(k, 2, 1).reshape(B * KV, S, Dh)
    vt = jnp.moveaxis(v, 2, 1).reshape(B * KV, S, Dh)
    gt = jnp.moveaxis(g, 2, 1).reshape(B * H, S, Dh).astype(F32)
    ot = jnp.moveaxis(out, 2, 1).reshape(B * H, S, Dh).astype(F32)
    delta = (gt * ot).sum(-1, keepdims=True)          # (B*H, S, 1)

    idx, n, idxt, n_t = streams
    per_graph = block_idx.ndim == 3
    graphs = B if per_graph else 1
    fwd = _ca.Stream(nq * mb, nq, mb, graphs)
    trn = _ca.Stream(idxt.shape[0] // graphs, nq, mb, graphs,
                     by_kblock=True)
    lay = block_idx.astype(jnp.int32).reshape(-1)

    # dQ over the forward stream: q/do/lse/delta and the output by the
    # entry's q-row, k/v by its k-block
    qkv_specs = _ca.stream_specs(H, KV, fwd, bq, bk, Dh)
    q_rows = qkv_specs[0].index_map
    dq_in_specs = qkv_specs + [pl.BlockSpec((1, bq, Dh), q_rows),
                               pl.BlockSpec((1, bq, 1), q_rows),
                               pl.BlockSpec((1, bq, 1), q_rows)]
    dq_out_specs = [pl.BlockSpec((1, bq, Dh), q_rows)]
    dq_out_shape = [jax.ShapeDtypeStruct((B * H, S, Dh), q.dtype)]
    dq_scratch = [pltpu.VMEM((bq, Dh), F32)]
    dq_args = (idx, lay, qt, kt, vt, gt, lse, delta)
    kw = dict(sm_scale=sm_scale, causal=causal, block_q=bq, block_k=bk,
              hoist_scale=hoist_scale, biased=with_bias)
    if with_bias:
        # dbias (bucket sums, db output) stays at the ORIGINAL table
        # width; under fuse_bias the bias OPERAND grows the sentinel
        # column, exactly like the forward launch
        nb = bias_table.shape[1]
        bias_op = (_ca.extend_bias_table(bias_table) if fuse_bias
                   else bias_table.astype(F32))
        kw.update(width=bias_op.shape[1], fuse_bias=fuse_bias)

        def bias_spec():
            return pl.BlockSpec(bias_op.shape, lambda b, h, e, *r: (0, 0),
                                memory_space=pltpu.SMEM)

        def db_rows(b, h, e, *refs):
            return (b, h, q_rows(b, h, e, *refs)[1], 0, 0)

        dq_in_specs += [_ca.buckets_spec(fwd, bq, bk, per_graph),
                        bias_spec()]
        dq_out_specs.append(pl.BlockSpec((1, 1, 1, 1, nb), db_rows))
        dq_out_shape.append(jax.ShapeDtypeStruct((B, H, nq, 1, nb), F32))
        dq_scratch.append(pltpu.VMEM((1, nb), F32))
        dq_args += (buckets, bias_op)

    _ca._PALLAS_CALLS[0] += 1
    res = pl.pallas_call(
        functools.partial(_dq_kernel, stream=fwd, n_buckets=nb if with_bias
                          else None, **kw),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(B, H, n), in_specs=dq_in_specs,
            out_specs=dq_out_specs, scratch_shapes=dq_scratch),
        out_shape=dq_out_shape, interpret=interpret,
    )(*dq_args)
    dqt = res[0]
    dbias = None
    if with_bias:
        # epilogue: the bucketing already happened in-kernel (masked
        # reductions per block); the (B, H, nq, 1, nb) partials just
        # collapse over graphs and q-rows onto the (H, n_buckets) table
        dbias = res[1].sum(axis=(0, 2, 3)).astype(bias_table.dtype)

    # dK/dV over the transposed stream: q/do/lse/delta by the visiting
    # q-row, k/v and the outputs by the entry's k-block
    t_specs = _ca.stream_specs(H, KV, trn, bq, bk, Dh)
    t_rows = t_specs[0].index_map

    def k_rows(b, h, e, idx, lay):              # dK/dV: per q-head
        return b * H + h, trn.kblk(b, trn.word(b, e, idx), lay), 0

    dkv_in_specs = t_specs + [pl.BlockSpec((1, bq, Dh), t_rows),
                              pl.BlockSpec((1, bq, 1), t_rows),
                              pl.BlockSpec((1, bq, 1), t_rows)]
    dkv_args = (idxt, lay, qt, kt, vt, gt, lse, delta)
    if with_bias:
        dkv_in_specs += [_ca.buckets_spec(trn, bq, bk, per_graph),
                         bias_spec()]
        dkv_args += (buckets, bias_op)

    _ca._PALLAS_CALLS[0] += 1
    dkt, dvt = pl.pallas_call(
        functools.partial(_dkv_kernel, stream=trn, **kw),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(B, H, n_t), in_specs=dkv_in_specs,
            out_specs=[pl.BlockSpec((1, bk, Dh), k_rows),
                       pl.BlockSpec((1, bk, Dh), k_rows)],
            scratch_shapes=[pltpu.VMEM((bk, Dh), F32),
                            pltpu.VMEM((bk, Dh), F32)]),
        out_shape=[jax.ShapeDtypeStruct((B * H, S, Dh), k.dtype),
                   jax.ShapeDtypeStruct((B * H, S, Dh), v.dtype)],
        interpret=interpret,
    )(*dkv_args)

    dq = jnp.moveaxis(dqt.reshape(B, H, S, Dh), 1, 2)
    # GQA: the per-q-head dK/dV partials reduce over each group
    dk = jnp.moveaxis(
        dkt.reshape(B, KV, G, S, Dh).sum(2), 1, 2).astype(k.dtype)
    dv = jnp.moveaxis(
        dvt.reshape(B, KV, G, S, Dh).sum(2), 1, 2).astype(v.dtype)
    return dq, dk, dv, dbias


# ------------------------------------------------------------ custom_vjp

@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _cluster_vjp(meta, q, k, v, block_idx, buckets, bias_table, streams):
    causal, interpret, hoist_scale, fuse_bias = meta
    return _ca.cluster_attention(q, k, v, block_idx, buckets, bias_table,
                                 streams[:2], causal=causal,
                                 interpret=interpret,
                                 hoist_scale=hoist_scale,
                                 fuse_bias=fuse_bias)


def _cluster_vjp_fwd(meta, q, k, v, block_idx, buckets, bias_table,
                     streams):
    causal, interpret, hoist_scale, fuse_bias = meta
    out, lse = _ca.cluster_attention(q, k, v, block_idx, buckets,
                                     bias_table, streams[:2], causal=causal,
                                     interpret=interpret,
                                     return_residuals=True,
                                     hoist_scale=hoist_scale,
                                     fuse_bias=fuse_bias)
    return out, (q, k, v, block_idx, buckets, bias_table, streams, out, lse)


def _cluster_vjp_bwd(meta, res, g):
    causal, interpret, hoist_scale, fuse_bias = meta
    q, k, v, block_idx, buckets, bias_table, streams, out, lse = res
    with_bias = buckets is not None
    had_table = bias_table is not None
    if with_bias and not had_table:
        bias_table = jnp.zeros((q.shape[2], 1), F32)
    dq, dk, dv, dbias = _cluster_bwd(
        q, k, v, g, out, lse, block_idx, buckets, bias_table, streams,
        causal=causal, interpret=interpret, with_bias=with_bias,
        hoist_scale=hoist_scale, fuse_bias=fuse_bias and with_bias)
    return dq, dk, dv, None, None, (dbias if had_table else None), None


_cluster_vjp.defvjp(_cluster_vjp_fwd, _cluster_vjp_bwd)


def cluster_attention_vjp(q, k, v, block_idx, buckets=None, bias_table=None,
                          block_idx_t=None, *, causal: bool = False,
                          interpret: bool = False,
                          hoist_scale: bool = False,
                          fuse_bias: bool = False, streams=None):
    """Differentiable cluster-sparse attention: the forward kernel of
    ``kernels/cluster_attention.py`` with the recomputation backward above
    (dQ over the forward layout, dK/dV over the transposed one, bucketed
    ``bias_table`` gradient). This is what the dispatch layer
    (``kernels/ops.py``) routes kernel-mode calls through, which makes
    ``--attn-impl compiled|interpret`` a *training*-path setting.
    ``hoist_scale``/``fuse_bias`` are the autotuner's dataflow rewrites —
    applied identically in the forward and the recomputation backward.
    ``streams`` are the layout's :func:`build_streams`, when the caller
    built them once for several calls (then ``block_idx_t`` is not
    read); else they are built here."""
    if streams is None:
        bk = buckets.shape[-1] if buckets is not None \
            else q.shape[1] // block_idx.shape[-2]
        streams = build_streams(block_idx, block_idx_t, q.shape[1] // bk,
                                interpret=interpret)
    return _cluster_vjp((causal, interpret, hoist_scale,
                         fuse_bias and buckets is not None),
                        q, k, v, block_idx, buckets, bias_table, streams)
