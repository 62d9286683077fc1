"""FlashAttention-style recomputation backward for the cluster-sparse
Pallas kernel, wired through ``jax.custom_vjp``.

The forward (kernels/cluster_attention.py) additionally emits per-row
``logsumexp`` residuals; the backward never materializes probabilities —
each kernel rebuilds its block's scores from q/k and the residual:

* **dQ kernel** — reuses the *forward* q-row layout (``block_idx``): grid
  ``(B, H, nq, mb)``, accumulating ``scale * ds @ k`` over the visited
  k-blocks of each q-row. The biased variant also emits per-(b, h, q-row)
  bucket sums of ``ds`` — the raw material of the ``bias_table`` gradient.
* **dK/dV kernel** — consumes the *transposed* layout (``block_idx_t``,
  per k-block the ``(q-row, forward slot)`` pairs that visit it, emitted
  by ``core/reformation.transpose_block_idx`` alongside the forward one):
  grid ``(B, H, nk, mt)``, accumulating ``p^T @ dO`` and
  ``scale * ds^T @ q`` over the visiting q-blocks. When the caller did
  not thread a transposed layout through (``block_idx_t=None``), one is
  derived in-trace with the dense bound ``mt = nq`` — correct, but the
  production path threads the tight host-built one so re-reformation
  swaps both layouts with zero retraces.
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


def _dq_kernel(idx_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref,
               dq_ref, acc_s, *, sm_scale, causal, block_q, block_k,
               hoist_scale=False):
    b = pl.program_id(0)
    qi = pl.program_id(2)
    mi = pl.program_id(3)
    mb = pl.num_programs(3)

    @pl.when(mi == 0)
    def _init():
        acc_s[...] = jnp.zeros_like(acc_s)

    blk = idx_ref[_ca.flat_slot(b, qi, mi, pl.num_programs(2), mb)]

    @pl.when(blk >= 0)
    def _compute():
        q, k, s = _recompute_scores(q_ref, k_ref, sm_scale, block_q,
                                    block_k, hoist_scale)
        if causal:
            s = _causal_mask(s, qi, blk, block_q, block_k)
        do = do_ref[0].astype(F32)
        p = jnp.exp(s - lse_ref[0])
        dp = jax.lax.dot_general(do, v_ref[0].astype(F32),
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=F32)
        ds = p * (dp - dl_ref[0])
        acc_s[...] += sm_scale * jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=F32)

    @pl.when(mi == mb - 1)
    def _finalize():
        dq_ref[0] = acc_s[...].astype(dq_ref.dtype)


def _dq_kernel_biased(idx_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref,
                      bkt_ref, bias_ref, dq_ref, db_ref, acc_s, db_s, *,
                      sm_scale, block_q, block_k, n_buckets, width,
                      hoist_scale=False, fuse_bias=False):
    # no causal branch: the biased FORWARD kernel has none (masking lives
    # in the buckets; ops.py rejects causal+buckets), and the backward
    # must recompute scores under exactly the forward's masking
    b = pl.program_id(0)
    h = pl.program_id(1)
    qi = pl.program_id(2)
    mi = pl.program_id(3)
    mb = pl.num_programs(3)

    @pl.when(mi == 0)
    def _init():
        acc_s[...] = jnp.zeros_like(acc_s)
        db_s[...] = jnp.zeros_like(db_s)

    blk = idx_ref[_ca.flat_slot(b, qi, mi, pl.num_programs(2), mb)]

    @pl.when(blk >= 0)
    def _compute():
        q, k, s = _recompute_scores(q_ref, k_ref, sm_scale, block_q,
                                    block_k, hoist_scale)
        bkt, s = _ca.apply_bucket_bias(s, bkt_ref, bias_ref, h, block_q,
                                       block_k, width, fuse_bias)
        do = do_ref[0].astype(F32)
        p = jnp.exp(s - lse_ref[0])
        dp = jax.lax.dot_general(do, v_ref[0].astype(F32),
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=F32)
        ds = p * (dp - dl_ref[0])
        acc_s[...] += sm_scale * jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=F32)
        # bucket the raw dS (masked entries have p = 0 => ds = 0) at the
        # ORIGINAL n_buckets width — under fuse_bias the bias OPERAND is
        # one sentinel column wider, but the sentinel never receives
        # gradient (masked ds = 0) and the returned dbias keeps the
        # caller's table width
        db_s[...] += _bucket_sums(bkt, ds, n_buckets)

    @pl.when(mi == mb - 1)
    def _finalize():
        dq_ref[0] = acc_s[...].astype(dq_ref.dtype)
        db_ref[0, 0, 0] = db_s[...]


# ---------------------------------------------------------- dK/dV kernel

def _dkv_kernel(idxt_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref,
                dk_ref, dv_ref, dk_s, dv_s, *, sm_scale, causal, block_q,
                block_k, hoist_scale=False):
    b = pl.program_id(0)
    ki = pl.program_id(2)
    ti = pl.program_id(3)
    mt = pl.num_programs(3)

    @pl.when(ti == 0)
    def _init():
        dk_s[...] = jnp.zeros_like(dk_s)
        dv_s[...] = jnp.zeros_like(dv_s)

    qrow = idxt_ref[2 * _ca.flat_slot(b, ki, ti, pl.num_programs(2), mt)]

    @pl.when(qrow >= 0)
    def _compute():
        q, k, s = _recompute_scores(q_ref, k_ref, sm_scale, block_q,
                                    block_k, hoist_scale)
        if causal:
            s = _causal_mask(s, qrow, ki, block_q, block_k)
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

    @pl.when(ti == mt - 1)
    def _finalize():
        dk_ref[0] = dk_s[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_s[...].astype(dv_ref.dtype)


def _dkv_kernel_biased(idxt_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                       dl_ref, bkt_ref, bias_ref, dk_ref, dv_ref, dk_s,
                       dv_s, *, sm_scale, block_q, block_k, width,
                       hoist_scale=False, fuse_bias=False):
    # no causal branch — see _dq_kernel_biased
    b = pl.program_id(0)
    h = pl.program_id(1)
    ki = pl.program_id(2)
    ti = pl.program_id(3)
    mt = pl.num_programs(3)

    @pl.when(ti == 0)
    def _init():
        dk_s[...] = jnp.zeros_like(dk_s)
        dv_s[...] = jnp.zeros_like(dv_s)

    qrow = idxt_ref[2 * _ca.flat_slot(b, ki, ti, pl.num_programs(2), mt)]

    @pl.when(qrow >= 0)
    def _compute():
        q, k, s = _recompute_scores(q_ref, k_ref, sm_scale, block_q,
                                    block_k, hoist_scale)
        _, s = _ca.apply_bucket_bias(s, bkt_ref, bias_ref, h, block_q,
                                     block_k, width, fuse_bias)
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

    @pl.when(ti == mt - 1)
    def _finalize():
        dk_ref[0] = dk_s[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_s[...].astype(dv_ref.dtype)


# ------------------------------------------------------------ bwd driver

@functools.partial(jax.jit, static_argnames=("causal", "interpret",
                                             "with_bias", "hoist_scale",
                                             "fuse_bias"))
def _cluster_bwd(q, k, v, g, out, lse, block_idx, buckets, bias_table,
                 block_idx_t, *, causal, interpret, with_bias,
                 hoist_scale=False, fuse_bias=False):
    B, S, H, Dh = q.shape
    KV = k.shape[2]
    G = H // KV
    per_graph = block_idx.ndim == 3
    nq, mb = block_idx.shape[-2:]
    bq = S // nq
    bk = buckets.shape[-1] if buckets is not None else bq
    nk = S // bk
    sm_scale = Dh ** -0.5

    qt = jnp.moveaxis(q, 2, 1).reshape(B * H, S, Dh)
    kt = jnp.moveaxis(k, 2, 1).reshape(B * KV, S, Dh)
    vt = jnp.moveaxis(v, 2, 1).reshape(B * KV, S, Dh)
    gt = jnp.moveaxis(g, 2, 1).reshape(B * H, S, Dh).astype(F32)
    ot = jnp.moveaxis(out, 2, 1).reshape(B * H, S, Dh).astype(F32)
    delta = (gt * ot).sum(-1, keepdims=True)          # (B*H, S, 1)

    idx = jnp.broadcast_to(
        block_idx.astype(jnp.int32) if per_graph
        else block_idx.astype(jnp.int32)[None], (B, nq, mb))
    if block_idx_t is None:
        idxt = jax.vmap(lambda bi: derive_block_idx_t(bi, nk))(idx)
    else:
        idxt = jnp.broadcast_to(
            block_idx_t.astype(jnp.int32) if block_idx_t.ndim == 4
            else block_idx_t.astype(jnp.int32)[None],
            (B,) + block_idx_t.shape[-3:])
    mt = idxt.shape[2]
    # both scalar-prefetch streams go in flat (see _ca.flat_slot): the
    # (B, nk, mt, 2) transposed layout would take 64x its size of SMEM
    idx = idx.reshape(-1)
    idxt = idxt.reshape(-1)

    def visitor(b, ki, ti, idxt, col):
        """Column ``col`` (0: q-row, 1: forward slot) of the transposed
        layout's ``[b, ki, ti]`` entry, -1 padding clamped to 0."""
        pos = 2 * _ca.flat_slot(b, ki, ti, nk, mt) + col
        return jnp.maximum(idxt[pos], 0)

    qkv_do_specs = _ca.qkv_specs(H, KV, nq, mb, bq, bk, Dh) + [
        pl.BlockSpec((1, bq, Dh),
                     lambda b, h, qi, mi, idx: (b * H + h, qi, 0)),
        pl.BlockSpec((1, bq, 1),
                     lambda b, h, qi, mi, idx: (b * H + h, qi, 0)),
        pl.BlockSpec((1, bq, 1),
                     lambda b, h, qi, mi, idx: (b * H + h, qi, 0)),
    ]
    if with_bias:
        # dbias (bucket sums, db output) stays at the ORIGINAL table
        # width; under fuse_bias the bias OPERAND grows the sentinel
        # column, exactly like the forward launch
        nb = bias_table.shape[1]
        bias_op = (_ca.extend_bias_table(bias_table) if fuse_bias
                   else bias_table.astype(F32))
        nb_op = bias_op.shape[1]
        if per_graph:
            bkt_spec = pl.BlockSpec(
                (1, 1, 1, bq, bk),
                lambda b, h, qi, mi, idx: (b, qi, mi, 0, 0))
        else:
            bkt_spec = pl.BlockSpec(
                (1, 1, bq, bk), lambda b, h, qi, mi, idx: (qi, mi, 0, 0))
        bias_spec = pl.BlockSpec((H, nb_op),
                                 lambda b, h, qi, mi, idx: (0, 0),
                                 memory_space=pltpu.SMEM)
        bias_args = (buckets, bias_op)

        _ca._PALLAS_CALLS[0] += 1
        dqt, db_part = pl.pallas_call(
            functools.partial(_dq_kernel_biased, sm_scale=sm_scale,
                              block_q=bq, block_k=bk, n_buckets=nb,
                              width=nb_op, hoist_scale=hoist_scale,
                              fuse_bias=fuse_bias),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=(B, H, nq, mb),
                in_specs=qkv_do_specs + [bkt_spec, bias_spec],
                out_specs=[
                    pl.BlockSpec((1, bq, Dh),
                                 lambda b, h, qi, mi, idx: (b * H + h, qi, 0)),
                    pl.BlockSpec((1, 1, 1, 1, nb),
                                 lambda b, h, qi, mi, idx: (b, h, qi, 0, 0)),
                ],
                scratch_shapes=[pltpu.VMEM((bq, Dh), F32),
                                pltpu.VMEM((1, nb), F32)]),
            out_shape=[jax.ShapeDtypeStruct((B * H, S, Dh), q.dtype),
                       jax.ShapeDtypeStruct((B, H, nq, 1, nb), F32)],
            interpret=interpret,
        )(idx, qt, kt, vt, gt, lse, delta, *bias_args)
        # epilogue: the bucketing already happened in-kernel (masked
        # reductions per block); the (B, H, nq, 1, nb) partials just
        # collapse over graphs and q-rows onto the (H, n_buckets) table
        dbias = db_part.sum(axis=(0, 2, 3)).astype(bias_table.dtype)
    else:
        _ca._PALLAS_CALLS[0] += 1
        dqt = pl.pallas_call(
            functools.partial(_dq_kernel, sm_scale=sm_scale, causal=causal,
                              block_q=bq, block_k=bk,
                              hoist_scale=hoist_scale),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=(B, H, nq, mb),
                in_specs=qkv_do_specs,
                out_specs=pl.BlockSpec(
                    (1, bq, Dh),
                    lambda b, h, qi, mi, idx: (b * H + h, qi, 0)),
                scratch_shapes=[pltpu.VMEM((bq, Dh), F32)]),
            out_shape=jax.ShapeDtypeStruct((B * H, S, Dh), q.dtype),
            interpret=interpret,
        )(idx, qt, kt, vt, gt, lse, delta)
        dbias = None

    # dK/dV over the transposed layout: q/do/lse/delta blocks are selected
    # by the visiting q-row, k/v by the grid's own k-block position
    def q_rows(width):
        return pl.BlockSpec(
            (1, bq, width), lambda b, h, ki, ti, idxt: (
                b * H + h, visitor(b, ki, ti, idxt, 0), 0))

    dkv_in_specs = [
        q_rows(Dh),
        pl.BlockSpec((1, bk, Dh),
                     lambda b, h, ki, ti, idxt: (b * KV + h // G, ki, 0)),
        pl.BlockSpec((1, bk, Dh),
                     lambda b, h, ki, ti, idxt: (b * KV + h // G, ki, 0)),
        q_rows(Dh), q_rows(1), q_rows(1),
    ]
    dkv_out_specs = [
        pl.BlockSpec((1, bk, Dh),
                     lambda b, h, ki, ti, idxt: (b * H + h, ki, 0)),
        pl.BlockSpec((1, bk, Dh),
                     lambda b, h, ki, ti, idxt: (b * H + h, ki, 0)),
    ]
    dkv_scratch = [pltpu.VMEM((bk, Dh), F32), pltpu.VMEM((bk, Dh), F32)]
    if with_bias:
        if per_graph:
            bkt_t_spec = pl.BlockSpec(
                (1, 1, 1, bq, bk),
                lambda b, h, ki, ti, idxt: (
                    b, visitor(b, ki, ti, idxt, 0),
                    visitor(b, ki, ti, idxt, 1), 0, 0))
        else:
            bkt_t_spec = pl.BlockSpec(
                (1, 1, bq, bk),
                lambda b, h, ki, ti, idxt: (
                    visitor(b, ki, ti, idxt, 0),
                    visitor(b, ki, ti, idxt, 1), 0, 0))
        bias_t_spec = pl.BlockSpec((H, nb_op),
                                   lambda b, h, ki, ti, idxt: (0, 0),
                                   memory_space=pltpu.SMEM)
        kernel = functools.partial(_dkv_kernel_biased, sm_scale=sm_scale,
                                   block_q=bq, block_k=bk, width=nb_op,
                                   hoist_scale=hoist_scale,
                                   fuse_bias=fuse_bias)
        in_specs = dkv_in_specs + [bkt_t_spec, bias_t_spec]
        args = (idxt, qt, kt, vt, gt, lse, delta, buckets, bias_op)
    else:
        kernel = functools.partial(_dkv_kernel, sm_scale=sm_scale,
                                   causal=causal, block_q=bq, block_k=bk,
                                   hoist_scale=hoist_scale)
        in_specs = dkv_in_specs
        args = (idxt, qt, kt, vt, gt, lse, delta)

    _ca._PALLAS_CALLS[0] += 1
    dkt, dvt = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(B, H, nk, mt),
            in_specs=in_specs, out_specs=dkv_out_specs,
            scratch_shapes=dkv_scratch),
        out_shape=[jax.ShapeDtypeStruct((B * H, S, Dh), k.dtype),
                   jax.ShapeDtypeStruct((B * H, S, Dh), v.dtype)],
        interpret=interpret,
    )(*args)

    dq = jnp.moveaxis(dqt.reshape(B, H, S, Dh), 1, 2)
    # GQA: the per-q-head dK/dV partials reduce over each group
    dk = jnp.moveaxis(
        dkt.reshape(B, KV, G, S, Dh).sum(2), 1, 2).astype(k.dtype)
    dv = jnp.moveaxis(
        dvt.reshape(B, KV, G, S, Dh).sum(2), 1, 2).astype(v.dtype)
    return dq, dk, dv, dbias


# ------------------------------------------------------------ custom_vjp

@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _cluster_vjp(meta, q, k, v, block_idx, buckets, bias_table,
                 block_idx_t):
    causal, interpret, hoist_scale, fuse_bias = meta
    return _ca.cluster_attention(q, k, v, block_idx, buckets, bias_table,
                                 causal=causal, interpret=interpret,
                                 hoist_scale=hoist_scale,
                                 fuse_bias=fuse_bias)


def _cluster_vjp_fwd(meta, q, k, v, block_idx, buckets, bias_table,
                     block_idx_t):
    causal, interpret, hoist_scale, fuse_bias = meta
    out, lse = _ca.cluster_attention(q, k, v, block_idx, buckets,
                                     bias_table, causal=causal,
                                     interpret=interpret,
                                     return_residuals=True,
                                     hoist_scale=hoist_scale,
                                     fuse_bias=fuse_bias)
    return out, (q, k, v, block_idx, buckets, bias_table, block_idx_t,
                 out, lse)


def _cluster_vjp_bwd(meta, res, g):
    causal, interpret, hoist_scale, fuse_bias = meta
    q, k, v, block_idx, buckets, bias_table, block_idx_t, out, lse = res
    with_bias = buckets is not None
    had_table = bias_table is not None
    if with_bias and not had_table:
        bias_table = jnp.zeros((q.shape[2], 1), F32)
    dq, dk, dv, dbias = _cluster_bwd(
        q, k, v, g, out, lse, block_idx, buckets, bias_table, block_idx_t,
        causal=causal, interpret=interpret, with_bias=with_bias,
        hoist_scale=hoist_scale, fuse_bias=fuse_bias and with_bias)
    return dq, dk, dv, None, None, (dbias if had_table else None), None


_cluster_vjp.defvjp(_cluster_vjp_fwd, _cluster_vjp_bwd)


def cluster_attention_vjp(q, k, v, block_idx, buckets=None, bias_table=None,
                          block_idx_t=None, *, causal: bool = False,
                          interpret: bool = False,
                          hoist_scale: bool = False,
                          fuse_bias: bool = False):
    """Differentiable cluster-sparse attention: the forward kernel of
    ``kernels/cluster_attention.py`` with the recomputation backward above
    (dQ over the forward layout, dK/dV over the transposed one, bucketed
    ``bias_table`` gradient). This is what the dispatch layer
    (``kernels/ops.py``) routes kernel-mode calls through, which makes
    ``--attn-impl compiled|interpret`` a *training*-path setting.
    ``hoist_scale``/``fuse_bias`` are the autotuner's dataflow rewrites —
    applied identically in the forward and the recomputation backward."""
    return _cluster_vjp((causal, interpret, hoist_scale,
                         fuse_bias and buckets is not None),
                        q, k, v, block_idx, buckets, bias_table,
                        block_idx_t)
