"""Cluster-sparse attention Pallas kernel — the Elastic Computation
Reformation kernel (paper §III-D), adapted to TPU (DESIGN.md §2).

The GPU version fights irregular memory access with L1/L2-tuned sub-block
gathers; on TPU we eliminate the irregularity structurally:

* the layout builder (core/reformation.py) emits, per q-block row, the list
  of k-blocks to visit (``block_idx``, -1 padded) — everything inside a
  visited block is dense, MXU-shaped work;
* ``block_idx`` is *scalar-prefetched* (PrefetchScalarGridSpec) so the
  index stream is known to the DMA engine ahead of the compute — the
  gather becomes a sequence of contiguous HBM->VMEM block copies that
  double-buffer behind the MXU. The stream is flattened to 1-D
  (:func:`flat_slot`): SMEM pads an array's minor dim to 128 words, so a
  multi-dim stream can take many times its size of the 1 MiB SMEM;
* padded (-1) entries skip compute with pl.when (they still index block 0
  for the DMA, which is harmless and keeps the pipeline static);
* optional int8 ``buckets`` blocks carry the bias bucket / mask per
  position (graph mode); bias_table is a small (H, n_buckets) table held
  in SMEM and looked up with an unrolled compare-select over its (static,
  small) bucket count — Mosaic lowers no vector gather.

Grid (B, H, nq, mb) — per-graph layouts (``block_idx`` of shape
``(B, nq, mb)``) batch the scalar-prefetch stream into the SAME single
``pallas_call`` (the index maps select graph ``b``'s rows), so a batch of
graphs costs one launch, not a Python loop. Online-softmax scratch is
carried over mb.

The forward can additionally emit per-row ``logsumexp`` residuals
(``return_residuals=True``) — the recomputation backward
(kernels/cluster_attention_bwd.py) rebuilds block scores from q/k and the
residual instead of materializing the (S, S) probability matrix. The
residual is laid out ``(B*H, S, 1)``: its ``(bq, 1)`` block keeps the
online-softmax column layout (no in-kernel transpose), and a trailing dim
equal to the array's own is a legal TPU block.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.policy import F32, NEG_INF

# trace-time launch counter (tests assert the batched per-graph path
# issues exactly ONE pallas_call per traced forward)
_PALLAS_CALLS = [0]


def pallas_call_count() -> int:
    """Number of ``pl.pallas_call`` launches built by this module so far
    (increments at trace time; cached jit re-executions don't count)."""
    return _PALLAS_CALLS[0]


def extend_bias_table(bias_table):
    """The ``fuse_bias`` rewrite's bias operand: the ``(H, n_buckets)``
    table with one trailing ``NEG_INF`` sentinel column appended, so the
    kernel's lookup (:func:`apply_bucket_bias`, whose default is the
    last column) routes masked positions (``bkt = -1``) onto it and
    ``s + bias`` replaces the clip+where pair. Exact in fp32
    (``s + NEG_INF == NEG_INF`` for every finite score the kernels
    produce); ``-1`` is the ONLY negative the layout builders emit."""
    bt = bias_table.astype(F32)
    sentinel = jnp.full((bt.shape[0], 1), NEG_INF, F32)
    return jnp.concatenate([bt, sentinel], axis=1)


def flat_slot(b, row, col, rows: int, cols: int):
    """Position of ``[b, row, col]`` of a ``(B, rows, cols)`` layout in
    its flattened 1-D scalar-prefetch stream."""
    return (b * rows + row) * cols + col


def apply_bucket_bias(s, bkt_ref, bias_ref, h, block_q, block_k, width,
                      fuse_bias):
    """Add the bucket bias to a ``(bq, bk)`` score tile and mask
    ``bkt < 0``; returns ``(bkt, s)``.

    The lookup ``table[h, clip(bkt, 0, width - 1)]`` is an unrolled
    compare-select over the table's static width (3 for ``adj``,
    ``max_spd + 2`` for ``spd``), reading scalars from the SMEM-resident
    table: Mosaic lowers no vector gather. Out-of-range ids (``-1``
    masked positions) take the LAST column — the clip of the plain
    lookup, and the ``NEG_INF`` sentinel of the fused one
    (:func:`extend_bias_table`), so under ``fuse_bias`` one add also
    masks."""
    bkt = bkt_ref[...].reshape(block_q, block_k).astype(jnp.int32)
    bias = jnp.full(bkt.shape, bias_ref[h, width - 1], F32)
    for j in range(width - 1):
        bias = jnp.where(bkt == j, bias_ref[h, j], bias)
    if fuse_bias:
        return bkt, s + bias
    return bkt, jnp.where(bkt >= 0, s + bias, NEG_INF)


def _finalize_row(o_ref, lse_ref, m_s, l_s, acc_s):
    """Write the output block and (training path: ``lse_ref`` is None on
    forward-only calls) its ``(bq, 1)`` logsumexp residual from the
    online-softmax state. Dead rows (no unmasked entry anywhere: l == 0)
    get lse = 0, so the backward's ``exp(s - lse)`` underflows to exactly
    0 for their NEG_INF scores instead of producing exp(0) = 1."""
    l = l_s[...]
    o_ref[0] = (acc_s[...] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
    if lse_ref is not None:
        lse = m_s[...] + jnp.log(jnp.maximum(l, 1e-30))
        lse_ref[0] = jnp.where(l > 0, lse, 0.0)


def _cluster_kernel(idx_ref,            # scalar-prefetch (B*nq*mb,)
                    q_ref, k_ref, v_ref, o_ref, lse_ref, m_s, l_s, acc_s, *,
                    sm_scale, causal, block_q, block_k, hoist_scale=False):
    b = pl.program_id(0)
    qi = pl.program_id(2)
    mi = pl.program_id(3)
    mb = pl.num_programs(3)

    @pl.when(mi == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    blk = idx_ref[flat_slot(b, qi, mi, pl.num_programs(2), mb)]

    @pl.when(blk >= 0)
    def _compute():
        q = q_ref[0].astype(F32)
        if hoist_scale:       # scale the (bq, Dh) q tile, not every score
            q = q * sm_scale
        k = k_ref[0].astype(F32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=F32)
        if not hoist_scale:
            s = s * sm_scale
        if causal:
            qpos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            kpos = blk * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(qpos >= kpos, s, NEG_INF)
        m_prev = m_s[...]
        m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_s[...] = l_s[...] * corr + p.sum(-1, keepdims=True)
        acc_s[...] = acc_s[...] * corr + jax.lax.dot_general(
            p, v_ref[0].astype(F32), (((1,), (0,)), ((), ())),
            preferred_element_type=F32)
        m_s[...] = m_new

    @pl.when(mi == mb - 1)
    def _finalize():
        _finalize_row(o_ref, lse_ref, m_s, l_s, acc_s)


def _cluster_kernel_biased(idx_ref, q_ref, k_ref, v_ref, bkt_ref, bias_ref,
                           o_ref, lse_ref, m_s, l_s, acc_s, *,
                           sm_scale, causal, block_q, block_k, width,
                           hoist_scale=False, fuse_bias=False):
    """Variant with int8 bucket masks + per-head bias table (graph mode).
    Under ``fuse_bias`` the bias operand already carries the trailing
    NEG_INF sentinel column (``extend_bias_table``); ``width`` is the
    operand's column count."""
    b = pl.program_id(0)
    h = pl.program_id(1)
    qi = pl.program_id(2)
    mi = pl.program_id(3)
    mb = pl.num_programs(3)

    @pl.when(mi == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    blk = idx_ref[flat_slot(b, qi, mi, pl.num_programs(2), mb)]

    @pl.when(blk >= 0)
    def _compute():
        q = q_ref[0].astype(F32)
        if hoist_scale:       # scale the (bq, Dh) q tile, not every score
            q = q * sm_scale
        k = k_ref[0].astype(F32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=F32)
        if not hoist_scale:
            s = s * sm_scale
        _, s = apply_bucket_bias(s, bkt_ref, bias_ref, h, block_q, block_k,
                                 width, fuse_bias)
        m_prev = m_s[...]
        m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
        m_new = jnp.maximum(m_new, NEG_INF)            # all-masked guard
        p = jnp.where(m_new <= NEG_INF, 0.0, jnp.exp(s - m_new))
        corr = jnp.exp(jnp.maximum(m_prev, NEG_INF) - m_new)
        l_s[...] = l_s[...] * corr + p.sum(-1, keepdims=True)
        acc_s[...] = acc_s[...] * corr + jax.lax.dot_general(
            p, v_ref[0].astype(F32), (((1,), (0,)), ((), ())),
            preferred_element_type=F32)
        m_s[...] = m_new

    @pl.when(mi == mb - 1)
    def _finalize():
        _finalize_row(o_ref, lse_ref, m_s, l_s, acc_s)


def qkv_specs(H, KV, nq, mb, bq, bk, Dh):
    """q / k / v BlockSpecs over the ``(B, H, nq, mb)`` grid: q by its
    own row, k and v by the k-block the flat prefetch stream names
    (padded ``-1`` slots fetch block 0, compute is skipped)."""
    G = H // KV

    def kv_map(b, h, qi, mi, idx):
        blk = idx[flat_slot(b, qi, mi, nq, mb)]
        return b * KV + h // G, jnp.maximum(blk, 0), 0

    return [pl.BlockSpec((1, bq, Dh),
                         lambda b, h, qi, mi, idx: (b * H + h, qi, 0)),
            pl.BlockSpec((1, bk, Dh), kv_map),
            pl.BlockSpec((1, bk, Dh), kv_map)]


def grid_triple(B, S, H, KV, Dh, nq, mb, *, bk, per_graph=False,
                n_buckets=None, return_residuals=False) -> dict:
    """The (grid, BlockSpec index_maps, operand shapes) contract of the
    forward kernel, built in ONE place so the launch below and the grid
    auditor (``repro.analysis.ir.pallas_check``) can never desync.

    Shapes are the *reshaped* operands as handed to pallas_call — the
    scalar prefetch is the flat ``(B*nq*mb,)`` stream, q
    ``(B*H, S, Dh)``, k/v ``(B*KV, S, Dh)``, buckets
    ``(B, nq, mb, bq, bk)`` per-graph / ``(nq, mb, bq, bk)`` shared,
    bias ``(H, n_buckets)`` (SMEM). The residual output is
    ``(B*H, S, 1)``. The dict feeds ``audit_grid`` directly:
    ``audit_grid(t["grid"], t["in_specs"], t["out_specs"],
    t["in_shapes"], t["out_shapes"], scalar_prefetch=(idx.reshape(-1),))``.

    The out index map revisits each ``(b*H+h, qi, 0)`` block across the
    innermost ``mb`` steps — *contiguous* revisits, the legal
    accumulate-in-VMEM pattern; the auditor's race rule allows exactly
    that and nothing else.
    """
    bq = S // nq
    grid = (B, H, nq, mb)
    in_specs = qkv_specs(H, KV, nq, mb, bq, bk, Dh)
    in_shapes = [(B * H, S, Dh), (B * KV, S, Dh), (B * KV, S, Dh)]
    out_specs = [pl.BlockSpec((1, bq, Dh),
                              lambda b, h, qi, mi, idx: (b * H + h, qi, 0))]
    out_shapes = [(B * H, S, Dh)]
    if return_residuals:
        out_specs.append(pl.BlockSpec(
            (1, bq, 1), lambda b, h, qi, mi, idx: (b * H + h, qi, 0)))
        out_shapes.append((B * H, S, 1))
    if n_buckets is not None:
        if per_graph:
            in_specs.append(pl.BlockSpec(
                (1, 1, 1, bq, bk),
                lambda b, h, qi, mi, idx: (b, qi, mi, 0, 0)))
            in_shapes.append((B, nq, mb, bq, bk))
        else:
            in_specs.append(pl.BlockSpec(
                (1, 1, bq, bk), lambda b, h, qi, mi, idx: (qi, mi, 0, 0)))
            in_shapes.append((nq, mb, bq, bk))
        in_specs.append(pl.BlockSpec(
            (H, n_buckets), lambda b, h, qi, mi, idx: (0, 0),
            memory_space=pltpu.SMEM))
        in_shapes.append((H, n_buckets))
    return {"grid": grid, "in_specs": in_specs, "out_specs": out_specs,
            "in_shapes": in_shapes, "out_shapes": out_shapes}


@functools.partial(jax.jit, static_argnames=("causal", "interpret",
                                             "return_residuals",
                                             "hoist_scale", "fuse_bias"))
def cluster_attention(q, k, v, block_idx, buckets=None, bias_table=None, *,
                      causal: bool = False, interpret: bool = False,
                      return_residuals: bool = False,
                      hoist_scale: bool = False, fuse_bias: bool = False):
    """q (B,S,H,Dh); k/v (B,S,KV,Dh); block_idx (nq, mb) int32 shared
    across the batch OR (B, nq, mb) per-graph layouts — both run as ONE
    pallas_call (the grid carries the batch dim and the scalar-prefetch
    index maps select per-graph rows); buckets (nq, mb, bq, bk) /
    (B, nq, mb, bq, bk) int8 optional; bias_table (H, n_buckets).
    Block sizes are implied: bq = S // nq, bk from buckets or = bq.
    ``return_residuals=True`` also returns the per-row logsumexp
    ``(B*H, S, 1)`` f32 for the recomputation backward.

    ``hoist_scale`` / ``fuse_bias`` are the autotuner's dataflow rewrites
    (same math, fewer vector ops — see ``repro.tune.schedule``):
    ``hoist_scale`` multiplies the softmax scale onto the q tile before
    the k-loop dot; ``fuse_bias`` (bucketed calls only) extends the bias
    table with a NEG_INF sentinel column so the mask select fuses into
    the lookup."""
    B, S, H, Dh = q.shape
    KV = k.shape[2]
    G = H // KV
    per_graph = block_idx.ndim == 3
    nq, mb = block_idx.shape[-2:]
    bq = S // nq
    bk = buckets.shape[-1] if buckets is not None else bq
    sm_scale = Dh ** -0.5

    qt = jnp.moveaxis(q, 2, 1).reshape(B * H, S, Dh)
    kt = jnp.moveaxis(k, 2, 1).reshape(B * KV, S, Dh)
    vt = jnp.moveaxis(v, 2, 1).reshape(B * KV, S, Dh)
    # one flat (B*nq*mb,) prefetch stream either way: a batch-shared
    # layout is broadcast (nq*mb int32 per graph — noise next to q/k/v)
    idx = jnp.broadcast_to(block_idx.astype(jnp.int32)[None] if not per_graph
                           else block_idx.astype(jnp.int32),
                           (B, nq, mb)).reshape(-1)

    fuse_bias = fuse_bias and buckets is not None
    if buckets is not None and bias_table is None:
        # zero bias: a 1-wide table is jit-safe (no data-dependent
        # width) and numerically exact — bucket lookups clamp to row 0
        bias_table = jnp.zeros((H, 1), F32)
    if fuse_bias:
        # extend BEFORE grid_triple so n_buckets below picks up the
        # sentinel column and the audited triple matches the launch
        bias_table = extend_bias_table(bias_table)
    triple = grid_triple(
        B, S, H, KV, Dh, nq, mb, bk=bk, per_graph=per_graph,
        n_buckets=bias_table.shape[1] if buckets is not None else None,
        return_residuals=return_residuals)
    scratch = [pltpu.VMEM((bq, 1), F32), pltpu.VMEM((bq, 1), F32),
               pltpu.VMEM((bq, Dh), F32)]
    # the residual output only exists on the training path — forward-only
    # calls (inference, serve) don't pay the (B*H, S, 1) f32 write
    out_dtypes = [q.dtype, F32]
    out_shape = [jax.ShapeDtypeStruct(s, dt)
                 for s, dt in zip(triple["out_shapes"], out_dtypes)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=triple["grid"],
        in_specs=triple["in_specs"], out_specs=triple["out_specs"],
        scratch_shapes=scratch)

    if buckets is None:
        kernel = functools.partial(
            _cluster_kernel, sm_scale=sm_scale, causal=causal, block_q=bq,
            block_k=bk, hoist_scale=hoist_scale)
        if not return_residuals:
            body = kernel
            kernel = lambda i, q_, k_, v_, o, m, l, a: \
                body(i, q_, k_, v_, o, None, m, l, a)
        args = (idx, qt, kt, vt)
    else:
        kernel = functools.partial(
            _cluster_kernel_biased, sm_scale=sm_scale, causal=causal,
            block_q=bq, block_k=bk, width=bias_table.shape[1],
            hoist_scale=hoist_scale, fuse_bias=fuse_bias)
        if not return_residuals:
            body = kernel
            kernel = lambda i, q_, k_, v_, bk_, bi_, o, m, l, a: \
                body(i, q_, k_, v_, bk_, bi_, o, None, m, l, a)
        args = (idx, qt, kt, vt, buckets, bias_table.astype(F32))

    _PALLAS_CALLS[0] += 1
    res = pl.pallas_call(
        kernel, grid_spec=grid_spec, out_shape=out_shape,
        interpret=interpret)(*args)
    out = jnp.moveaxis(res[0].reshape(B, H, S, Dh), 1, 2)
    return (out, res[1]) if return_residuals else out
