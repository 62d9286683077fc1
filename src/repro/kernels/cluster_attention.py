"""Cluster-sparse attention Pallas kernel — the Elastic Computation
Reformation kernel (paper §III-D), adapted to TPU (DESIGN.md §2).

The GPU version fights irregular memory access with L1/L2-tuned sub-block
gathers; on TPU we eliminate the irregularity structurally:

* the layout builder (core/reformation.py) emits, per q-block row, the list
  of k-blocks to visit (``block_idx``, -1 padded) — everything inside a
  visited block is dense, MXU-shaped work;
* the kernels walk a *compacted* slot stream (:func:`fwd_stream`) built
  in-trace from that layout: one entry per live ``(q-row, slot)`` pair,
  in the layout's visiting order, plus one dead entry for a q-row that
  visits nothing (its output and ``lse`` must still be written). The
  stream and ``block_idx`` are *scalar-prefetched*
  (PrefetchScalarGridSpec, both flat 1-D: SMEM pads an array's minor dim
  to 128 words) so the DMA engine knows the gather ahead of the compute —
  a sequence of contiguous HBM->VMEM block copies that double-buffer
  behind the MXU;
* the grid's innermost axis is the stream's live count, a *dynamic* grid
  bound (a device scalar): a layout with 1% of its rectangle live takes
  1% of the rectangle's grid steps, a full layout takes all of them, and
  a re-layout with another live count changes the bound's value, never a
  shape (no retrace);
* optional int8 ``buckets`` blocks carry the bias bucket / mask per
  position (graph mode); bias_table is a small (H, n_buckets) table held
  in SMEM and looked up with an unrolled compare-select over its (static,
  small) bucket count — Mosaic lowers no vector gather.

Grid (B, H, n) — per-graph layouts (``block_idx`` of shape
``(B, nq, mb)``) give one stream per graph in the SAME single
``pallas_call`` (the index maps select graph ``b``'s entries; ``n`` is the
largest count over the batch), so a batch of graphs costs one launch, not
a Python loop. Online-softmax scratch is carried over a q-row's entries.

The forward can additionally emit per-row ``logsumexp`` residuals
(``return_residuals=True``) — the recomputation backward
(kernels/cluster_attention_bwd.py) rebuilds block scores from q/k and the
residual instead of materializing the (S, S) probability matrix. The
residual is laid out ``(B*H, S, 1)``: its ``(bq, 1)`` block keeps the
online-softmax column layout (no in-kernel transpose), and a trailing dim
equal to the array's own is a legal TPU block.
"""

from __future__ import annotations

import dataclasses
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


# ------------------------------------------------------ compacted stream
#
# The kernels walk a stream of entries, one a grid step, in order: the
# live slots of each row the kernel accumulates over (a q-row for the
# forward and dQ, a k-block for dK/dV), rows ascending. An entry is one
# int32: the forward slot it visits, ``q-row << 13 | slot`` (its k-block
# is ``block_idx`` there, which the kernels read from SMEM beside the
# stream), and the flags LIVE (the slot does work), FIRST and LAST (the
# first and last entry of its row). A row with no live slot gets one dead
# entry: a forward q-row its slot 0, a dK/dV k-block that nothing visits
# the flag KBLK with the k-block in the q-row field. Entries past a
# graph's own count, up to the batch's, repeat its last entry with no
# flag but KBLK.
#
# A small scalar Pallas kernel (:func:`_compact_kernel`) writes it: its
# loops touch the live slots and one more a row, where any XLA form
# (a sort, a scatter or gather the size of the layout) costs the TPU half
# a millisecond or hundreds of kilobytes of code, which stay in HBM.
# Layouts are -1 *padded* (a row's live slots come first). Rows and slots
# are below 2**13 (FIELD_MAX).

FIELD_MAX = 1 << 13
_FIELD = FIELD_MAX - 1
LIVE, FIRST, LAST, KBLK = 1 << 26, 1 << 27, 1 << 28, 1 << 29


@dataclasses.dataclass(frozen=True)
class Stream:
    """Static geometry of a stream: ``cap`` entries a graph, ``graphs``
    graphs (1 when one layout is shared by the batch), over a forward
    layout of ``nq`` x ``mb`` slots a graph; ``by_kblock`` for the dK/dV
    stream, whose dead entries carry their k-block (KBLK). Each accessor
    reads no more SMEM than its field needs: the index maps run every
    grid step."""
    cap: int
    nq: int
    mb: int
    graphs: int
    by_kblock: bool = False

    def _graph(self, b):
        return b if self.graphs > 1 else 0

    def word(self, b, e, stream):
        return stream[self._graph(b) * self.cap + e]

    def qrow(self, w):
        hi = (w >> 13) & _FIELD
        return jnp.where((w & KBLK) != 0, 0, hi) if self.by_kblock else hi

    @staticmethod
    def slot(w):
        return w & _FIELD

    def kblk(self, b, w, layout):
        """The entry's k-block (block 0 for a dead forward slot)."""
        hi = (w >> 13) & _FIELD
        at = hi * self.mb + (w & _FIELD)
        if self.by_kblock:
            own = (w & KBLK) != 0
            blk = layout[self._graph(b) * self.nq * self.mb
                         + jnp.where(own, 0, at)]
            return jnp.where(own, hi, jnp.maximum(blk, 0))
        return jnp.maximum(
            layout[self._graph(b) * self.nq * self.mb + at], 0)


def _compact_kernel(lay_ref, out_ref, n_ref, *, graphs, rows, slots,
                    visitors):
    """Write each graph's entries (module comment above) of a layout in
    SMEM: ``block_idx`` (``visitors`` False: row = q-row, slot t is the
    entry's slot) or ``block_idx_t`` (two words a slot: row = k-block,
    slot t holds the visiting ``(q-row, slot)``). ``n_ref[0]`` gets the
    largest count over the graphs; entries past it are not written (no
    grid reads them)."""
    width = 2 if visitors else 1
    cap = rows * slots

    def at(g, r, t):
        return ((g * rows + r) * slots + jnp.minimum(t, slots - 1)) * width

    def run(g, r):                # live slots at the head of row r
        return jax.lax.while_loop(
            lambda t: (t < slots) & (lay_ref[at(g, r, t)] >= 0),
            lambda t: t + 1, 0)

    def count(g):
        return jax.lax.fori_loop(
            0, rows, lambda r, n: n + jnp.maximum(run(g, r), 1), 0)

    n_max = jax.lax.fori_loop(
        0, graphs, lambda g, m: jnp.maximum(m, count(g)), 0)
    n_ref[0] = n_max

    def graph(g, carry):
        base = g * cap

        def row(r, p):
            c = run(g, r)

            def put(t, p):
                i = at(g, r, t)
                slot = ((lay_ref[i] << 13) | lay_ref[i + 1] if visitors
                        else (r << 13) | t)
                out_ref[base + p] = (slot | LIVE
                                     | jnp.where(t == 0, FIRST, 0)
                                     | jnp.where(t == c - 1, LAST, 0))
                return p + 1

            p = jax.lax.fori_loop(0, c, put, p)

            @pl.when(c == 0)
            def _dead():
                out_ref[base + p] = ((r << 13) | (KBLK if visitors else 0)
                                     | FIRST | LAST)

            return p + (c == 0).astype(jnp.int32)

        n = jax.lax.fori_loop(0, rows, row, 0)
        tail = out_ref[base + n - 1] & (KBLK | (LIVE - 1))

        def pad(p, c):
            out_ref[base + p] = tail
            return c

        jax.lax.fori_loop(n, n_max, pad, 0)
        return carry

    jax.lax.fori_loop(0, graphs, graph, 0)


def _compact(layout, visitors: bool, interpret: bool):
    G, R, M = layout.shape[:3]
    entries, n = pl.pallas_call(
        functools.partial(_compact_kernel, graphs=G, rows=R, slots=M,
                          visitors=visitors),
        out_shape=[jax.ShapeDtypeStruct((G * R * M,), jnp.int32),
                   jax.ShapeDtypeStruct((1,), jnp.int32)],
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                   pl.BlockSpec(memory_space=pltpu.SMEM)],
        interpret=interpret, name="compact_stream",
    )(layout.astype(jnp.int32).reshape(-1))
    return entries, n[0]


def fwd_stream(block_idx, *, interpret: bool = False):
    """The forward / dQ stream of a ``(G, nq, mb)`` layout: per graph, the
    live ``(q-row, slot)`` pairs, q-rows ascending then slots ascending.
    Returns ``((G * nq * mb,) int32, n)`` with ``n`` the largest count
    over the graphs — the kernels' grid bound."""
    return _compact(block_idx, False, interpret)


def dkv_stream(block_idx_t, *, interpret: bool = False):
    """The dK/dV stream of a ``(G, nk, mt, 2)`` transposed layout: per
    graph, the ``(q-row, forward slot)`` visitors of each k-block,
    k-blocks ascending then in the layout's order (q-rows ascending).
    Returns ``((G * nk * mt,) int32, n_t)``."""
    return _compact(block_idx_t, True, interpret)


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


def _cluster_kernel(idx_ref, lay_ref,   # scalar prefetch: stream, layout
                    q_ref, k_ref, v_ref, *rest, sm_scale, causal, block_q,
                    block_k, stream, biased, width=None,
                    hoist_scale=False, fuse_bias=False, residuals=True):
    """One grid step = one stream entry. ``biased``: the int8 bucket
    block and the per-head bias table follow v (graph mode; under
    ``fuse_bias`` the table carries the NEG_INF sentinel column and
    ``width`` is its column count; the biased kernel has no causal
    path — masking lives in the buckets)."""
    if biased:
        bkt_ref, bias_ref, *rest = rest
    o_ref, *rest = rest
    lse_ref = rest.pop(0) if residuals else None
    m_s, l_s, acc_s = rest
    b, h = pl.program_id(0), pl.program_id(1)
    w = stream.word(b, pl.program_id(2), idx_ref)

    @pl.when((w & FIRST) != 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    @pl.when((w & LIVE) != 0)
    def _compute():
        q = q_ref[0].astype(F32)
        if hoist_scale:       # scale the (bq, Dh) q tile, not every score
            q = q * sm_scale
        k = k_ref[0].astype(F32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=F32)
        if not hoist_scale:
            s = s * sm_scale
        m_prev = m_s[...]
        if biased:
            _, s = apply_bucket_bias(s, bkt_ref, bias_ref, h, block_q,
                                     block_k, width, fuse_bias)
            m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
            m_new = jnp.maximum(m_new, NEG_INF)        # all-masked guard
            p = jnp.where(m_new <= NEG_INF, 0.0, jnp.exp(s - m_new))
            corr = jnp.exp(jnp.maximum(m_prev, NEG_INF) - m_new)
        else:
            if causal:
                qpos = stream.qrow(w) * block_q + jax.lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 0)
                kpos = stream.kblk(b, w, lay_ref) * block_k + \
                    jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
                s = jnp.where(qpos >= kpos, s, NEG_INF)
            m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
        l_s[...] = l_s[...] * corr + p.sum(-1, keepdims=True)
        acc_s[...] = acc_s[...] * corr + jax.lax.dot_general(
            p, v_ref[0].astype(F32), (((1,), (0,)), ((), ())),
            preferred_element_type=F32)
        m_s[...] = m_new

    @pl.when((w & LAST) != 0)
    def _finalize():
        _finalize_row(o_ref, lse_ref, m_s, l_s, acc_s)


def stream_specs(H, KV, stream, bq, bk, Dh):
    """q / k / v BlockSpecs over the ``(B, H, n)`` grid of a stream: q by
    the entry's q-row, k and v by its k-block."""
    G = H // KV

    def q_map(b, h, e, idx, lay):
        return b * H + h, stream.qrow(stream.word(b, e, idx)), 0

    def kv_map(b, h, e, idx, lay):
        return (b * KV + h // G,
                stream.kblk(b, stream.word(b, e, idx), lay), 0)

    return [pl.BlockSpec((1, bq, Dh), q_map),
            pl.BlockSpec((1, bk, Dh), kv_map),
            pl.BlockSpec((1, bk, Dh), kv_map)]


def buckets_spec(stream, bq, bk, per_graph):
    """The int8 bucket block of an entry: ``buckets[(b,) q-row, slot]``."""
    def at(b, e, idx):
        w = stream.word(b, e, idx)
        return stream.qrow(w), stream.slot(w)

    if per_graph:
        return pl.BlockSpec((1, 1, 1, bq, bk), lambda b, h, e, idx, lay: (
            b, *at(b, e, idx), 0, 0))
    return pl.BlockSpec((1, 1, bq, bk), lambda b, h, e, idx, lay: (
        *at(b, e, idx), 0, 0))


def grid_triple(B, S, H, KV, Dh, nq, mb, n, *, bk, per_graph=False,
                n_buckets=None, return_residuals=False) -> dict:
    """The (grid, BlockSpec index_maps, operand shapes) contract of the
    forward kernel, built in ONE place so the launch below and the grid
    auditor (``repro.analysis.ir.pallas_check``) can never desync. ``n``
    is the stream's entry count, the grid's dynamic bound (a device
    scalar at launch, an int for the auditor).

    Shapes are the *reshaped* operands as handed to pallas_call — the
    scalar prefetch is the ``(G * nq * mb,)`` stream of :func:`fwd_stream`
    and the flat ``block_idx`` (``G = B`` per-graph, 1 shared), q
    ``(B*H, S, Dh)``, k/v ``(B*KV, S, Dh)``, buckets
    ``(B, nq, mb, bq, bk)`` per-graph / ``(nq, mb, bq, bk)`` shared,
    bias ``(H, n_buckets)`` (SMEM). The residual output is
    ``(B*H, S, 1)``. The dict feeds ``audit_grid`` directly:
    ``stream, n = fwd_stream(block_idx)``, then
    ``audit_grid(t["grid"], t["in_specs"], t["out_specs"],
    t["in_shapes"], t["out_shapes"],
    scalar_prefetch=(stream, block_idx.reshape(-1)))``.

    The out index map revisits each ``(b*H+h, q-row, 0)`` block across
    the q-row's consecutive entries (and a graph's tail of copies of its
    last entry) — *contiguous* revisits, the legal accumulate-in-VMEM
    pattern; the auditor's race rule allows exactly that and nothing
    else.
    """
    bq = S // nq
    stream = Stream(nq * mb, nq, mb, B if per_graph else 1)
    grid = (B, H, n)
    in_specs = stream_specs(H, KV, stream, bq, bk, Dh)
    in_shapes = [(B * H, S, Dh), (B * KV, S, Dh), (B * KV, S, Dh)]
    row_spec = in_specs[0]
    out_specs = [pl.BlockSpec((1, bq, Dh), row_spec.index_map)]
    out_shapes = [(B * H, S, Dh)]
    if return_residuals:
        out_specs.append(pl.BlockSpec((1, bq, 1), row_spec.index_map))
        out_shapes.append((B * H, S, 1))
    if n_buckets is not None:
        in_specs.append(buckets_spec(stream, bq, bk, per_graph))
        in_shapes.append((B, nq, mb, bq, bk) if per_graph
                         else (nq, mb, bq, bk))
        in_specs.append(pl.BlockSpec(
            (H, n_buckets), lambda b, h, e, *refs: (0, 0),
            memory_space=pltpu.SMEM))
        in_shapes.append((H, n_buckets))
    return {"grid": grid, "in_specs": in_specs, "out_specs": out_specs,
            "in_shapes": in_shapes, "out_shapes": out_shapes}


def layout_stream(block_idx, *, interpret: bool = False):
    """``(stream, n)`` of a ``(nq, mb)`` shared or ``(B, nq, mb)``
    per-graph layout: a shared layout gives one stream for the whole
    batch."""
    bi = block_idx.astype(jnp.int32)
    return fwd_stream(bi if bi.ndim == 3 else bi[None], interpret=interpret)


@functools.partial(jax.jit, static_argnames=("causal", "interpret",
                                             "return_residuals",
                                             "hoist_scale", "fuse_bias"))
def cluster_attention(q, k, v, block_idx, buckets=None, bias_table=None,
                      stream=None, *,
                      causal: bool = False, interpret: bool = False,
                      return_residuals: bool = False,
                      hoist_scale: bool = False, fuse_bias: bool = False):
    """q (B,S,H,Dh); k/v (B,S,KV,Dh); block_idx (nq, mb) int32 shared
    across the batch OR (B, nq, mb) per-graph layouts — both run as ONE
    pallas_call over the layout's compacted stream (the grid carries the
    batch dim, and its last axis is the live count); buckets
    (nq, mb, bq, bk) / (B, nq, mb, bq, bk) int8 optional; bias_table
    (H, n_buckets). Block sizes are implied: bq = S // nq, bk from
    buckets or = bq. ``return_residuals=True`` also returns the per-row
    logsumexp ``(B*H, S, 1)`` f32 for the recomputation backward.
    ``stream`` is the layout's ``(stream, n)`` from :func:`layout_stream`
    when the caller built it once for several calls; else it is built
    here.

    ``hoist_scale`` / ``fuse_bias`` are the autotuner's dataflow rewrites
    (same math, fewer vector ops — see ``repro.tune.schedule``):
    ``hoist_scale`` multiplies the softmax scale onto the q tile before
    the k-loop dot; ``fuse_bias`` (bucketed calls only) extends the bias
    table with a NEG_INF sentinel column so the mask select fuses into
    the lookup."""
    B, S, H, Dh = q.shape
    KV = k.shape[2]
    nq, mb = block_idx.shape[-2:]
    bq = S // nq
    bk = buckets.shape[-1] if buckets is not None else bq
    sm_scale = Dh ** -0.5

    qt = jnp.moveaxis(q, 2, 1).reshape(B * H, S, Dh)
    kt = jnp.moveaxis(k, 2, 1).reshape(B * KV, S, Dh)
    vt = jnp.moveaxis(v, 2, 1).reshape(B * KV, S, Dh)
    per_graph = block_idx.ndim == 3
    idx, n = stream if stream is not None else layout_stream(
        block_idx, interpret=interpret)

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
        B, S, H, KV, Dh, nq, mb, n, bk=bk, per_graph=per_graph,
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
        num_scalar_prefetch=2, grid=triple["grid"],
        in_specs=triple["in_specs"], out_specs=triple["out_specs"],
        scratch_shapes=scratch)

    biased = buckets is not None
    kernel = functools.partial(
        _cluster_kernel, sm_scale=sm_scale, causal=causal, block_q=bq,
        block_k=bk, stream=Stream(nq * mb, nq, mb, B if per_graph else 1),
        biased=biased,
        width=bias_table.shape[1] if biased else None,
        hoist_scale=hoist_scale, fuse_bias=fuse_bias,
        residuals=return_residuals)
    args = (idx, block_idx.astype(jnp.int32).reshape(-1), qt, kt, vt)
    if biased:
        args += (buckets, bias_table.astype(F32))

    _PALLAS_CALLS[0] += 1
    res = pl.pallas_call(
        kernel, grid_spec=grid_spec, out_shape=out_shape,
        interpret=interpret)(*args)
    out = jnp.moveaxis(res[0].reshape(B, H, S, Dh), 1, 2)
    return (out, res[1]) if return_residuals else out
