"""Kernel dispatch layer: one call site per op, three execution paths.

Every attention/SSD call in the codebase goes through this module instead
of picking an implementation at the call site. Each public op —
``flash_attention``, ``cluster_attention``, ``ssd``,
``paged_attention`` — resolves an
*execution mode* at call (trace) time and then either runs the Pallas
kernel or the pure-jnp oracle with identical semantics:

``ref``
    The jnp oracle (``kernels/ref.py`` / ``core/dual_attention.py``).
    Exact same math, no Pallas. Default on CPU/GPU backends.
``interpret``
    The Pallas kernel body executed by the Pallas interpreter — kernel
    semantics (block pipeline, online softmax, scalar prefetch) on any
    backend. This is how the kernels run in CI and inside the sharded
    path on the fake-device CPU mesh.
``compiled``
    The Pallas kernel compiled for TPU — the production path. Requires a
    TPU backend; without one the op raises (a forced device path never
    silently runs the oracle on the host).
``auto``
    ``compiled`` on TPU, ``ref`` elsewhere. The default.

Mode resolution, highest priority first:

1. per-op environment override: ``REPRO_FORCE_PALLAS_FLASH`` /
   ``REPRO_FORCE_PALLAS_CLUSTER`` / ``REPRO_FORCE_PALLAS_SSD`` /
   ``REPRO_FORCE_PALLAS_PAGED``;
2. process-wide environment override: ``REPRO_FORCE_PALLAS``;
3. per-op programmatic override: ``set_mode(mode, op)``;
4. process-wide programmatic override: ``set_mode(mode)`` — this is what
   ``TrainerConfig.attn_impl`` / ``launch/train.py --attn-impl`` set;
5. ``auto``.

Environment beats config on purpose: a test or an operator can force a
path without editing any call site. ``dispatch_table()`` reports the
effective mode per op for logging.

Legality and fallback policy. ``compiled`` without a TPU backend raises
``RuntimeError`` at call (trace) time. Every other illegal corner warns
and falls back to the oracle:

* cluster block shapes that violate TPU tiling — ``bq``/``bk`` not a
  multiple of the fp32 sublane (8), or a sequence the block rows don't
  tile — -> ``ref`` (block sizes are baked into the layout, so they
  cannot be padded here);
* ``causal=True`` together with bucket masks -> ``ref`` (the bucketed
  kernel variant carries masking in the buckets and has no causal path);
* a head dim that is not lane-aligned (128) is *padded*, not rejected:
  q/k/v are zero-padded on the lane axis (q pre-scaled so the kernel's
  softmax scale still equals ``Dh**-0.5``) and the output is sliced back.

The legality check is **vjp-aware**: kernel-mode calls are routed through
the ``custom_vjp``-wrapped kernels (``cluster_attention_bwd`` /
``flash_attention_vjp``), so ``jax.grad`` stays on the kernel path —
corners the backward kernels cannot serve (non-float q/k/v, a malformed
transposed layout) fall back to the differentiable-by-construction jnp
oracle with a RuntimeWarning *at call time*, instead of raising later
under ``grad``.

Shape contract of ``cluster_attention`` (the sharded path's ``attn_fn``):
``(q, k, v, block_idx, buckets, bias_table, block_idx_t)`` with q
``(B, S, H, Dh)``, k/v ``(B, S, KV, Dh)``; ``block_idx`` either
``(nq, mb)`` (one layout shared by the batch — LM local+global mode) or
``(B, nq, mb)`` (per-graph layouts — ONE batched ``pallas_call``, the
scalar-prefetch grid carries the batch dim; the ref path consumes the
batch dim directly). ``buckets`` carries the extra leading batch dim iff
``block_idx`` does; ``bias_table`` is ``(H, n_buckets)`` where ``H`` is
the *local* head count — under the sharded path each device passes its
own head chunk of the table. ``block_idx_t`` is the transposed pattern
``(nk, mt, 2)`` / ``(B, nk, mt, 2)`` the dK/dV backward kernel consumes
(``core/reformation.transpose_block_idx``); when omitted, the backward
derives one in-trace at the dense ``mt = nq`` bound — which requires
duplicate-free rows (no q-row listing the same k-block twice; layout
builders guarantee this, concrete violations warn-and-fall-back, and a
*traced* custom layout with duplicates must thread ``block_idx_t``).

The kernels never walk that rectangle: they compact both layouts in-trace
to streams of their live slots (``kernels/cluster_attention.fwd_stream``,
``dkv_stream``) and take the live count as a dynamic grid bound, so a
call's grid steps follow the live count of the layout in hand. Both
layouts are ``-1`` *padded*: each row's live slots come first, as every
layout builder writes them (a concrete layout with a gap falls back to
the oracle with a warning; a traced one must not have one). Every q-row,
k-block and slot count must stay under ``FIELD_MAX`` (2**13).
"""

from __future__ import annotations

import os
import warnings

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.dual_attention import cluster_sparse_attention
from repro.kernels import cluster_attention as _ca
from repro.kernels import cluster_attention_bwd as _cab
from repro.kernels import flash_attention as _fa
from repro.kernels import ref as _ref
from repro.kernels import ssd as _ssd
from repro.kernels.policy import F32

# re-exported for the autotuner: the forward launch contract lives in ONE
# place (kernels/cluster_attention.grid_triple, fed by the compacted
# stream of fwd_stream) and the dispatch layer is the kernels package's
# public surface — REP002 keeps everything outside repro/kernels/ off the
# kernel modules themselves
grid_triple = _ca.grid_triple
fwd_stream = _ca.fwd_stream

MODES = ("auto", "ref", "interpret", "compiled")
OPS = ("flash_attention", "cluster_attention", "ssd", "paged_attention")

_ENV_GLOBAL = "REPRO_FORCE_PALLAS"
_ENV_PER_OP = {
    "flash_attention": "REPRO_FORCE_PALLAS_FLASH",
    "cluster_attention": "REPRO_FORCE_PALLAS_CLUSTER",
    "ssd": "REPRO_FORCE_PALLAS_SSD",
    "paged_attention": "REPRO_FORCE_PALLAS_PAGED",
}

LANE = 128     # TPU lane width: the last dim of every VMEM tile
SUBLANE = 8    # fp32 sublane: granularity of the second-to-last tile dim

_overrides: dict[str, str] = {}   # op name or "*" -> mode


def _check_mode(mode: str):
    if mode not in MODES:
        raise ValueError(f"mode {mode!r} not in {MODES}")


def set_mode(mode: str, op: str | None = None):
    """Programmatic dispatch override: ``set_mode("interpret")`` routes all
    ops through the Pallas interpreter; ``set_mode("ref", "ssd")`` pins one
    op. ``"auto"`` clears the corresponding override. Environment overrides
    (see module docstring) still take precedence."""
    _check_mode(mode)
    if op is not None and op not in OPS:
        raise ValueError(f"op {op!r} not in {OPS}")
    key = op or "*"
    if mode == "auto":
        _overrides.pop(key, None)
    else:
        _overrides[key] = mode


def resolve_mode(op: str) -> str:
    """Effective execution mode for ``op`` right now: first set of per-op
    env, global env, per-op ``set_mode``, global ``set_mode``; then
    ``auto`` = compiled-on-TPU / ref-elsewhere."""
    for mode in (os.environ.get(_ENV_PER_OP[op], ""),
                 os.environ.get(_ENV_GLOBAL, ""),
                 _overrides.get(op, ""),
                 _overrides.get("*", "")):
        if mode:
            _check_mode(mode)
            break
    else:
        mode = "auto"
    if mode == "auto":
        return "compiled" if jax.default_backend() == "tpu" else "ref"
    return mode


def dispatch_table() -> dict[str, str]:
    """{op: effective mode} — for launch-time logging and tests."""
    return {op: resolve_mode(op) for op in OPS}


def _fallback(op: str, reason: str):
    warnings.warn(
        f"repro.kernels.ops: {op}: falling back to the jnp reference path "
        f"({reason})", RuntimeWarning, stacklevel=3)


def _require_tpu(op: str, mode: str):
    """``compiled`` is a device path: without a TPU it raises instead of
    quietly running the oracle on the host."""
    if mode == "compiled" and jax.default_backend() != "tpu":
        raise RuntimeError(
            f"repro.kernels.ops: {op}: mode=compiled but no TPU backend is "
            f"attached (backend={jax.default_backend()!r}); use "
            f"'interpret' or 'ref' off the chip")


def _nonfloat(q, k, v) -> str | None:
    for name, x in (("q", q), ("k", k), ("v", v)):
        if not jnp.issubdtype(x.dtype, jnp.floating):
            return f"kernel vjp path needs floating-point q/k/v, " \
                   f"{name} is {x.dtype}"
    return None


# ------------------------------------------------- trace-time memo tables
#
# Dispatch decisions are host-side and happen once per TRACE, but eager
# interpret-mode loops re-enter dispatch per call — both memos keep the
# hot path allocation-free (no fresh tuple/string/float objects per call).

# (op, seq_len, heads, d_head, dtype, tune-generation) -> Schedule. The
# generation component makes a winner-table refresh() invalidate every
# entry without touching jit caches (see repro.tune.runtime).
_SCHED_MEMO: dict = {}

# (d_head, dtype) -> (pad, pre-scale): the lane-padding decision per
# head-dim/dtype, computed once
_PAD_MEMO: dict = {}


def resolve_schedule(op: str, *, seq_len: int, heads: int | None = None,
                     d_head: int | None = None, dtype="float32"):
    """The effective :class:`repro.tune.schedule.Schedule` for this
    op/shape right now: winner table first (warn-and-fallback on any
    miss/stale/corrupt state — never raises), ``DEFAULT_SCHEDULES``
    otherwise. Memoized per shape signature and tune generation, so a
    mid-training table refresh changes what FUTURE traces resolve while
    existing jitted programs keep their baked-in schedule."""
    from repro.tune import runtime as _tune_rt
    key = (op, int(seq_len), heads, d_head, str(dtype),
           _tune_rt.generation())
    sched = _SCHED_MEMO.get(key)
    if sched is None:
        from repro.tune.schedule import shape_bucket
        if len(_SCHED_MEMO) > 4096:   # stale generations never hit again
            _SCHED_MEMO.clear()
        bucket = shape_bucket(op, seq_len=seq_len, heads=heads,
                              d_head=d_head, dtype=dtype)
        sched = _tune_rt.lookup(op, bucket)
        _SCHED_MEMO[key] = sched
    return sched


def _sched_field(sched, name: str):
    """A schedule field with the op-default as backstop (a hand-written
    table entry may omit fields; dispatch must still resolve)."""
    val = getattr(sched, name)
    if val is None:
        from repro.tune.schedule import DEFAULT_SCHEDULES
        val = getattr(DEFAULT_SCHEDULES[sched.op], name)
    return val


def _pad_plan(dh: int, dtype) -> tuple:
    key = (int(dh), str(dtype))
    plan = _PAD_MEMO.get(key)
    if plan is None:
        pad = -dh % LANE
        scale = float(((dh + pad) / dh) ** 0.5) if pad else 1.0
        plan = (pad, scale)
        _PAD_MEMO[key] = plan
    return plan


def _pad_lanes(q, k, v):
    """Zero-pad the head (lane) dim of q/k/v up to a multiple of LANE and
    return an un-pad function for the output. The kernels derive their
    softmax scale from the padded Dh, so q is pre-scaled by
    ``sqrt(Dh_padded / Dh)`` to keep the effective scale at ``Dh**-0.5``;
    zero lanes contribute nothing to q.k or to the sliced-off output.
    The (pad, scale) decision is memoized per (d_head, dtype)."""
    dh = q.shape[-1]
    pad, scale = _pad_plan(dh, q.dtype)
    if not pad:
        return q, k, v, lambda o: o
    q = q * scale
    width = ((0, 0),) * (q.ndim - 1) + ((0, pad),)
    return (jnp.pad(q, width), jnp.pad(k, width), jnp.pad(v, width),
            lambda o: o[..., :dh])


# --------------------------------------------------------------- flash

def flash_attention(q, k, v, *, causal=True, block_q=None, block_k=None):
    """Dense flash attention. q ``(B, Sq, H, Dh)``, k/v ``(B, Sk, KV, Dh)``.
    The Pallas path pads ragged sequence tails and non-lane-aligned head
    dims itself and is differentiable (``flash_attention_vjp``); a missing
    TPU raises under ``compiled``; non-float inputs force the ref
    fallback.

    ``block_q``/``block_k`` default to the autotuner's answer for this
    shape bucket (winner table if one is installed, else
    ``DEFAULT_SCHEDULES``); passing them explicitly overrides the tile
    sizes while rewrite flags (``hoist_scale``) still come from the
    resolved schedule."""
    mode = resolve_mode("flash_attention")
    _require_tpu("flash_attention", mode)
    reason = _nonfloat(q, k, v) if mode != "ref" else None
    if reason:
        _fallback("flash_attention", reason)
        mode = "ref"
    if mode == "ref":
        return _ref.flash_attention_ref(q, k, v, causal=causal)
    sched = resolve_schedule("flash_attention", seq_len=q.shape[1],
                             heads=q.shape[2], d_head=q.shape[3],
                             dtype=q.dtype)
    if block_q is None:
        block_q = _sched_field(sched, "block_q")
    if block_k is None:
        block_k = _sched_field(sched, "block_k")
    q, k, v, unpad = _pad_lanes(q, k, v)
    return unpad(_fa.flash_attention_vjp(q, k, v, causal=causal,
                                         block_q=block_q, block_k=block_k,
                                         interpret=(mode == "interpret"),
                                         hoist_scale=sched.hoist_scale))


# --------------------------------------------------------------- cluster

def _cluster_illegal(q, k, v, block_idx, buckets, causal, mode, want_bq,
                     want_bk, block_idx_t=None) -> str | None:
    """Reason the Pallas cluster kernel cannot run this call, or None.
    Block sizes are baked into the layout (they index the pattern), so
    violations here fall back to ref rather than padding. The kernel
    derives bq = S // nq and bk from buckets (= bq without them); caller
    overrides it cannot honor are rejected so ref and kernel modes never
    silently compute different things. The check is vjp-aware: anything
    the recomputation backward cannot serve (non-float inputs, a
    malformed transposed layout) is rejected here, at call time, so
    ``jax.grad`` falls back instead of raising mid-trace."""
    if block_idx.ndim not in (2, 3):
        return f"block_idx must be (nq, mb) or (B, nq, mb), got " \
               f"{block_idx.ndim}-d"
    S = q.shape[1]
    nq = block_idx.shape[-2]
    if S % nq:
        return f"sequence {S} is not tiled by {nq} q-block rows"
    bq = S // nq
    bk = buckets.shape[-1] if buckets is not None else bq
    if want_bq is not None and want_bq != bq:
        return f"kernel derives bq={bq} but caller requires bq={want_bq}"
    if want_bk is not None and want_bk != bk:
        return f"kernel derives bk={bk} but caller requires bk={want_bk}"
    if S % bk:
        return f"sequence {S} is not tiled by k-blocks of {bk}"
    if bq % SUBLANE or bk % SUBLANE:
        return f"block shape ({bq}, {bk}) is not sublane-aligned " \
               f"(multiples of {SUBLANE})"
    widths = (nq, S // bk, block_idx.shape[-1])
    if max(widths) >= _ca.FIELD_MAX:
        return f"layout rows and slots {widths} overflow the kernels' " \
               f"stream fields (< {_ca.FIELD_MAX})"
    if causal and buckets is not None:
        return "the bucketed kernel variant has no causal mask"
    if buckets is not None and buckets.ndim != block_idx.ndim + 2:
        return f"buckets rank {buckets.ndim} does not match block_idx " \
               f"rank {block_idx.ndim}"
    reason = _nonfloat(q, k, v)
    if reason:
        return reason
    for name, arr in (("block_idx", block_idx), ("block_idx_t", None if
                      block_idx_t is None else block_idx_t[..., 0])):
        if arr is None or isinstance(arr, jax.core.Tracer):
            continue
        # the kernels take entry j of a row as its slot j: live slots
        # first, -1 padding after (every layout builder's form). Concrete
        # numpy only, as below.
        live = np.asarray(arr) >= 0
        # repro-lint: disable=REP004
        if bool((live[..., 1:] & ~live[..., :-1]).any()):
            return f"{name} has a live slot after a -1 in its row: the " \
                   f"kernels need each row's live slots first"
    if block_idx_t is None and not isinstance(block_idx, jax.core.Tracer):
        # the in-trace derived transposed layout stores one visitor per
        # (q-row, k-block) — a row listing the same k-block twice cannot
        # be represented at the dense mt = nq bound. The layout builders
        # never emit duplicates, and this host scan catches every
        # concrete hand-built one; a TRACED duplicate layout without
        # block_idx_t is undetectable at trace time and is a documented
        # contract violation (thread the host-built transposed layout).
        # Cost note: the sync+sort below runs only on eager concrete
        # calls — jitted training passes tracers and never pays it.
        srt = np.sort(np.asarray(block_idx).reshape(-1,
                                                    block_idx.shape[-1]),
                      axis=1)
        # concrete numpy only: the enclosing branch excludes tracers, so
        # this bool() can never hit a traced value.
        # repro-lint: disable=REP004
        if bool(((srt[:, 1:] == srt[:, :-1]) & (srt[:, 1:] >= 0)).any()):
            return "a q-row visits the same k-block twice: the derived " \
                   "transposed layout cannot represent duplicates — " \
                   "pass block_idx_t"
    if block_idx_t is not None:
        if block_idx_t.ndim != block_idx.ndim + 1 or \
                block_idx_t.shape[-1] != 2:
            return f"transposed layout must be (..., nk, mt, 2) with the " \
                   f"batch dim of block_idx, got shape " \
                   f"{tuple(block_idx_t.shape)}"
        if block_idx_t.shape[-3] != S // bk:
            return f"transposed layout has {block_idx_t.shape[-3]} " \
                   f"k-block rows, sequence {S} has {S // bk}"
        if block_idx.ndim == 3 and \
                block_idx_t.shape[0] != block_idx.shape[0]:
            return f"transposed layout batch {block_idx_t.shape[0]} != " \
                   f"block_idx batch {block_idx.shape[0]}"
    return None


# layouts already grid-audited this process: (dims, layout-bytes) keys —
# eager interpret calls re-use layouts heavily and the enumeration is
# O(grid cells), so never audit the same launch twice
_GRID_AUDITED: set = set()


def _grid_race_reason(q, k, block_idx, buckets, bias_table,
                      fuse_bias=False) -> str | None:
    """Dispatch-time pallas grid audit (interpret/debug mode, or any
    mode under REPRO_IR_AUDIT): check the forward (grid, index_map,
    out_shape) triple — the exact one ``grid_triple`` hands to
    pallas_call — against the concrete scalar-prefetch stream. A traced
    ``block_idx`` cannot be audited statically (its gather targets are
    data-dependent): skip, like the duplicate-row scan above.
    ``fuse_bias`` widens the audited bias table by the sentinel column
    the fused launch appends. Returns a fallback reason on error
    findings (never raises — dispatch policy)."""
    if isinstance(block_idx, jax.core.Tracer):
        return None
    from repro.analysis.ir import errors as _ir_errors
    from repro.analysis.ir import pallas_check

    B, S, H, Dh = q.shape
    KV = k.shape[2]
    nq, mb = block_idx.shape[-2:]
    bq = S // nq
    bk = buckets.shape[-1] if buckets is not None else bq
    arr = np.asarray(block_idx, np.int32)
    per_graph = arr.ndim == 3
    n_buckets = None
    if buckets is not None:
        n_buckets = bias_table.shape[1] + (1 if fuse_bias else 0)
    key = (B, S, H, KV, Dh, nq, mb, bk, per_graph, n_buckets,
           hash(arr.tobytes()))
    if key in _GRID_AUDITED:
        return None
    with jax.core.eval_context():          # concrete inside any trace
        stream, n = fwd_stream(jnp.asarray(arr if per_graph else arr[None]),
                               interpret=True)
    triple = grid_triple(B, S, H, KV, Dh + (-Dh % LANE), nq, mb, int(n),
                         bk=bk, per_graph=per_graph,
                         n_buckets=n_buckets, return_residuals=True)
    findings = pallas_check.audit_grid(
        triple["grid"], triple["in_specs"], triple["out_specs"],
        triple["in_shapes"], triple["out_shapes"],
        scalar_prefetch=(np.asarray(stream), arr.reshape(-1)),
        label="cluster_attention")
    bad = _ir_errors(findings)
    if bad:
        return f"pallas grid audit: {bad[0].message}"
    _GRID_AUDITED.add(key)
    return None


def _cluster_ref(q, k, v, block_idx, buckets, bias_table, *, causal,
                 row_chunk, bq, bk):
    if block_idx.ndim == 2:
        block_idx = jnp.broadcast_to(block_idx[None],
                                     (q.shape[0],) + block_idx.shape)
        if buckets is not None:
            buckets = jnp.broadcast_to(buckets[None],
                                       (q.shape[0],) + buckets.shape)
    nq = block_idx.shape[1]
    bq = bq or q.shape[1] // nq
    bk = bk or (buckets.shape[-1] if buckets is not None else bq)
    return cluster_sparse_attention(q, k, v, block_idx, buckets, bias_table,
                                    bq=bq, bk=bk, causal=causal,
                                    row_chunk=row_chunk)


def cluster_streams(block_idx, block_idx_t, *, seq_len: int, bk: int):
    """The compacted streams of one layout for the kernel path
    (``kernels/cluster_attention_bwd.build_streams``), or None where
    :func:`cluster_attention` resolves to the reference. A model that
    runs every layer on one layout builds them once, outside its layer
    loop, and passes them to each call: built inside a rematerialized
    layer, the backward would rebuild them per layer. Calls that fall
    back to the reference ignore them."""
    mode = resolve_mode("cluster_attention")
    if mode == "ref":
        return None
    _require_tpu("cluster_attention", mode)
    return _cab.build_streams(block_idx, block_idx_t, seq_len // bk,
                              interpret=mode == "interpret")


def cluster_attention(q, k, v, block_idx, buckets=None, bias_table=None,
                      block_idx_t=None, *, causal=False, row_chunk=None,
                      bq=None, bk=None, streams=None):
    """Cluster-sparse attention over a reformation layout — the production
    ``attn_fn`` of ``parallel/cluster_parallel.py`` (shape contract in the
    module docstring). ``bq``/``bk`` are only needed when they cannot be
    implied (``bq = S // nq``, ``bk`` from buckets); ``row_chunk`` tunes
    the ref path's q-row chunking (ignored by the kernel) and defaults to
    the autotuner's answer for this shape bucket, as do the schedule
    rewrite flags (``hoist_scale``/``fuse_bias``) applied on the kernel
    path.

    The kernel path is differentiable end-to-end (``custom_vjp`` with
    FlashAttention-style recomputation — kernels/cluster_attention_bwd);
    ``block_idx_t`` is the transposed layout its dK/dV kernel consumes
    (derived in-trace at the dense bound when omitted; the ref path never
    needs it). Per-graph (3-D) layouts run as ONE batched pallas_call.
    ``streams`` (:func:`cluster_streams` of this layout) saves the kernel
    path building them inside the call."""
    mode = resolve_mode("cluster_attention")
    _require_tpu("cluster_attention", mode)
    sched = resolve_schedule("cluster_attention", seq_len=q.shape[1],
                             heads=q.shape[2], d_head=q.shape[3],
                             dtype=q.dtype)
    if row_chunk is None:
        row_chunk = _sched_field(sched, "row_chunk")
    if mode != "ref":
        reason = _cluster_illegal(q, k, v, block_idx, buckets, causal,
                                  mode, bq, bk, block_idx_t)
        if reason is not None:
            _fallback("cluster_attention", reason)
            mode = "ref"
    if mode == "ref":
        return _cluster_ref(q, k, v, block_idx, buckets, bias_table,
                            causal=causal, row_chunk=row_chunk, bq=bq, bk=bk)

    interpret = mode == "interpret"
    # the fused lookup's sentinel column sits right after the table: a
    # caller without a table gets the 1-wide zero table below, whose
    # sentinel would mask every bucket id >= 1 — fuse only real tables
    fuse_bias = sched.fuse_bias and buckets is not None \
        and bias_table is not None
    block_idx = block_idx.astype(jnp.int32)
    if buckets is not None and bias_table is None:
        # zero bias; 1-wide table (bucket lookups clamp to row 0)
        bias_table = jnp.zeros((q.shape[2], 1), F32)
    if interpret or os.environ.get("REPRO_IR_AUDIT", ""):
        reason = _grid_race_reason(q, k, block_idx, buckets, bias_table,
                                   fuse_bias=fuse_bias)
        if reason is not None:
            _fallback("cluster_attention", reason)
            return _cluster_ref(q, k, v, block_idx, buckets, bias_table,
                                causal=causal, row_chunk=row_chunk,
                                bq=bq, bk=bk)
    q, k, v, unpad = _pad_lanes(q, k, v)
    return unpad(_cab.cluster_attention_vjp(
        q, k, v, block_idx, buckets, bias_table, block_idx_t,
        causal=causal, interpret=interpret,
        hoist_scale=sched.hoist_scale, fuse_bias=fuse_bias,
        streams=streams))


# --------------------------------------------------------------- paged

def paged_attention(q, k_pool, v_pool, block_tables, cache_len, *,
                    q_offset=None, window=0, n_global=0):
    """Paged-KV attention for the serving engine: every decode step and
    chunked-prefill chunk reads the shared physical block pool through a
    per-request block table (shape contract in
    ``kernels/ref.paged_attention_ref``). ``window``/``n_global`` apply
    the TorchGT cluster-sparse decode mask on this dispatch path.

    The block-table gather has no Pallas kernel yet — ``ref`` serves
    every resolved mode; ``interpret``/``compiled`` warn and fall back so
    forcing Pallas process-wide (``REPRO_FORCE_PALLAS``) never silently
    changes serving semantics (``compiled`` off a TPU raises, as for
    every op)."""
    mode = resolve_mode("paged_attention")
    _require_tpu("paged_attention", mode)
    if mode != "ref":
        _fallback("paged_attention",
                  "the paged block-table gather has no Pallas kernel "
                  "yet (ref is the only implementation)")
    return _ref.paged_attention_ref(q, k_pool, v_pool, block_tables,
                                    cache_len, q_offset=q_offset,
                                    window=window, n_global=n_global)


# --------------------------------------------------------------- ssd

def ssd(x, dt, a, b, c, *, chunk=None):
    """Mamba2 SSD chunked scan. ``chunk`` defaults to the autotuner's
    answer for this shape bucket (winner table first, else
    ``DEFAULT_SCHEDULES``). Falls back to ref when the sequence is not
    tiled by ``chunk``; ``compiled`` without a TPU raises."""
    if chunk is None:
        sched = resolve_schedule("ssd", seq_len=x.shape[1],
                                 heads=x.shape[2], d_head=x.shape[3],
                                 dtype=x.dtype)
        chunk = _sched_field(sched, "chunk")
    mode = resolve_mode("ssd")
    _require_tpu("ssd", mode)
    if mode != "ref" and x.shape[1] % chunk:
        _fallback("ssd", f"sequence {x.shape[1]} is not tiled by chunk "
                         f"{chunk}")
        mode = "ref"
    if mode == "ref":
        return _ref.ssd_ref(x, dt, a, b, c, chunk)
    return _ssd.ssd(x, dt, a, b, c, chunk=chunk,
                    interpret=(mode == "interpret"))
