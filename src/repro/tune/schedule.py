"""Schedule contract of the kernel autotuner.

A :class:`Schedule` is everything the dispatch layer may legally vary
about a kernel launch without changing its math: block sizes where the
kernel owns them (flash ``block_q``/``block_k``, SSD ``chunk``), the
oracle's q-row chunking (``row_chunk`` — the cluster kernel's block
shape is baked into the reformation layout and is NOT tunable here), and
the dataflow rewrites applied inside the kernel bodies:

``hoist_scale``
    multiply the softmax scale onto Q once per q-tile *before* the
    k-loop instead of scaling every (bq, bk) score tile — the
    egglog-for-kernels rewrite (ROADMAP item 3). Applied to the flash
    and cluster kernels, forward and recomputation backward (both must
    rebuild identical scores).
``fuse_bias``
    fold the bucket-bias masking select into the table lookup: the
    bias table grows a trailing ``NEG_INF`` sentinel column
    (``kernels/cluster_attention.extend_bias_table``) and the masked
    ``bkt = -1`` entries wrap onto it (``jnp.take(..., mode="wrap")``),
    so the inner loop runs ``s + bias`` with no ``jnp.where`` pair.
    Exact in fp32: ``s + NEG_INF == NEG_INF`` for every finite score
    the kernels produce (|s| < 1e23). ``-1`` is the ONLY negative
    sentinel the layout builders emit; ``-2`` would misroute.

``DEFAULT_SCHEDULES`` is the single home of the block-size constants
that used to be hard-coded per kernel signature (lint rule REP007
forbids re-introducing literals under ``repro/kernels/``). Winner tables
(:mod:`repro.tune.table`) override these per shape bucket; dispatch
falls back here whenever no entry matches.

The enumerator validates every candidate through the PR 8 pallas grid
auditor (``analysis.ir.pallas_check``) against the exact
(grid, index_map, shapes) triple the launch would use — illegal
schedules are pruned before ever being timed, never crashed on.
"""

from __future__ import annotations

import dataclasses

import numpy as np

# bump when the Schedule fields / bucket key format / rewrite semantics
# change: tables recorded under another version are stale and dispatch
# warns + falls back to DEFAULT_SCHEDULES instead of misreading them
SCHEDULE_CACHE_VERSION = 1

_FIELD_DOC = {
    "block_q": "flash q-tile rows",
    "block_k": "flash k-tile cols",
    "chunk": "SSD scan chunk / serve prefill chunk",
    "row_chunk": "cluster oracle q-row chunk",
    "hoist_scale": "scale Q once before the k-loop",
    "fuse_bias": "sentinel-column bias lookup, no where-pair",
}


@dataclasses.dataclass(frozen=True)
class Schedule:
    """One legal launch configuration for one op (unused fields None)."""

    op: str
    block_q: int | None = None
    block_k: int | None = None
    chunk: int | None = None
    row_chunk: int | None = None
    hoist_scale: bool = False
    fuse_bias: bool = False

    def to_json(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_json(cls, d: dict) -> "Schedule":
        """Tolerant of unknown keys (newer writers) — version skew is
        handled one level up by the table's version field."""
        known = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in known})

    def describe(self) -> str:
        parts = [f"{k}={getattr(self, k)}" for k in _FIELD_DOC
                 if getattr(self, k) not in (None, False)]
        return f"{self.op}({', '.join(parts) or 'defaults'})"


# the ONE home of the block-size constants (REP007): kernels take these
# as required arguments, dispatch resolves winner-table -> this dict
DEFAULT_SCHEDULES: dict[str, Schedule] = {
    "flash_attention": Schedule("flash_attention", block_q=128, block_k=128),
    "cluster_attention": Schedule("cluster_attention", row_chunk=8),
    "ssd": Schedule("ssd", chunk=256),
    "paged_attention": Schedule("paged_attention", chunk=32),
}


def shape_bucket(op: str, *, seq_len: int, heads: int | None = None,
                 d_head: int | None = None, dtype="float32") -> str:
    """Winner-table key: op + pow2-bucketed sequence length + head
    geometry + dtype. Sequences bucket to the next power of two so a
    244-token graph and a 250-token graph share one entry (schedules
    are not that shape-sensitive; the table stays small)."""
    s = 1 << max(0, int(seq_len) - 1).bit_length()
    parts = [op, f"S{s}"]
    if heads:
        parts.append(f"H{int(heads)}")
    if d_head:
        parts.append(f"D{int(d_head)}")
    parts.append(np.dtype(dtype).name)
    return "/".join(parts)


# ------------------------------------------------------------ enumerator

_LANE = 128
_SUBLANE = 8


def _audit_triple(triple: dict, scalar_prefetch=(), label="") -> str | None:
    """Run the PR 8 grid auditor on a launch triple; return the first
    error-finding message (candidate is illegal) or None (legal)."""
    from repro.analysis.ir import errors as _ir_errors
    from repro.analysis.ir import pallas_check
    try:
        findings = pallas_check.audit_grid(
            triple["grid"], triple["in_specs"], triple["out_specs"],
            triple["in_shapes"], triple["out_shapes"],
            scalar_prefetch=scalar_prefetch, label=label)
    # pruning, never crashing: the reason string rejects the candidate
    except Exception as e:  # noqa: BLE001  # repro-lint: disable=REP008
        return f"grid audit raised: {e!r}"
    bad = _ir_errors(findings)
    return bad[0].message if bad else None


def _flash_triple(B, Sq, Sk, H, KV, Dh, bq, bk) -> dict:
    """The flash forward launch triple (mirrors kernels/flash_attention)
    in the duck-typed shape ``audit_grid`` consumes."""
    import jax.experimental.pallas as pl

    G = H // KV
    nq, nk = -(-Sq // bq), -(-Sk // bk)
    sq_p, sk_p = nq * bq, nk * bk

    def kv_map(bh, qi, ki):
        return ((bh // H) * KV + (bh % H) // G, ki, 0)

    return {
        "grid": (B * H, nq, nk),
        "in_specs": [
            pl.BlockSpec((1, bq, Dh), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, bk, Dh), kv_map),
            pl.BlockSpec((1, bk, Dh), kv_map),
        ],
        "out_specs": [pl.BlockSpec((1, bq, Dh),
                                   lambda bh, qi, ki: (bh, qi, 0))],
        "in_shapes": [(B * H, sq_p, Dh), (B * KV, sk_p, Dh),
                      (B * KV, sk_p, Dh)],
        "out_shapes": [(B * H, sq_p, Dh)],
    }


def enumerate_schedules(op: str, case: dict) -> list[Schedule]:
    """Legal candidate schedules for ``op`` on ``case`` (a dict from
    :mod:`repro.tune.cases` carrying the concrete shapes — and for the
    cluster op the concrete layout, whose scalar-prefetch stream the
    auditor replays). Illegal candidates are pruned silently; the
    hard-coded default is always candidate 0 so search can never return
    an empty set or lose to the status quo by omission."""
    default = DEFAULT_SCHEDULES[op]
    out = [default]

    if op == "flash_attention":
        B, S, H, KV, Dh = (case["B"], case["seq_len"], case["heads"],
                           case.get("kv_heads", case["heads"]),
                           case["d_head"])
        dh_pad = Dh + (-Dh % _LANE)
        for bq in (32, 64, 128, 256):
            for bk in (32, 64, 128, 256):
                if bq % _SUBLANE or bk % _SUBLANE:
                    continue
                if _audit_triple(_flash_triple(
                        B, S, S, H, KV, dh_pad, min(bq, S), min(bk, S)),
                        label=f"tune:flash:{bq}x{bk}"):
                    continue
                for hoist in (False, True):
                    cand = Schedule(op, block_q=bq, block_k=bk,
                                    hoist_scale=hoist)
                    if cand != default:
                        out.append(cand)

    elif op == "cluster_attention":
        # block shape is the layout's; candidates vary the rewrites and
        # the oracle row_chunk. fuse_bias changes the bias operand width
        # (sentinel column), so each flag combo gets its own grid audit.
        import jax
        import jax.numpy as jnp

        from repro.kernels import ops as kops

        lay = case["lay"]
        B, H, Dh = case.get("B", 1), case["heads"], case["d_head"]
        KV = case.get("kv_heads", H)
        S = case["seq_len"]
        nq, mb = lay.block_idx.shape[-2:]
        bk = lay.buckets.shape[-1] if lay.buckets is not None else S // nq
        bi = np.asarray(lay.block_idx, np.int32).reshape((-1, nq, mb))[:1]
        bi = np.broadcast_to(bi, (B, nq, mb))
        with jax.core.eval_context():
            stream, n = kops.fwd_stream(jnp.asarray(bi), interpret=True)
        prefetch = (np.asarray(stream), bi.reshape(-1))
        nb = case.get("n_buckets", getattr(lay, "n_buckets", None))
        for fuse in (False, True):
            if fuse and nb is None:
                continue
            triple = kops.grid_triple(
                B, S, H, KV, Dh + (-Dh % _LANE), nq, mb, int(n), bk=bk,
                per_graph=True,
                n_buckets=(nb + 1 if fuse else nb) if nb else None,
                return_residuals=True)
            if _audit_triple(triple, scalar_prefetch=prefetch,
                             label=f"tune:cluster:fuse={fuse}"):
                continue
            for hoist in (False, True):
                for rc in (4, 8, 16):
                    if nq % min(rc, nq):
                        continue
                    cand = Schedule(op, row_chunk=rc, hoist_scale=hoist,
                                    fuse_bias=fuse)
                    if cand != default:
                        out.append(cand)

    elif op == "ssd":
        S = case["seq_len"]
        for chunk in (64, 128, 256, 512):
            if S % min(chunk, S):
                continue  # kernel requires the chunk to tile the sequence
            cand = Schedule(op, chunk=chunk)
            if cand != default:
                out.append(cand)

    elif op == "paged_attention":
        for chunk in (16, 32, 64):
            cand = Schedule(op, chunk=chunk)
            if cand != default:
                out.append(cand)
    else:
        raise ValueError(f"unknown op {op!r}")
    return out
