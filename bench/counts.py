"""Operations and bytes the benchmark charges to a step and to each
attention kernel: the yardstick for ``mfu`` and ``attn_kernel_roofline``.

The counts describe the work the algorithm requires, whatever implements
it:

* attention over the admitted (query, key) pairs of the active layout
  (bucket >= 0 in a live slot) in sparse steps, all S^2 pairs in dense
  steps, at the published ``d_head`` with no lane padding;
* projections, FFN, input embedding and head from the config's widths;
* the backward as twice the forward; recomputation is not counted.

Kernel bytes are the operands each kernel must read or write once: Q, K,
V, O, dO, dQ, dK, dV at the activation width, the f32 row statistics
(lse, delta) and one bucket byte per admitted pair. None of it depends on
the block size, the grid or padding, so a change that removes dead grid
slots or packs heads is judged on the same count.
"""

from __future__ import annotations

import numpy as np


def admitted_pairs(block_idx, buckets, bq: int, bk: int) -> int:
    """(query, key) pairs the layout admits: bucket >= 0 inside a live
    slot (block_idx >= 0). Without buckets a live slot admits its whole
    bq x bk tile."""
    bi = np.asarray(block_idx)
    live = bi >= 0
    if buckets is None:
        return int(live.sum()) * bq * bk
    bu = np.asarray(buckets)
    return int((bu[live] >= 0).sum())


def live_share(block_idx) -> float:
    """Live ``block_idx`` slots over all slots of the padded grid."""
    bi = np.asarray(block_idx)
    return float((bi >= 0).sum()) / bi.size


def forward_flops(m: dict, seq: int, attn_pairs: int) -> float:
    """One forward pass over a sequence of ``seq`` positions whose
    attention admits ``attn_pairs`` pairs per head and layer. ``m`` is a
    configuration's ``model`` block (bench/configs/*.json) plus its
    ``lap_pe_dim``."""
    D, H, Dh = m["d_model"], m["n_heads"], m["d_head"]
    KV = m.get("n_kv_heads") or H
    per_tok = 2 * m["feat_dim"] * D + 2 * m.get("lap_pe_dim", 0) * D \
        + 2 * D * m["n_classes"]
    per_layer_tok = 2 * D * (H + 2 * KV) * Dh + 2 * H * Dh * D \
        + 3 * 2 * D * m["d_ff"]
    attn = 4 * Dh * H * attn_pairs
    return float(seq * per_tok
                 + m["n_layers"] * (seq * per_layer_tok + attn))


def step_flops(m: dict, seq: int, attn_pairs: int) -> float:
    """Forward and backward (twice the forward) of one training step."""
    return 3.0 * forward_flops(m, seq, attn_pairs)


def attn_kernel_counts(*, seq: int, heads: int, kv_heads: int,
                       d_head: int, pairs: int,
                       act_bytes: int = 2) -> dict:
    """{kernel: (flops, bytes)} for one call of each cluster-attention
    kernel over ``heads`` query heads (the heads one device holds).

    fwd computes S = QK^T and O = PV (4 d_head per pair and head); the
    backward's required work is twice that, split as dQ (dP = dO V^T,
    dQ = dS K) and dK/dV (dV = P^T dO, dK = dS^T Q)."""
    q = seq * heads * d_head * act_bytes
    kv = seq * kv_heads * d_head * act_bytes
    stats = seq * heads * 4                  # one f32 per row and head
    f = 4.0 * d_head * heads * pairs
    return {
        "fwd": (f, q + 2 * kv + q + stats + pairs),
        "dq": (f, q + 2 * kv + q + 2 * stats + pairs + q),
        "dkv": (f, q + 2 * kv + q + 2 * stats + pairs + 2 * kv),
    }


def least_seconds(flops: float, nbytes: float, peaks: dict):
    """(least time, bound) on one chip: the larger of the compute and the
    memory time, and which of the two it is."""
    tc = flops / peaks["bf16_flops"]
    tm = nbytes / peaks["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
