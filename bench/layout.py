"""The admitted (query, key) pairs of a frozen layout, derived by the
benchmark from its own graph: the paper's elastic computation reformation
(section III-D), written from its description and importing nothing of
the program.

The sequence is the graph's nodes in the program's order (``perm``: the
node id at each position after the global tokens) with the global tokens
in front. Its pattern is the graph augmented with self loops, the chain
between neighbouring positions and every pair with a global token. The
sequence is cut into a grid of clusters of ``cs`` positions (the
configuration's ``reform.clusters`` along each side). A cluster whose
share of nonzero pairs lies under ``beta_thre`` is reformed: its pairs
are snapped to ``tile`` x ``tile`` tiles, the ceil(nnz / tile^2) densest
tiles are kept whole (ties to the lower tile row, then column) and the
other pairs are dropped. Other clusters keep their pairs exactly.

Buckets: 0 self (every node position's diagonal), 1 a kept pair of the
pattern, 2 a pair filled in by a kept tile, -1 not admitted.
"""

from __future__ import annotations

import numpy as np


def _ceil_to(x: int, m: int) -> int:
    return -(-x // m) * m


def augmented_pattern(n, src, dst, perm, ng: int):
    """(rows, cols, s0) of the augmented pattern in sequence positions."""
    inv = np.empty(n, np.int64)
    inv[perm] = np.arange(n)
    s0 = n + ng
    pos = np.arange(s0, dtype=np.int64)
    g = np.arange(ng, dtype=np.int64)
    r = np.concatenate([ng + inv[src], pos, pos[:-1], pos[1:],
                        np.repeat(g, s0), np.tile(pos, ng)])
    c = np.concatenate([ng + inv[dst], pos, pos[1:], pos[:-1],
                        np.tile(pos, ng), np.repeat(g, s0)])
    key = np.unique(r * s0 + c)
    return key // s0, key % s0, s0


def admitted(graph, perm, ng: int, S: int, beta_thre: float,
             clusters: int, tile: int, align: int) -> np.ndarray:
    """(S, S) int8 bucket of every pair, -1 where none is admitted.
    ``align``: the cluster side is a multiple of it (the kernel block)."""
    n, src, dst = graph[:3]
    r, c, s0 = augmented_pattern(n, src, dst, perm, ng)
    cs = _ceil_to(-(-S // clusters), align)
    kk = -(-S // cs)
    cid = (r // cs) * kk + c // cs
    nnz = np.bincount(cid, minlength=kk * kk)
    reformed = (nnz / float(cs) ** 2 < beta_thre) & (nnz > 0)
    moved = reformed[cid]

    out = np.full((S, S), -1, np.int8)
    kr, kc = r[~moved], c[~moved]
    out[kr, kc] = np.where(kr == kc, 0, 1)
    d = np.arange(s0)
    out[d, d] = 0

    # reformed clusters: count pairs per (cluster, tile), keep the densest
    tr, tc, tcl = r[moved] // tile, c[moved] // tile, cid[moved]
    nt = S // tile + 1
    key, cnt = np.unique((tcl * nt + tr) * nt + tc, return_counts=True)
    kcl, ktr, ktc = key // (nt * nt), (key // nt) % nt, key % nt
    order = np.lexsort((ktc, ktr, -cnt, kcl))
    kcl, ktr, ktc = kcl[order], ktr[order], ktc[order]
    first = np.searchsorted(kcl, kcl)          # first tile of its cluster
    rank = np.arange(kcl.size) - first
    budget = -(-nnz // (tile * tile))
    keep = rank < budget[kcl]
    off = np.arange(tile)
    rows = (ktr[keep] * tile)[:, None, None] + off[None, :, None]
    cols = (ktc[keep] * tile)[:, None, None] + off[None, None, :]
    rows, cols = np.broadcast_arrays(rows, cols)
    free = out[rows, cols] == -1
    out[rows[free], cols[free]] = 2
    return out


def program_dense(block_idx, buckets, S: int, bq: int, bk: int):
    """(S, S) int8: the program's layout (``block_idx`` slots and their
    per-position buckets) as the bucket of every pair, -1 elsewhere."""
    out = np.full((S // bq, bq, S // bk, bk), -1, np.int8)
    ii, mm = np.nonzero(block_idx >= 0)
    jj = block_idx[ii, mm]
    out[ii, :, jj, :] = 0 if buckets is None else buckets[ii, mm]
    return out.reshape(S, S)
