"""The graph generator behind the traffic mixes. A mix is a data file
(bench/traffic/<name>.json) naming a ``generator`` and its parameters;
the graph is made from the run's ``--seed`` alone.

* ``dcsbm``: a degree-corrected SBM that hits a target mean degree and
  edge homophily with heavy-tailed (power-law) degree propensities, for
  mixes that copy a real graph's published statistics.

It returns ``(n, src, dst, feat, labels)`` with symmetrised, deduplicated
int32 edges and no self loops.
"""

from __future__ import annotations

import numpy as np


def _symmetrize(n, src, dst):
    s = np.concatenate([src, dst]).astype(np.int64)
    d = np.concatenate([dst, src]).astype(np.int64)
    _, idx = np.unique(s * n + d, return_index=True)
    return s[idx].astype(np.int32), d[idx].astype(np.int32)


def _shuffle(n, src, dst, feat, labels, rng):
    perm = rng.permutation(n)
    inv = np.empty(n, np.int64)
    inv[perm] = np.arange(n)
    return (inv[src].astype(np.int32), inv[dst].astype(np.int32),
            feat[perm], labels[perm])


def dcsbm(n: int, t: dict, rng):
    """Undirected pairs are drawn until ``mean_degree * n / 2`` distinct
    ones exist: one end by propensity over all nodes, the other by
    propensity inside the first end's community with probability
    ``p_intra``, else over all nodes (which lands inside with probability
    size/n, so edge homophily ~ p_intra + (1 - p_intra) / communities)."""
    k = int(t["communities"])
    comm = rng.permutation(np.arange(n) % k)
    # power-law propensities: P(theta > x) ~ x^-(gamma - 1), x >= 1
    theta = (1.0 - rng.random(n)) ** (-1.0 / (t["degree_exponent"] - 1.0))
    theta = np.minimum(theta, t["max_propensity_share"] * n)
    p_all = theta / theta.sum()
    members = [np.flatnonzero(comm == c) for c in range(k)]
    p_in = [theta[mb] / theta[mb].sum() for mb in members]
    target = int(round(t["mean_degree"] * n / 2))
    keys = np.empty(0, np.int64)
    while keys.size < target:
        m = target - keys.size + target // 8 + 16
        a = rng.choice(n, m, p=p_all)
        b = rng.choice(n, m, p=p_all)
        intra = rng.random(m) < t["p_intra"]
        for c in range(k):
            sel = np.flatnonzero(intra & (comm[a] == c))
            if sel.size:
                b[sel] = members[c][rng.choice(members[c].size, sel.size,
                                               p=p_in[c])]
        lo, hi = np.minimum(a, b), np.maximum(a, b)
        new = (lo * n + hi)[lo != hi]
        # first occurrence wins, in draw order, so the set is seed-stable
        merged = np.concatenate([keys, new])
        _, first = np.unique(merged, return_index=True)
        keys = merged[np.sort(first)]
    keys = keys[:target]
    src, dst = (keys // n).astype(np.int32), (keys % n).astype(np.int32)
    fd = int(t["feat_dim"])
    centers = rng.normal(0, 1, (k, fd)).astype(np.float32)
    feat = centers[comm] + rng.normal(0, t["feat_noise"], (n, fd)).astype(
        np.float32)
    labels = (comm % int(t["classes"])).astype(np.int32)
    src, dst = _symmetrize(n, src, dst)
    src, dst, feat, labels = _shuffle(n, src, dst, feat, labels, rng)
    return n, src, dst, feat, labels


GENERATORS = {"dcsbm": dcsbm}


def make_graph(n: int, traffic: dict, seed: int):
    rng = np.random.default_rng(seed)
    return GENERATORS[traffic["generator"]](n, traffic, rng)


def edge_homophily(src, dst, labels) -> float:
    return float(np.mean(labels[src] == labels[dst]))
