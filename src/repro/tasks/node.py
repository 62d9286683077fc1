"""Node-level task: single-graph node classification (the paper's
ogbn-style workload) with the elastic layout ladder.

Absorbs the old ``runtime/elastic.ElasticGraphTask`` (which remains as an
alias): one sequence of all nodes (B=1), global tokens prepended, masked
cross-entropy over labeled positions. Loss variants come from the graph
model (``sparse`` = cluster-sparse dispatch, ``dense`` = fully-connected
interleave step biased from ``dense_buckets``).

Shape stability is the whole design (see tasks/elastic.py): every ladder
rung's layout is built once through ``prepare_node_task_ladder`` and the
``mb`` (selected-k-block) axis is padded to the max across the ladder, so
a ladder move swaps array contents only — the Trainer's two jitted steps
are traced exactly once each for the whole run.

This composes unchanged with the sharded path
(``parallel/cluster_parallel.sharded_cluster_attention``): S is constant
across rungs and whole-block (``S % bq == 0``), and the pattern operands
are replicated inside the shard_map (every device holds the full sequence
post-a2a), so the same ``block_idx``/``buckets`` drive the Ulysses
sequence-sharded attention at any rung.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.data.graph_pipeline import pad_layout_mb, prepare_node_task_ladder
from repro.runtime.spans import count, span
from repro.tasks.elastic import ElasticTask


class NodeTask(ElasticTask):
    """Single-graph node classification with an elastic layout.

    ``train_mask`` hides non-train labels from the loss; ``eval(params)``
    then reports accuracy over the held-out (non-train) nodes, or over
    all labeled nodes when no mask was given."""

    name = "node"

    def __init__(self, g, cfg, *, train_mask=None, bq: int = 32,
                 bk: int = 32, d_b: int = 8, delta: int = 10,
                 seed: int = 0):
        self.cfg = cfg
        self.g = g
        betas = self._init_ladder(g.sparsity, delta)
        with span("repro.task.prep") as prep:
            preps = dict(zip(betas, prepare_node_task_ladder(
                g, cfg, betas, bq=bq, bk=bk, d_b=d_b,
                train_mask=train_mask, with_dense_buckets=True,
                seed=seed)))
        self.prep_seconds = prep.seconds
        seqs = {p.layout.seq_len for p in preps.values()}
        if len(seqs) != 1:  # deterministic prep => can't happen; be loud
            raise AssertionError(f"re-layout changed seq_len: {seqs}")
        mb_cap = max(p.layout.mb for p in preps.values())
        mt_cap = max(p.layout.mt for p in preps.values())
        with span("repro.prep.pad"):
            self._set_rungs({bt: [pad_layout_mb(p, mb_cap, mt_cap)]
                             for bt, p in preps.items()})
            count("layout.rect_slots", self.layout.nq * mb_cap)
        # held-out labels for eval: the permuted full label vector, with
        # train positions masked out when a train_mask was given
        ng = cfg.n_global
        S = next(iter(seqs))
        ev = np.full((1, S), -1, np.int32)
        if g.labels is not None:
            lab = g.labels[self.prep.perm]
            if train_mask is not None:
                lab = np.where(train_mask[self.prep.perm], -1, lab)
            ev[0, ng:ng + g.n] = lab
        self._eval_labels = ev

    # --------------------------------------------------------------- eval

    def eval(self, params) -> dict:
        """Metrics of the sparse variant on the eval label set (held-out
        nodes under a train_mask, all labeled nodes otherwise)."""
        b = dict(self.batches(0))
        b["labels"] = jnp.asarray(self._eval_labels)
        return {k: float(v) for k, v in self._metrics_fn()(params, b).items()}
