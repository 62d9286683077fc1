"""Shared benchmark helpers: timing, synthetic graph training harness."""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_smoke_config  # noqa: E402
from repro.core.dual_attention import (dense_bias_from_layout,  # noqa: E402
                                       use_dense_step)
from repro.core.graph import sbm_graph  # noqa: E402
from repro.core.graph_model import graph_loss, graph_predict  # noqa: E402
from repro.data.graph_pipeline import prepare_node_task  # noqa: E402
from repro.models import build  # noqa: E402
from repro.optim.adamw import AdamW  # noqa: E402
from repro.runtime.spans import span  # noqa: E402


from repro.tune.cases import cluster_grad_case  # noqa: E402,F401
from repro.tune.timing import timeit  # noqa: E402,F401

# timeit and cluster_grad_case moved to repro.tune (the autotuner times
# the EXACT tier-1 bench case through the same rig); re-exported here so
# every benchmark keeps its import path.


def row(name: str, us_per_call: float, derived: str = ""):
    print(f"{name},{us_per_call:.1f},{derived}")


class GraphTrainBench:
    """Synthetic-SBM node-classification harness used by several paper
    tables: trains Graphormer_slim/GT variants with a selectable attention
    mode ('raw' dense+bias / 'flash' dense no-bias / 'sparse' pure
    topology / 'torchgt' dual-interleaved)."""

    def __init__(self, arch="graphormer_slim", n=512, n_clusters=4,
                 beta_thre=None, seed=0, dtype=None):
        cfg = get_smoke_config(arch)
        if dtype:
            cfg = cfg.replace(dtype=dtype)
        self.cfg = cfg
        g = sbm_graph(n, n_clusters, p_in=0.04, p_out=0.002,
                      feat_dim=cfg.feat_dim, n_classes=cfg.n_classes,
                      seed=seed)
        rng = np.random.default_rng(seed)
        self.train_mask = rng.random(g.n) < 0.6
        with span("repro.task.prep") as prep:
            self.prep = prepare_node_task(g, cfg, bq=32, bk=32, d_b=8,
                                          beta_thre=beta_thre,
                                          train_mask=self.train_mask)
        self.prep_seconds = prep.seconds
        self.batch = {k: jnp.asarray(v) for k, v in self.prep.batch.items()}
        # eval batch: all labels visible
        prep_all = prepare_node_task(g, cfg, bq=32, bk=32, d_b=8,
                                     beta_thre=beta_thre)
        eb = {k: jnp.asarray(v) for k, v in prep_all.batch.items()}
        self.eval_labels = np.asarray(prep_all.batch["labels"][0])
        self.eval_batch = eb
        self.g = g
        self.model = build(cfg)
        self.opt = AdamW(lr=2e-3, weight_decay=0.01)

        self._loss_sparse = jax.jit(
            lambda p, o, b: self._step(p, o, b, dense=False, bias=False))
        self._loss_dense_bias = jax.jit(
            lambda p, o, b: self._step(p, o, b, dense=True, bias=True))
        self._loss_dense_nobias = jax.jit(
            lambda p, o, b: self._step(p, o, b, dense=True, bias=False))
        self._predict = jax.jit(
            lambda p, b: graph_predict(p, self.cfg, b, dense=False))

    def _step(self, params, opt_state, batch, *, dense, bias):
        def lf(p):
            b = dict(batch)
            if dense and bias:
                b["dense_bias"] = self._dense_bias(p)
            elif dense:
                b["dense_bias"] = None
            loss, m = graph_loss(p, self.cfg, b, dense=dense)
            return loss, m

        (loss, m), grads = jax.value_and_grad(lf, has_aux=True)(params)
        new_p, new_o = self.opt.update(grads, opt_state, params)
        return loss, m, new_p, new_o

    def _dense_bias(self, params):
        tbl = params.get("bias_table")
        if tbl is None:
            return None
        return dense_bias_from_layout(self.prep.layout, tbl,
                                      self.cfg.n_heads)

    def init(self, seed=0):
        p = self.model.init(jax.random.PRNGKey(seed))
        return p, self.opt.init(p)

    def train(self, mode: str, epochs: int = 60, interleave_period: int = 8,
              seed: int = 0):
        """Returns (history list of dict, seconds_per_epoch, test_acc)."""
        params, ost = self.init(seed)
        cond_ok = self.prep.report.ok
        hist = []
        times = []
        for ep in range(epochs):
            if mode == "torchgt":
                dense = use_dense_step(ep, interleave_period, cond_ok)
                fn = self._loss_dense_bias if dense else self._loss_sparse
            elif mode == "sparse":
                fn = self._loss_sparse
            elif mode == "raw":
                fn = self._loss_dense_bias
            elif mode == "flash":
                fn = self._loss_dense_nobias
            else:
                raise ValueError(mode)
            t0 = time.perf_counter()
            loss, m, params, ost = fn(params, ost, self.batch)
            jax.block_until_ready(loss)
            times.append(time.perf_counter() - t0)
            hist.append({"epoch": ep, "loss": float(loss),
                         "train_acc": float(m["acc"])})
        acc = self.test_acc(params)
        # drop compile epochs from timing (paper: 10-epoch warmup)
        t_epoch = float(np.median(times[2:]))
        return hist, t_epoch, acc

    def test_acc(self, params):
        logits = np.asarray(self._predict(params, self.eval_batch),
                            np.float32)
        pred = logits[0].argmax(-1)
        mask = (self.eval_labels >= 0)
        ng = self.cfg.n_global
        test = mask.copy()
        test[ng:ng + self.g.n] &= ~self.train_mask
        test[:ng] = False
        if test.sum() == 0:
            return 0.0
        return float((pred[test] == self.eval_labels[test]).mean())
