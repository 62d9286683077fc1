"""Shared elastic-ladder machinery for graph tasks (paper §III-D).

Every concrete graph task (node-level, graph-level, link prediction) is
elastic the same way: an AutoTuner walks a ``beta_thre`` ladder on the
Loss-Descent-Rate signal the Trainer feeds at epoch boundaries, and a
ladder move swaps in a re-reformed layout. ``ElasticTask`` owns that
machinery once:

* every rung's layout is prepared ONCE at construction and padded to a
  fixed shape budget, so a ladder move swaps array *contents* only — the
  Trainer's jitted steps (one per loss variant) trace exactly once each
  for the whole run, re-layouts included;
* device uploads are deduped by host-array identity: rung-invariant
  arrays (features, degrees, labels) are aliased across rungs by the
  ladder preps and live on device exactly once;
* tuner position / ``beta_thre`` / layout stats / the move log ride the
  checkpoint manifest through ``state_dict``/``load_state_dict`` so an
  elastic restart resumes the ladder instead of resetting it.

Subclasses provide the rung preps (``_set_rungs``) and the task-specific
``loss_variants``/``eval``.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.auto_tuner import AutoTuner
from repro.tasks.base import Task


@dataclasses.dataclass
class LadderMove:
    step: int           # trainer step after which the move happened
    pos: int            # new ladder position
    beta_thre: float    # new transfer threshold
    ldr: float          # the LDR value that triggered the move


class ElasticTask(Task):
    """A task whose layouts live on an AutoTuner ``beta_thre`` ladder.

    The Trainer calls ``batches(step)`` every step (active rung's arrays,
    shape-identical across rungs and mini-batches) and ``on_epoch(loss,
    seconds, step)`` at each epoch boundary; a ladder move swaps the
    active rung."""

    name = "elastic"
    prep_seconds: float  # the subclass's ``repro.task.prep`` span

    def _init_ladder(self, beta_g: float, delta: int) -> list[float]:
        """Create the tuner; returns the deduped rung thresholds to
        prepare (the top of the ladder can collapse to 1.0 on dense
        graphs)."""
        self.tuner = AutoTuner(beta_g=beta_g, delta=delta)
        self.moves: list[LadderMove] = []
        self._batches_dev: dict[tuple, dict] = {}
        self._uploads: dict[int, object] = {}  # id(host arr) -> device arr
        self._eval_fn = None
        return list(dict.fromkeys(self.tuner.ladder))

    def _metrics_fn(self):
        """Lazily-jitted sparse-variant metrics fn shared by every
        subclass's ``eval`` (one trace per task instance)."""
        if self._eval_fn is None:
            self._eval_fn = jax.jit(
                lambda p, b: self.loss_variants["sparse"](p, b)[1])
        return self._eval_fn

    def _set_rungs(self, preps: dict) -> None:
        """``preps``: beta_thre -> list[PreparedGraph] (one per
        mini-batch; single-graph tasks have exactly one). Every prep must
        already be padded to one common shape budget — validated here, so
        a shape drift is loud at construction, not a silent retrace."""
        self._preps = {bt: list(ps) for bt, ps in preps.items()}
        first = next(iter(self._preps.values()))[0]
        shapes = {k: v.shape for k, v in first.batch.items()}
        self.n_batches = len(next(iter(self._preps.values())))
        for ps in self._preps.values():
            if len(ps) != self.n_batches:
                raise AssertionError("rungs have unequal mini-batch counts")
            for p in ps:
                got = {k: v.shape for k, v in p.batch.items()}
                if got != shapes:
                    raise AssertionError(
                        f"rung/mini-batch shape drift: {got} != {shapes}")
        self.mb_cap = first.layout.mb

    # ------------------------------------------------------------ state

    @property
    def beta_thre(self) -> float:
        return self.tuner.beta_thre

    @property
    def prep(self):
        """The active rung's first PreparedGraph (shape-budget padded)."""
        return self._preps[self.tuner.beta_thre][0]

    @property
    def conditions_ok(self) -> bool:
        return all(p.report.ok for p in self._preps[self.tuner.beta_thre])

    @property
    def layout(self):
        return self.prep.layout

    def batches(self, step: int) -> dict:
        """jnp-ready batch of the active rung for this step — mini-batches
        cycle by step, so a restart replays nothing. Device uploads are
        cached per (rung, mini-batch) and deduped by host-array identity;
        a ladder move uploads only the pattern arrays, never retraces."""
        bt = self.tuner.beta_thre
        idx = step % self.n_batches
        key = (bt, idx)
        if key not in self._batches_dev:
            dev = {}
            for k, v in self._preps[bt][idx].batch.items():
                hid = id(v)
                if hid not in self._uploads:
                    self._uploads[hid] = jnp.asarray(v)
                dev[k] = self._uploads[hid]
            self._batches_dev[key] = dev
        return self._batches_dev[key]

    def batch(self) -> dict:
        """Single-batch spelling (kept for the pre-Task API)."""
        return self.batches(0)

    # ------------------------------------------------------------ loop

    def on_epoch(self, loss: float, epoch_seconds: float,
                 step: int) -> bool:
        """Feed one epoch's (mean loss, wall seconds) to the AutoTuner;
        returns True iff the ladder moved (the next ``batches()`` serves
        the new rung's layout)."""
        before = self.tuner.pos
        self.tuner.update(float(loss), float(epoch_seconds))
        if self.tuner.pos == before:
            return False
        self.moves.append(LadderMove(step=step, pos=self.tuner.pos,
                                     beta_thre=self.tuner.beta_thre,
                                     ldr=float(self.tuner._ldr[-1])))
        return True

    def log_extras(self) -> dict:
        return {"beta_thre": float(self.beta_thre)}

    # ------------------------------------------------------- durability

    def state_dict(self) -> dict:
        stats = {k: (int(v) if isinstance(v, (int, np.integer)) else
                     float(v))
                 for k, v in self.layout.stats.items()}
        return {"task": self.name,
                "tuner": self.tuner.state_dict(),
                "mb_cap": int(self.mb_cap),
                "layout_stats": stats,
                "moves": [dataclasses.asdict(m) for m in self.moves]}

    def load_state_dict(self, d: dict) -> None:
        if d.get("task", self.name) != self.name:
            raise ValueError(
                f"checkpoint belongs to task {d['task']!r}, not "
                f"{self.name!r}: task type changed under restart")
        self.tuner.load_state_dict(d["tuner"])
        if int(d["mb_cap"]) != self.mb_cap:
            raise ValueError(
                f"checkpoint mb capacity {d['mb_cap']} != this run's "
                f"{self.mb_cap}: graph or prep knobs changed under restart")
        if self.tuner.beta_thre not in self._preps:
            raise ValueError(
                f"checkpoint ladder rung {self.tuner.beta_thre} has no "
                f"prepared layout: graph changed under restart")
        self.moves = [LadderMove(**m) for m in d.get("moves", [])]
