"""Fault-tolerant training loop, generic over the Task protocol.

Production concerns implemented (and unit-tested at CPU scale):

* step-granular checkpoint/restart — tasks are seekable (step -> batch is
  pure), so a restart replays nothing and skips nothing;
* async checkpoints every `ckpt_every` steps + graceful save on
  preemption (SIGTERM) and on uncaught worker failure;
* failure injection hook (`fail_at_step`) for restart tests, plus the
  seeded ``FaultPlan`` hooks (repro.resilience: ``fault_plan`` /
  ``REPRO_FAULTS``) — non-finite loss, mid-step preemption after
  donation, checkpoint byte corruption;
* self-healing: a jit-safe non-finite guard inside the jitted step
  (``jnp.where`` skip-update + a consecutive-bad-step counter riding the
  state carry — no extra traced programs); after ``max_bad_steps``
  consecutive bad steps the loop rolls back to the newest
  checksum-verified checkpoint outside the bad streak and replays;
* straggler mitigation policy: per-step wall-time EMA; steps slower than
  `straggler_factor` x EMA are flagged and the policy callback fires (at
  real scale: re-dispatch / hot-spare swap; here: recorded + surfaced);
* elastic restart: checkpoints restore onto a different mesh (shardings
  come from the current run's recipe, not the saved one);
* kernel dispatch: ``TrainerConfig.attn_impl`` routes every attention/SSD
  op in the jitted step through repro.kernels.ops (oracle / Pallas
  interpret / Pallas compiled) — no call-site edits anywhere in the model.

All workload behavior enters through the ``repro.tasks.Task`` protocol —
the Trainer has no model-family or graph-specific branches:

* the task's ``loss_variants`` each get ONE jitted step (an elastic graph
  run traces exactly two: sparse + dense — never more, re-layouts
  included, because tasks keep their batches shape-stable);
* ``task.variant(step, interleave_period)`` is the dual-interleave
  schedule (paper §III-B) — keyed off the absolute step, so the cadence
  survives restart;
* every ``elastic_every`` steps the epoch's (mean loss, wall time) feed
  ``task.on_epoch`` (paper §III-D: the AutoTuner ladder / re-reformation
  for elastic tasks, a no-op for streams);
* ``task.state_dict()`` rides in the checkpoint manifest
  (``Checkpointer.save(extra=...)``), so an elastic restart resumes the
  ladder instead of resetting it;
* passing ``mesh``/``recipe`` runs every variant's step under the mesh —
  node-level, graph-level and link tasks all hit the sharded
  cluster-sparse path (``parallel/cluster_parallel``) identically.

A plain ``batch_fn`` is wrapped into a ``BatchFnTask``, so the LM
families flow through the identical loop.
"""

from __future__ import annotations

import contextlib
import dataclasses
import os
import signal
import time
import warnings
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro import compat
from repro.ckpt.checkpoint import Checkpointer, CheckpointCorrupt
from repro.kernels import ops as kernel_ops
from repro.optim.adamw import AdamW, warmup_cosine
from repro.parallel.axes import axis_rules
from repro.resilience.faults import FaultPlan, Preempted
from repro.runtime.spans import span
from repro.tasks.base import BatchFnTask


@dataclasses.dataclass
class TrainerConfig:
    steps: int = 100
    ckpt_every: int = 50
    ckpt_dir: str = "/tmp/repro_ckpt"
    keep: int = 3
    lr: float = 3e-4
    warmup: int = 10
    weight_decay: float = 0.1
    state_dtype: str = "float32"
    straggler_factor: float = 3.0
    fail_at_step: int = -1          # failure injection (tests)
    log_every: int = 10
    # kernel dispatch for every attention/SSD op in the step (kernels/ops):
    # auto = Pallas-compiled on TPU / jnp oracle elsewhere; ref / interpret /
    # compiled force a path. REPRO_FORCE_PALLAS* env vars still win.
    attn_impl: str = "auto"
    # task schedule knobs (consumed through the Task protocol):
    interleave_period: int = 0   # dense step every k steps (0 = never)
    elastic_every: int = 0       # steps per task epoch (0 = frozen layout)
    # IR audit (repro.analysis.ir): before the first step, lower+compile
    # each loss variant's program and check its collectives (no seq-axis
    # all-gather under a mesh) + dtype flow; error findings abort the run
    # pre-launch. REPRO_IR_AUDIT=1 turns it on too (env wins when set).
    ir_audit: bool = False
    # kernel autotuning (repro.tune): reload the winner table from disk
    # every k steps (0 = never). A refresh NEVER retraces the jitted
    # steps — schedules resolve at trace time, so the two step programs
    # survive the swap and refreshed winners apply to traces made after
    # it (an elastic re-layout, a new loss variant).
    retune_every: int = 0
    tune_table: str = ""         # "" = REPRO_TUNE_TABLE / TUNE_winners.json
    # crash rescue: refresh an undonated host copy of the state every k
    # steps so the crash-consistent save survives donated-buffer deletion
    # when the jitted step itself dies mid-call (0 = off). Each refresh is
    # a synchronous device_get of the whole state — fine at this repo's
    # CPU test scale; raise the cadence (or disable) for big states. Only
    # active when donation is on and no mesh is set: undonated state
    # stays live for the crash save, and sharded runs fall back to their
    # periodic checkpoints.
    rescue_every: int = 1
    # deterministic fault injection (repro.resilience.faults): a seeded
    # FaultPlan spec like "nonfinite@5,preempt@7,ckpt_corrupt@10,seed=3".
    # REPRO_FAULTS wins over this field when set. Empty = no faults.
    fault_plan: str = ""
    # self-healing escalation: after this many CONSECUTIVE non-finite
    # steps (each already skip-updated by the in-step guard), roll back
    # to the newest verified checkpoint outside the bad streak and
    # replay. 0 disables escalation (skip-only).
    max_bad_steps: int = 3
    # hard cap on rollbacks per run — a fault that survives replay this
    # many times is not transient; raise instead of looping forever
    max_rollbacks: int = 3


@dataclasses.dataclass
class StragglerReport:
    step: int
    seconds: float
    ema: float


@dataclasses.dataclass
class RollbackReport:
    at_step: int   # loop step the escalation fired at
    to_step: int   # verified checkpoint step replay resumed from


class Trainer:
    def __init__(self, model, cfg: TrainerConfig,
                 batch_fn: Callable[[int], Any] | None = None,
                 *, mesh=None, recipe=None, donate: bool = True,
                 task=None, elastic=None):
        self.model = model
        self.cfg = cfg
        self.mesh = mesh
        self.recipe = recipe
        # one Task supplies batches, losses and the step schedule; a bare
        # batch_fn becomes the trivial stream task (``elastic`` is the
        # pre-Task spelling of the same keyword)
        task = task if task is not None else elastic
        if task is None:
            if batch_fn is None:
                raise ValueError("need batch_fn or a task")
            task = BatchFnTask(batch_fn)
        self.task = task.prepare(model)
        # route every kernel call in the jitted step through the dispatch
        # layer: one config knob selects oracle / interpret / compiled
        # everywhere, including inside shard_map (kernels/ops.py)
        kernel_ops.set_mode(cfg.attn_impl)
        self.ckpt = Checkpointer(cfg.ckpt_dir, keep=cfg.keep)
        self.opt = AdamW(
            lr=warmup_cosine(cfg.lr, cfg.warmup, cfg.steps),
            weight_decay=cfg.weight_decay, state_dtype=cfg.state_dtype)
        self.stragglers: list[StragglerReport] = []
        self.history: list[dict] = []
        self.ir_findings: list = []
        self.rollbacks: list[RollbackReport] = []
        self.fault_log: list[dict] = []
        self.faults = FaultPlan.resolve(cfg.fault_plan)
        self._preempted = False
        self._rescue: tuple[int, Any] | None = None
        self._donate = donate

        def make_step(loss):
            def step_fn(state, batch, fault):
                def loss_fn(p):
                    lval, metrics = loss(p, batch)
                    # nonfinite fault hook (repro.resilience): ``fault``
                    # is a traced fp32 scalar — exactly 1.0 on healthy
                    # steps (bitwise identity), NaN on an injected step
                    # (poisons loss and every gradient). Same shape and
                    # dtype either way, so no retrace.
                    return lval * fault, metrics

                def fwd_bwd():
                    (lval, metrics), grads = jax.value_and_grad(
                        loss_fn, has_aux=True)(state["params"])
                    new_p, new_opt = self.opt.update(
                        grads, state["opt"], state["params"])
                    return lval, metrics, grads, new_p, new_opt

                if recipe is not None and mesh is not None:
                    with axis_rules(recipe, mesh):
                        lval, metrics, grads, new_p, new_opt = fwd_bwd()
                else:
                    lval, metrics, grads, new_p, new_opt = fwd_bwd()
                # jit-safe non-finite guard: a bad loss or any bad grad
                # leaf skips the update (jnp.where keeps the old state
                # bitwise) and bumps the consecutive-bad-step counter
                # riding the carry; a good step resets it
                ok = jnp.isfinite(lval)
                for g in jax.tree.leaves(grads):
                    ok = ok & jnp.all(jnp.isfinite(g))
                keep = lambda new, old: jax.tree.map(  # noqa: E731
                    lambda a, b: jnp.where(ok, a, b), new, old)
                bad = jnp.where(ok, jnp.zeros((), jnp.int32),
                                state["bad"] + 1)
                return ({"params": keep(new_p, state["params"]),
                         "opt": keep(new_opt, state["opt"]),
                         "step": state["step"] + 1, "bad": bad},
                        {"loss": lval, "bad_steps": bad,
                         "skipped": (~ok).astype(jnp.int32), **metrics})

            return jax.jit(step_fn, donate_argnums=(0,) if donate else ())

        # ONE jitted step per task loss variant — the whole run traces
        # len(variants) programs (two for dual-interleave tasks), however
        # often the task re-lays out: variants select per step host-side
        self._steps = {name: make_step(fn)
                       for name, fn in self.task.loss_variants.items()}

    # back-compat spellings for the variant steps (tests/benchmarks
    # introspect trace counts through these)
    @property
    def _step(self):
        return self._steps["sparse"]

    @_step.setter
    def _step(self, fn):
        self._steps["sparse"] = fn

    @property
    def _step_dense(self):
        return self._steps.get("dense")

    @property
    def elastic(self):
        """Pre-Task alias for the bound task."""
        return self.task

    def _mesh_ctx(self):
        """Ambient-mesh context for step execution — the distributed trainer
        runs its jitted step under the run's mesh; single-device runs get a
        nullcontext."""
        if self.mesh is None:
            return contextlib.nullcontext()
        return compat.use_mesh(self.mesh)

    # ------------------------------------------------------------ state

    def init_state(self, seed: int = 0):
        params = self.model.init(jax.random.PRNGKey(seed))
        return {"params": params, "opt": self.opt.init(params),
                "step": jnp.zeros((), jnp.int32),
                # consecutive non-finite steps (in-step guard carry)
                "bad": jnp.zeros((), jnp.int32)}

    def _adopt(self, state, step: int):
        """Normalize a freshly-restored tree into step-ready state and
        load the task's saved state from the manifest."""
        state["step"] = jnp.asarray(state["step"], jnp.int32)
        # checkpoints predating the non-finite guard carry no counter
        state.setdefault("bad", jnp.zeros((), jnp.int32))
        state["bad"] = jnp.asarray(state["bad"], jnp.int32)
        extra = self.ckpt.load_extra(step)
        if extra:
            # "elastic" is the pre-Task manifest key; keep restoring it
            sd = extra.get("task") or extra.get("elastic")
            if sd:
                self.task.load_state_dict(sd)
        return state

    def restore_or_init(self, seed: int = 0):
        # newest generation that passes checksum verification; a corrupt
        # or uncommitted latest falls back (with a RuntimeWarning) to an
        # older retained generation, and nothing verified means re-init
        got = self.ckpt.restore_latest_verified()
        if got is None:
            return self.init_state(seed), 0
        state, latest = got
        return self._adopt(state, latest), latest

    def _ckpt_extra(self):
        sd = self.task.state_dict()
        return {"task": sd} if sd else None

    # --------------------------------------------------------- ir audit

    def _ir_audit_enabled(self) -> bool:
        return bool(os.environ.get("REPRO_IR_AUDIT", "")) or \
            self.cfg.ir_audit

    def ir_audit(self, state=None, step: int = 0) -> list:
        """First-compile IR audit (repro.analysis.ir) of every loss
        variant's jitted step: under a mesh, the compiled collectives
        must contain no sequence-axis all-gather (the O(S/P) contract of
        the sharded attention path); the dtype-flow report rides along
        for ANALYSIS_ir_report.json. Returns the findings list (stored
        on ``self.ir_findings``); raises ``IRAuditError`` on error-level
        findings — a pre-launch gate, like ``check_shard_specs``."""
        from repro.analysis.ir import (CollectiveBudget, IRAuditError,
                                       audit_collectives, errors)
        from repro.analysis.ir.dtype_flow import audit_dtype_flow
        if state is None:
            state, step = self.restore_or_init()
        findings: list = []
        batch = self.task.batches(step)
        budget = None
        if self.mesh is not None:
            # HLO dims are positional: in a whole training step, weight
            # all-gathers along dim 1 are the recipe working as designed.
            # Pin the check to gathers that span the batch's actual
            # sequence length (skip it if no batch leaf reveals one),
            # and report at warning level — the plain LM path under a
            # recipe legitimately re-materializes k/v per layer; only
            # the sharded cluster-attention programs promise O(S/P)
            # (their gate in parallel/cluster_parallel errors).
            seq = [s[1] for s in (jnp.shape(a) for a in
                                  jax.tree_util.tree_leaves(batch))
                   if len(s) >= 2]
            budget = CollectiveBudget(
                forbid_seq_allgather=bool(seq),
                seq_len=max(seq) if seq else None,
                seq_allgather_level="warning")
        one = np.float32(1.0)  # healthy-step fault operand
        for name, fn in self._steps.items():
            label = f"trainer:{name}"
            if budget is not None:
                hlo = self.lower(name, state, step).compile().as_text()
                findings += audit_collectives(hlo, budget, label=label)
            with self._mesh_ctx():
                findings += audit_dtype_flow(
                    jax.make_jaxpr(fn)(state, batch, one), label=label)
        self.ir_findings = findings
        if errors(findings):
            raise IRAuditError(findings, label="trainer ir_audit")
        return findings

    def lower(self, variant: str, state, step: int = 0):
        """The jitted step of loss variant ``variant`` lowered on
        ``state`` and the task's batch for ``step`` (healthy fault
        operand), under the run's mesh — the program ``run`` executes.
        ``.compile()`` it for its HLO text and memory analysis."""
        with self._mesh_ctx():
            return self._steps[variant].lower(
                state, self.task.batches(step), np.float32(1.0))

    @contextlib.contextmanager
    def trace_ctx(self):
        """The run's mesh and the recipe's sharding rules: the context
        the steps trace in (``step_fn`` enters the rules itself). Code
        that traces the model outside the steps enters it, so a sharded
        run reaches the shard_map-wrapped attention: GSPMD cannot
        partition a Pallas kernel."""
        with self._mesh_ctx():
            if self.mesh is None or self.recipe is None:
                yield
            else:
                with axis_rules(self.recipe, self.mesh):
                    yield

    def evaluate(self, params) -> dict:
        """``task.eval(params)`` traced as the steps are (``trace_ctx``)."""
        with self.trace_ctx():
            return self.task.eval(params)

    # ------------------------------------------------------------ loop

    def run(self, seed: int = 0):
        with span("repro.trainer.init"):
            state, start = self.restore_or_init(seed)
        cfg = self.cfg
        task = self.task
        if self._ir_audit_enabled():
            self.ir_audit(state, start)

        old = signal.getsignal(signal.SIGTERM)

        def on_term(sig, frame):
            self._preempted = True

        try:
            signal.signal(signal.SIGTERM, on_term)
        except ValueError:
            pass  # not main thread

        ema = None
        # rescue only matters when donation can delete buffers mid-call;
        # sharded state is left to the periodic checkpoints (device_get of
        # non-addressable arrays is not portable)
        rescue_on = cfg.rescue_every > 0 and self._donate and \
            self.mesh is None
        epoch_losses: list[float] = []
        epoch_seconds = 0.0
        try:
            step = start
            while step < cfg.steps:
                with span("repro.trainer.step", step=step) as sp:
                    if step == cfg.fail_at_step:
                        raise RuntimeError(
                            f"injected failure at step {step}")
                    t0 = time.perf_counter()
                    # the task owns the schedule (dual-interleave for graph
                    # tasks, always-"sparse" for streams); absolute step ->
                    # cadence survives restart
                    variant = task.variant(step, cfg.interleave_period)
                    sp.attrs["variant"] = variant
                    with span("repro.task.batches"):
                        batch = task.batches(step)
                    # fault hooks (repro.resilience): the nonfinite
                    # operand is 1.0 (bitwise identity) unless this step is
                    # armed; preemption keeps the pre-step carry so the
                    # raise lands after donation consumed it
                    nf = self.faults.take("nonfinite", step)
                    scale = np.float32("nan" if nf else 1.0)
                    pre = self.faults.take("preempt", step)
                    prev = state if pre is not None else None
                    with self._mesh_ctx(), span("repro.trainer.dispatch"):
                        state, metrics = self._steps[variant](
                            state, batch, scale)
                    if nf is not None:
                        self.fault_log.append(
                            {"kind": "nonfinite", "step": step})
                    if pre is not None:
                        # a real preemption kills the process mid-step:
                        # the outputs never escape, and under donation the
                        # inputs are already deleted — exactly what the
                        # crash save's rescue fallback must survive
                        state = prev
                        self.fault_log.append(
                            {"kind": "preempt", "step": step})
                        raise Preempted(
                            f"injected preemption at step {step}")
                    # the host blocks here until the device ends the step
                    with span("repro.trainer.wait") as wait:
                        metrics = {k: float(v) for k, v in metrics.items()}
                    dt = time.perf_counter() - t0
                    if step - start >= 2:  # skip compile-dominated warmup
                        prev_ema = ema
                        ema = dt if ema is None else 0.9 * ema + 0.1 * dt
                        if prev_ema is not None and \
                                dt > cfg.straggler_factor * prev_ema:
                            self.stragglers.append(
                                StragglerReport(step, dt, prev_ema))
                    rec = {"step": step + 1, **metrics, "seconds": dt,
                           "wait_s": wait.seconds, "variant": variant,
                           "dense": variant == "dense", **task.log_extras()}
                    self.history.append(rec)
                    if rescue_on and (step + 1) % cfg.rescue_every == 0:
                        # undonated host copy: the crash save below must
                        # not touch buffers the next step call donates away
                        with span("repro.trainer.rescue"):
                            self._rescue = (step + 1, jax.device_get(state))
                    if cfg.elastic_every > 0:
                        # compile-dominated warmup steps would poison the
                        # LDR denominator (the straggler EMA skips them
                        # too); non-finite losses (guard-skipped steps)
                        # would poison the mean
                        if step - start >= 2 and \
                                np.isfinite(metrics["loss"]):
                            epoch_losses.append(metrics["loss"])
                            epoch_seconds += dt
                        if (step + 1) % cfg.elastic_every == 0:
                            if epoch_losses:
                                with span("repro.task.on_epoch"):
                                    task.on_epoch(
                                        float(np.mean(epoch_losses)),
                                        epoch_seconds, step=step + 1)
                            epoch_losses, epoch_seconds = [], 0.0
                    if cfg.retune_every > 0 and \
                            (step + 1) % cfg.retune_every == 0:
                        # winner-table refresh (TrainerConfig.retune_every):
                        # warn-and-fallback on any load problem, never
                        # raises, never retraces the live step executables
                        from repro.tune import runtime as tune_runtime
                        with span("repro.tune.refresh"):
                            tune_runtime.refresh(cfg.tune_table or None)
                    # the final blocking save below covers step == cfg.steps
                    if (step + 1) % cfg.ckpt_every == 0 and \
                            step + 1 != cfg.steps:
                        self.ckpt.save(step + 1, state,
                                       extra=self._ckpt_extra())
                        self._maybe_corrupt(step + 1)
                    if self._preempted:
                        self.ckpt.save(step + 1, state, blocking=True,
                                       extra=self._ckpt_extra())
                        return state, "preempted"
                    # escalation: the in-step guard already skipped each
                    # bad update; a persistent streak means the carry itself
                    # may be poisoned (e.g. optimizer moments) — roll back
                    # to the newest verified checkpoint outside the streak
                    if cfg.max_bad_steps > 0 and \
                            metrics["bad_steps"] >= cfg.max_bad_steps:
                        with span("repro.trainer.rollback"):
                            state, step = self._rollback(step + 1, seed)
                        ema = None
                        epoch_losses, epoch_seconds = [], 0.0
                        continue
                    step += 1
            self.ckpt.save(cfg.steps, state, blocking=True,
                           extra=self._ckpt_extra())
            self._maybe_corrupt(cfg.steps)
            return state, "done"
        except Exception:
            # crash-consistent save so a restart resumes, then re-raise
            try:
                self._crash_save(state)
            # best-effort rescue: a failing save must never mask the
            # original crash we are about to re-raise
            except Exception:  # repro-lint: disable=REP008
                pass
            raise
        finally:
            self.ckpt.wait()
            try:
                signal.signal(signal.SIGTERM, old)
            except (ValueError, TypeError):
                pass

    def _maybe_corrupt(self, step: int):
        """ckpt_corrupt fault hook: flip one seeded byte in the
        checkpoint just written (after the async write lands)."""
        cf = self.faults.take("ckpt_corrupt", step)
        if cf is None:
            return
        self.ckpt.wait()
        fn, off = self.ckpt.corrupt(step, seed=self.faults.seed)
        self.fault_log.append({"kind": "ckpt_corrupt", "step": step,
                               "file": fn, "offset": off})

    def _rollback(self, at_step: int, seed: int):
        """Roll back to the newest verified checkpoint outside the bad
        streak (saved consecutive-bad counter == 0) and return
        ``(state, step)`` to replay from; re-init at step 0 when no
        generation qualifies. Tasks are seekable, so replay recomputes
        the same batches deterministically."""
        cfg = self.cfg
        if len(self.rollbacks) >= cfg.max_rollbacks:
            raise RuntimeError(
                f"non-finite steps persist after {len(self.rollbacks)} "
                f"rollbacks (max_rollbacks={cfg.max_rollbacks}); "
                "refusing to loop")
        self.ckpt.wait()
        state = to = None
        for s in self.ckpt.generations():
            try:
                tree = self.ckpt.restore(s)
            except (CheckpointCorrupt, OSError, ValueError, KeyError) as e:
                warnings.warn(
                    f"repro.runtime: rollback skipping checkpoint step "
                    f"{s} (failed verification: {e})",
                    RuntimeWarning, stacklevel=2)
                continue
            if int(np.asarray(tree.get("bad", 0))) > 0:
                # saved mid-streak: its step counter has advanced past
                # updates the guard skipped, so replaying from here
                # would drop those updates forever — only a generation
                # outside the streak gives exact replay
                warnings.warn(
                    f"repro.runtime: rollback skipping checkpoint step "
                    f"{s} (saved inside a bad streak)",
                    RuntimeWarning, stacklevel=2)
                continue
            state, to = self._adopt(tree, s), s
            break
        if state is None:
            state, to = self.init_state(seed), 0
        self._rescue = None  # pre-rollback copy is stale
        self.rollbacks.append(RollbackReport(at_step, to))
        warnings.warn(
            f"repro.runtime: {self.cfg.max_bad_steps} consecutive "
            f"non-finite steps at step {at_step}; rolled back to "
            f"verified checkpoint step {to} and replaying",
            RuntimeWarning, stacklevel=2)
        return state, to

    def _crash_save(self, state):
        """Rescue checkpoint after an uncaught failure. When the step
        raised mid-call its donated inputs are deleted — ``state`` then
        points at dead buffers, so fall back to the last undonated host
        copy (``rescue_every``) instead of crashing the rescue itself."""
        if _tree_live(state):
            self.ckpt.save(int(state["step"]), state, blocking=True,
                           extra=self._ckpt_extra())
        elif self._rescue is not None:
            step, host = self._rescue
            self.ckpt.save(step, host, blocking=True,
                           extra=self._ckpt_extra())


def _tree_live(tree) -> bool:
    """False iff any jax.Array leaf has been deleted (donated away)."""
    for leaf in jax.tree.leaves(tree):
        is_deleted = getattr(leaf, "is_deleted", None)
        if callable(is_deleted) and is_deleted():
            return False
    return True
