"""Chaos sweep: run the injected fault matrix end-to-end.

``python -m repro.resilience`` trains a small LM under every fault kind
(non-finite step, escalating non-finite streak, preemption with and
without buffer donation, corrupt latest checkpoint) and drives the serve
engine through overload and deadline faults, then writes
``RESILIENCE_report.json``. Each record states how the fault was
recovered and what the recovery promises:

* ``replay: "exact"`` — the recovered run's final params were checked
  bitwise-identical to an unfaulted baseline (rollback + replay,
  preemption resume, checkpoint-generation fallback);
* ``replay: "skip"`` — the bad step was skipped by the in-step guard;
  the run completes finite but takes one fewer update than the
  baseline (by design, no bitwise claim);
* ``replay: "n/a"`` — serve-side faults: the claim is typed rejection /
  shedding with the warm engine's trace budget staying 0.

Any unrecovered fault makes ``run_chaos`` return a failing report (the
CLI exits nonzero) — CI runs this.
"""

from __future__ import annotations

import json
import tempfile
import warnings

import jax
import numpy as np

SCHEMA = ("fault", "kind", "recovered", "replay", "detail", "n_warnings")


def _build_lm():
    from repro.configs import get_smoke_config
    from repro.models import build
    cfg = get_smoke_config("smollm_135m")
    return cfg, build(cfg)


def _mk_trainer(model, cfg, ckpt_dir, *, steps, donate=True,
                ckpt_every=2, **kw):
    from repro.data.lm_pipeline import LMDataConfig, lm_batch
    from repro.runtime.trainer import Trainer, TrainerConfig
    dc = LMDataConfig(vocab_size=cfg.vocab_size, seq_len=32,
                      global_batch=2)
    tc = TrainerConfig(steps=steps, ckpt_every=ckpt_every,
                       ckpt_dir=ckpt_dir, keep=3, lr=1e-3, warmup=2,
                       **kw)
    return Trainer(model, tc, lambda s: lm_batch(dc, s), donate=donate)


def _bitwise(a, b) -> bool:
    la = jax.tree.leaves(jax.device_get(a))
    lb = jax.tree.leaves(jax.device_get(b))
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(la, lb))


def run_chaos(report_path: str = "RESILIENCE_report.json", *,
              offline: bool = True, steps: int = 8,
              only: str | None = None) -> dict:
    """Run the fault matrix; write and return the report dict."""
    from repro.resilience.faults import Preempted

    cfg, model = _build_lm()
    records: list[dict] = []

    with tempfile.TemporaryDirectory() as d:
        tr = _mk_trainer(model, cfg, d, steps=steps)
        base_state, base_status = tr.run()
        baseline = jax.device_get(base_state["params"])
    if base_status != "done":
        raise RuntimeError(f"unfaulted baseline did not finish: "
                           f"{base_status!r}")

    # ---------------------------------------------------- train faults

    def case_nonfinite_skip(d):
        at = steps // 2
        tr = _mk_trainer(model, cfg, d, steps=steps,
                         fault_plan=f"nonfinite@{at}", max_bad_steps=0)
        state, status = tr.run()
        skipped = [h["step"] for h in tr.history if h.get("skipped")]
        finite = bool(np.isfinite(float(tr.history[-1]["loss"])))
        ok = status == "done" and skipped == [at + 1] and finite and \
            all(np.all(np.isfinite(np.asarray(v)))
                for v in jax.tree.leaves(jax.device_get(state["params"])))
        return ok, "skip", (f"status={status} skipped_steps={skipped} "
                            f"final_loss_finite={finite}")

    def case_nonfinite_rollback(d):
        lo = steps // 2
        hi = lo + 2
        tr = _mk_trainer(model, cfg, d, steps=steps,
                         fault_plan=f"nonfinite@{lo}-{hi}",
                         max_bad_steps=3)
        state, status = tr.run()
        rb = [(r.at_step, r.to_step) for r in tr.rollbacks]
        eq = _bitwise(baseline, state["params"])
        ok = status == "done" and len(rb) == 1 and eq
        return ok, "exact", (f"status={status} rollbacks={rb} "
                             f"bitwise_equal={eq}")

    def _case_preempt(d, donate):
        at = steps - 3
        tr = _mk_trainer(model, cfg, d, steps=steps, donate=donate,
                         fault_plan=f"preempt@{at}")
        died = False
        try:
            tr.run()
        except Preempted:
            died = True
        tr2 = _mk_trainer(model, cfg, d, steps=steps, donate=donate)
        state, status = tr2.run()
        resumed = tr2.history[0]["step"] - 1 if tr2.history else None
        eq = _bitwise(baseline, state["params"])
        ok = died and status == "done" and eq
        return ok, "exact", (f"preempted={died} resumed_at={resumed} "
                             f"status={status} bitwise_equal={eq}")

    def case_preempt_donated(d):
        return _case_preempt(d, donate=True)

    def case_preempt_undonated(d):
        return _case_preempt(d, donate=False)

    def case_ckpt_corrupt(d):
        tr = _mk_trainer(model, cfg, d, steps=steps,
                         fault_plan=f"ckpt_corrupt@{steps}")
        _, status = tr.run()
        issues = tr.ckpt.verify(steps)
        # a fresh trainer must fall back to the newest verified
        # generation and replay the tail bitwise
        tr2 = _mk_trainer(model, cfg, d, steps=steps)
        state, status2 = tr2.run()
        replayed = len(tr2.history)
        eq = _bitwise(baseline, state["params"])
        ok = status == "done" and bool(issues) and status2 == "done" and \
            replayed > 0 and eq
        return ok, "exact", (
            f"corrupted={tr.fault_log} verify_issues={len(issues)} "
            f"replayed_steps={replayed} bitwise_equal={eq}")

    # ---------------------------------------------------- serve faults

    def _build_engine(**kw):
        from repro.configs import get_smoke_config
        from repro.models import build
        scfg = get_smoke_config("qwen3_0_6b")
        smodel = build(scfg)
        params = smodel.init(jax.random.PRNGKey(0))
        from repro.serve.engine import ServeEngine
        return ServeEngine(smodel, params, batch_slots=2, page=8,
                           max_len=128, chunk=8, **kw)

    def case_serve_overload(d):
        from repro.serve.engine import Admitted, Rejected
        eng = _build_engine(max_queue=3)
        res = eng.inject_burst(8, max_tokens=4, seed=0)
        n_adm = sum(isinstance(r, Admitted) for r in res)
        n_rej = sum(isinstance(r, Rejected) and r.reason == "overloaded"
                    for r in res)
        stats = eng.run()
        ok = (n_adm == 3 and n_rej == 5 and stats["requests"] == 3
              and stats["rejected_overload"] == 5
              and stats["queue_peak"] <= 3
              and stats["traced_programs"] == 2)
        return ok, "n/a", (f"admitted={n_adm} rejected={n_rej} "
                           f"stats={ {k: stats[k] for k in ('requests', 'rejected_overload', 'queue_peak', 'traced_programs')} }")

    def case_serve_deadline(d):
        eng = _build_engine()
        eng.submit("warm", [1, 2, 3], 3)
        eng.run()   # warm: both programs traced
        eng.submit("past", [1, 2, 3], 4, deadline=-1.0)
        eng.submit("slow", [1, 2, 3, 4], 100, deadline=0.001)
        eng.submit("ok", [5, 6, 7], 4)
        stats = eng.run()   # assert_max_traces budget is 0 here
        sheds = {r.rid: r.reason for r in eng.rejected}
        ok = ("ok" in eng.done and len(eng.done["ok"]) == 4
              and sheds.get("past") == "deadline"
              and sheds.get("slow") == "deadline"
              and "past" in eng.shed and "slow" in eng.shed
              and stats["shed_deadline"] == 2
              and stats["traced_programs"] == 2)
        return ok, "n/a", (f"shed={sheds} partial_tokens="
                           f"{ {k: len(v) for k, v in eng.shed.items()} } "
                           f"traced_programs={stats['traced_programs']}")

    cases = [
        ("nonfinite_skip", "nonfinite", case_nonfinite_skip),
        ("nonfinite_rollback", "nonfinite", case_nonfinite_rollback),
        ("preempt_donated", "preempt", case_preempt_donated),
        ("preempt_undonated", "preempt", case_preempt_undonated),
        ("ckpt_corrupt", "ckpt_corrupt", case_ckpt_corrupt),
        ("serve_overload", "burst", case_serve_overload),
        ("serve_deadline", "burst", case_serve_deadline),
    ]

    for name, kind, fn in cases:
        if only is not None and only not in name:
            continue
        rec = {"fault": name, "kind": kind}
        try:
            with tempfile.TemporaryDirectory() as d, \
                    warnings.catch_warnings(record=True) as caught:
                # recovery paths warn by design (fallback, rollback);
                # record them in the report instead of erroring under
                # escalated-warning test runs
                warnings.simplefilter("always")
                ok, replay, detail = fn(d)
                rec.update(recovered=bool(ok), replay=replay,
                           detail=detail, n_warnings=len(caught))
        # the sweep must survive every fault: a crash IS the finding —
        # recorded unrecovered here and turned into a nonzero exit below
        except Exception as e:  # repro-lint: disable=REP008
            rec.update(recovered=False, replay="none",
                       detail=f"sweep case died: {type(e).__name__}: {e}",
                       n_warnings=0)
        records.append(rec)
        state = "recovered" if rec["recovered"] else "UNRECOVERED"
        print(f"[chaos] {name:20s} {state}  ({rec['detail']})")

    unrecovered = [r["fault"] for r in records if not r["recovered"]]
    doc = {
        "tool": "repro.resilience",
        "mode": "offline" if offline else "live",
        "arch": cfg.name, "steps": steps,
        "baseline_status": base_status,
        "faults": records,
        "unrecovered": unrecovered,
        "ok": not unrecovered,
    }
    with open(report_path, "w") as f:
        json.dump(doc, f, indent=2)
    print(f"[chaos] {len(records) - len(unrecovered)}/{len(records)} "
          f"faults recovered -> {report_path}")
    return doc
