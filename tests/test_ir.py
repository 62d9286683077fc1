"""repro.analysis.ir: the IR-level auditors (PR 8).

Three auditors over *compiled artifacts*: collective budgets on HLO
text, pallas grid/BlockSpec races on the (grid, index_map, shape)
triple, and dtype flow on jaxprs. The acceptance pair lives in the
4-device subprocess test: the real sharded cluster attention passes its
O(S/P) all-to-all budget while a mis-sharded seq-axis-all-gather
variant fails the gate *naming the offending HLO op*. The CLI test
pins the ``ANALYSIS_ir_report.json`` schema CI consumes.
"""

import json
import os
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import trace_audit as ta
from repro.analysis.ir import (CollectiveBudget, IRAuditError, IRFinding,
                               audit_collectives, audit_grid, check_grid,
                               errors)
from repro.analysis.ir import hlo as irh
from repro.analysis.ir import pallas_check  # noqa: F401 (import check)
from repro.analysis.ir.dtype_flow import (DtypePolicy, check_dtype_flow,
                                          convert_events, dot_accumulators,
                                          dtype_report)

from _subproc import run_code

REPO = pathlib.Path(__file__).resolve().parents[1]


# ----------------------------------------------------- finding vocabulary

def test_irfinding_vocabulary_and_error():
    f = IRFinding(auditor="x", level="error", message="boom", op="%op.1")
    assert f.to_json()["level"] == "error" and "%op.1" in str(f)
    with pytest.raises(ValueError, match="level"):
        IRFinding(auditor="x", level="fatal", message="nope")
    info = IRFinding(auditor="x", level="info", message="fine")
    assert errors([info, f]) == [f]
    err = IRAuditError([info, f], label="gate")
    assert isinstance(err, AssertionError) and "boom" in str(err)
    assert err.findings == [info, f]


# ----------------------------------------- HLO collective auditor (unit)

_SEQ_AG_HLO = """\
HloModule bad, entry_computation_layout={()->bf16[1,512,8,64]{3,2,1,0}}

ENTRY %main_spmd () -> bf16[1,512,8,64] {
  %p = bf16[1,128,8,64]{3,2,1,0} parameter(0)
  %ag.7 = bf16[1,512,8,64]{3,2,1,0} all-gather(%p), dimensions={1}
  ROOT %r = bf16[1,512,8,64]{3,2,1,0} copy(%ag.7)
}
"""


def test_audit_collectives_flags_seq_axis_allgather():
    budget = CollectiveBudget(forbid_seq_allgather=True, seq_dim=1)
    fs = audit_collectives(_SEQ_AG_HLO, budget, label="unit")
    errs = errors(fs)
    assert len(errs) == 1
    assert errs[0].op == "%ag.7" and "%ag.7" in errs[0].message
    assert "sequence-axis all-gather" in errs[0].message
    # a head-axis gather of the same size is allowed
    ok = audit_collectives(_SEQ_AG_HLO.replace("dimensions={1}",
                                               "dimensions={2}"), budget)
    assert not errors(ok)
    # tiny gathers (scalar bookkeeping) are below min_gather_bytes
    small = CollectiveBudget(forbid_seq_allgather=True, seq_dim=1,
                             min_gather_bytes=1 << 30)
    assert not errors(audit_collectives(_SEQ_AG_HLO, small))
    # seq_len disambiguates whole-program audits: a dim-1 gather whose
    # output spans the sequence is an error, one spanning some other
    # extent (a weight all-gather under the sharding recipe) is not
    pinned = CollectiveBudget(forbid_seq_allgather=True, seq_dim=1,
                              seq_len=512)
    assert errors(audit_collectives(_SEQ_AG_HLO, pinned))
    weighty = CollectiveBudget(forbid_seq_allgather=True, seq_dim=1,
                               seq_len=4096)
    assert not errors(audit_collectives(_SEQ_AG_HLO, weighty))
    # whole-step audits (Trainer/ServeEngine) demote to warning: the
    # plain LM path may re-materialize k/v — visible, not a gate failure
    soft = CollectiveBudget(forbid_seq_allgather=True, seq_dim=1,
                            seq_allgather_level="warning")
    fs = audit_collectives(_SEQ_AG_HLO, soft)
    assert not errors(fs)
    assert any(f.level == "warning" and "sequence-axis" in f.message
               for f in fs)


def test_audit_collectives_enforces_a2a_budget():
    hlo = _SEQ_AG_HLO.replace("all-gather", "all-to-all")
    over = CollectiveBudget(a2a_bytes=1024, forbid_seq_allgather=False)
    errs = errors(audit_collectives(hlo, over))
    assert len(errs) == 1 and "O(S/P) budget" in errs[0].message
    under = CollectiveBudget(a2a_bytes=1 << 30, forbid_seq_allgather=False)
    assert not errors(audit_collectives(hlo, under))


def test_hlo_parser_single_home_and_shim_agreement():
    """Satellite: launch/hlo_analysis re-exports analysis.ir.hlo — one
    parser, two historical import paths, identical results."""
    from repro.launch import hlo_analysis as old
    assert old.comm_summary is irh.comm_summary
    assert old.analyze is irh.analyze
    assert old.top_ops is irh.top_ops
    hlo = jax.jit(lambda a, b: a @ b).lower(
        jnp.ones((8, 8)), jnp.ones((8, 8))).compile().as_text()
    assert old.comm_summary(hlo) == irh.comm_summary(hlo)
    # and benchmarks consume the new home directly (no stale copy)
    bench = (REPO / "benchmarks" / "scalability.py").read_text()
    assert "repro.analysis.ir.hlo" in bench


def test_collective_report_schema():
    rep = irh.collective_report(
        _SEQ_AG_HLO, CollectiveBudget(forbid_seq_allgather=True), label="u")
    assert {"label", "bytes", "count", "total_bytes", "ops", "budget",
            "findings"} <= set(rep)
    assert rep["ops"][0]["kind"] == "all-gather"
    json.dumps(rep)  # must already be JSON-ready


# ------------------------------------------------ pallas grid race check

def test_grid_audit_catches_seeded_write_race():
    """Two non-adjacent grid cells map to the same output block — the
    class of bug the contiguous-revisit rule exists for."""
    fs = audit_grid((4,), out_specs=[((2,), lambda i: (i % 2,))],
                    out_shapes=[(8,)], label="seeded")
    errs = errors(fs)
    assert errs, [str(f) for f in fs]
    assert "race" in errs[0].message or "revisit" in errs[0].message
    with pytest.raises(IRAuditError, match="seeded"):
        check_grid((4,), out_specs=[((2,), lambda i: (i % 2,))],
                   out_shapes=[(8,)], label="seeded")


def test_grid_audit_allows_contiguous_accumulate_revisits():
    # the online-softmax pattern: innermost axis revisits one out block
    fs = audit_grid((2, 3), out_specs=[((4,), lambda i, j: (i,))],
                    out_shapes=[(8,)])
    assert not errors(fs), [str(f) for f in fs]


def test_grid_audit_bounds_and_divisibility():
    # block index past the end of the array
    fs = audit_grid((4,), in_specs=[((2,), lambda i: (i,))],
                    in_shapes=[(6,)])
    assert any("bounds" in f.message or "out of" in f.message
               for f in errors(fs)), [str(f) for f in fs]
    # block shape does not tile the array
    fs = audit_grid((2,), in_specs=[((3,), lambda i: (i,))],
                    in_shapes=[(8,)])
    assert errors(fs), [str(f) for f in fs]


def test_grid_audit_passes_real_cluster_triple():
    """The known-good layout: the actual forward-kernel triple from
    grid_triple with a concrete scalar-prefetch block index."""
    from repro.core.reformation import lm_local_global_layout
    # auditing the grid contract itself, not bypassing dispatch.  # repro-lint: disable=REP002
    from repro.kernels.cluster_attention import fwd_stream, grid_triple

    lay = lm_local_global_layout(512, bq=64, bk=64, window=128, n_global=64)
    nq, mb = lay.block_idx.shape
    idx, n = fwd_stream(jnp.asarray(lay.block_idx)[None], interpret=True)
    prefetch = (np.asarray(idx), lay.block_idx.reshape(-1))
    live = int((lay.block_idx >= 0).sum())
    assert int(n) == live < nq * mb   # the grid runs the live slots only
    t = grid_triple(2, 512, 4, 2, 128, nq, mb, int(n), bk=64,
                    return_residuals=True)
    assert t["grid"] == (2, 4, live)
    fs = audit_grid(t["grid"], t["in_specs"], t["out_specs"],
                    t["in_shapes"], t["out_shapes"],
                    scalar_prefetch=prefetch, label="cluster fwd")
    assert not errors(fs), [str(f) for f in fs]
    # a bound short of the last q-row's entries leaves that row unwritten
    short = live - int((lay.block_idx[-1] >= 0).sum())
    t = grid_triple(2, 512, 4, 2, 128, nq, mb, short, bk=64)
    fs = audit_grid(t["grid"], t["in_specs"], t["out_specs"],
                    t["in_shapes"], t["out_shapes"],
                    scalar_prefetch=prefetch, label="cluster fwd")
    assert any("never written" in f.message for f in fs)


def test_ops_dispatch_grid_audit_accepts_good_layout():
    """The dispatch-layer hook (kernels/ops._grid_race_reason): a valid
    concrete layout audits clean (None) and memoizes; tracers skip."""
    from repro.kernels import ops as kops

    q = jnp.ones((1, 256, 4, 32), jnp.float32)
    bi = jnp.zeros((1, 4, 2), jnp.int32)
    assert kops._grid_race_reason(q, q[:, :, :2], bi, None, None) is None
    before = len(kops._GRID_AUDITED)
    assert kops._grid_race_reason(q, q[:, :, :2], bi, None, None) is None
    assert len(kops._GRID_AUDITED) == before  # memo hit, not re-audit


# --------------------------------------------------- walk_jaxpr edge cases

def test_walk_jaxpr_sees_closed_over_consts():
    c = jnp.arange(4.0)

    def f(x):
        return x * jnp.sin(c)

    counts = ta.primitive_counts(f, jnp.ones((4,)))
    assert counts["sin"] == 1 and counts["mul"] == 1


def test_walk_jaxpr_custom_vjp_bwd_only_under_grad():
    """The pinned contract from walk_jaxpr's docstring: the bwd jaxpr
    materializes under jax.make_jaxpr(jax.grad(f)), not under plain
    tracing of f."""

    @jax.custom_vjp
    def f(x):
        return jnp.sum(x * x)

    def fwd(x):
        return f(x), x

    def bwd(res, g):
        return (2.0 * g * jnp.tanh(res),)   # tanh only exists in bwd

    f.defvjp(fwd, bwd)
    x = jnp.ones((3,))
    fwd_counts = {}
    for eqn in ta.walk_jaxpr(jax.make_jaxpr(f)(x)):
        fwd_counts[eqn.primitive.name] = \
            fwd_counts.get(eqn.primitive.name, 0) + 1
    assert "tanh" not in fwd_counts
    grad_counts = {}
    for eqn in ta.walk_jaxpr(jax.make_jaxpr(jax.grad(f))(x)):
        grad_counts[eqn.primitive.name] = \
            grad_counts.get(eqn.primitive.name, 0) + 1
    assert grad_counts.get("tanh", 0) >= 1, grad_counts


def test_walk_jaxpr_scan_body_inside_grad():
    def f(x):
        def body(c, _):
            return jnp.cos(c), c
        out, _ = jax.lax.scan(body, x, None, length=3)
        return out.sum()

    names = [e.primitive.name
             for e in ta.walk_jaxpr(jax.make_jaxpr(jax.grad(f))(
                 jnp.ones((2,))))]
    assert "cos" in names and "sin" in names  # body + its transpose


# ------------------------------------------------------------ dtype flow

def test_convert_events_and_dot_accumulators():
    def f(x, y):
        h = x.astype(jnp.float32)                    # upcast
        d = jax.lax.dot_general(
            x, y, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)      # wide accumulator
        return h.sum() + d.astype(jnp.bfloat16).sum()  # downcast

    x = jnp.ones((4, 4), jnp.bfloat16)
    jaxpr = jax.make_jaxpr(f)(x, x)
    evs = convert_events(jaxpr)
    assert any(e["widens"] for e in evs) and any(not e["widens"]
                                                 for e in evs)
    (dot,) = dot_accumulators(jaxpr)
    assert dot["accum"] == "float32"


def test_dtype_flow_flags_narrow_accumulator():
    def narrow(x, y):
        return jax.lax.dot_general(x, y, (((1,), (0,)), ((), ())))

    x = jnp.ones((4, 4), jnp.bfloat16)
    fs = check_dtype_flow(narrow, x, x, label="narrow")  # warning only
    assert any(f.level == "warning" and "bfloat16" in f.message
               for f in fs), [str(f) for f in fs]
    with pytest.raises(IRAuditError, match="narrow"):
        check_dtype_flow(narrow, x, x, policy=DtypePolicy(strict=True),
                         label="narrow")
    rep = dtype_report(narrow, x, x, label="narrow")
    assert {"label", "policy", "n_converts", "n_dots", "converts", "dots",
            "findings"} <= set(rep)
    json.dumps(rep)


# ------------------------------- the acceptance pair: 4-way sharded mesh

def test_sharded_attention_budget_pass_and_misshard_fail():
    """On a 4-way mesh: the real sharded cluster attention (run with the
    REPRO_IR_AUDIT gate live) stays inside its O(S/P) all-to-all budget,
    while a mis-sharded variant that all-gathers the sequence axis fails
    check_collectives naming the offending HLO op."""
    out = run_code("""
        import os
        os.environ["REPRO_IR_AUDIT"] = "1"
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro import compat
        from repro.analysis.ir import (CollectiveBudget, IRAuditError,
                                       check_collectives)
        from repro.core.reformation import lm_local_global_layout
        from repro.parallel.cluster_parallel import (
            cluster_a2a_budget, sharded_cluster_attention)

        mesh = compat.make_mesh((4,), ("model",))
        B, S, H, D = 1, 512, 8, 64

        # --- good: the real path, budget gate live via REPRO_IR_AUDIT
        lay = lm_local_global_layout(S, bq=64, bk=64, window=128,
                                     n_global=64)
        bidx = jnp.asarray(lay.block_idx)[None]
        key = jax.random.PRNGKey(0)
        q = jax.random.normal(key, (B, S, H, D), jnp.bfloat16)
        out = sharded_cluster_attention(q, q, q, bidx, mesh=mesh,
                                        axis="model", dp_axes=(), bq=64,
                                        bk=64, causal=True)
        assert out.shape == q.shape
        print("GOOD_PASSED_GATE")

        # --- bad: gather the whole sequence on every device
        def bad_inner(q, k, v):
            kf = jax.lax.all_gather(k, "model", axis=1, tiled=True)
            vf = jax.lax.all_gather(v, "model", axis=1, tiled=True)
            s = jnp.einsum("bqhd,bkhd->bhqk", q, kf)
            p = jax.nn.softmax(s.astype(jnp.float32), axis=-1)
            return jnp.einsum("bhqk,bkhd->bqhd", p.astype(q.dtype), vf)

        spec = P(None, "model", None, None)
        fn = jax.jit(compat.shard_map(bad_inner, mesh=mesh,
                                      in_specs=(spec,) * 3,
                                      out_specs=spec))
        with compat.use_mesh(mesh):
            compiled = fn.lower(q, q, q).compile()
        budget = CollectiveBudget(
            a2a_bytes=cluster_a2a_budget(q.shape, q.shape, 2, 4),
            seq_dim=1, forbid_seq_allgather=True)
        try:
            check_collectives(compiled, budget, label="misshard")
        except IRAuditError as e:
            msg = str(e)
            assert "sequence-axis all-gather" in msg, msg
            # names the HLO op and records its collective kind (XLA's
            # instruction-name spelling varies across versions)
            bad = [f for f in e.findings if f.level == "error"]
            assert bad and all(f.data["kind"] == "all-gather"
                               and f.op in msg for f in bad), msg
            print("BAD_CAUGHT")
        else:
            raise SystemExit("mis-sharded variant passed the gate")
        """, devices=4)
    assert "GOOD_PASSED_GATE" in out and "BAD_CAUGHT" in out


# ----------------------------------------- engine/trainer first-compile

def test_trainer_ir_audit_smoke(tmp_path):
    from repro.configs import get_smoke_config
    from repro.data.lm_pipeline import LMDataConfig, lm_batch
    from repro.models import build
    from repro.runtime.trainer import Trainer, TrainerConfig

    cfg = get_smoke_config("smollm_135m")
    dc = LMDataConfig(vocab_size=cfg.vocab_size, seq_len=32, global_batch=2)
    tc = TrainerConfig(steps=1, ckpt_every=100, ckpt_dir=str(tmp_path),
                       ir_audit=True)
    tr = Trainer(build(cfg), tc, lambda s: lm_batch(dc, s))
    assert tr._ir_audit_enabled()
    findings = tr.ir_audit()
    assert findings is tr.ir_findings and findings
    assert all(f.level != "error" for f in findings)
    assert any(f.auditor == "dtype_flow" for f in findings)


# ----------------------------------------------- the --ir CLI + report

def test_cli_ir_mode_writes_schema_report(tmp_path):
    from repro.analysis.ir.run import IR_REPORT_SCHEMA

    report = tmp_path / "ANALYSIS_ir_report.json"
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src")
    env.pop("XLA_FLAGS", None)           # run.ensure_devices must cope
    r = subprocess.run(
        [sys.executable, "-m", "repro.analysis", "--ir",
         "--report", str(report)],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr
    doc = json.loads(report.read_text())
    assert set(IR_REPORT_SCHEMA) <= set(doc)
    assert doc["tool"] == "repro.analysis.ir" and doc["ok"] is True
    assert set(doc["programs"]) == {"sharded", "serve"}
    sharded = doc["programs"]["sharded"]
    assert "skipped" not in sharded, sharded
    # the tier-1 program passed its O(S/P) budget with real a2a traffic
    coll = sharded["collectives"]
    assert coll["bytes"]["all-to-all"] > 0
    assert coll["bytes"]["all-to-all"] <= coll["budget"]["a2a_bytes"]
    assert not errors([IRFinding(**f) for f in coll["findings"]])
    # every flattened finding carries the documented fields
    assert doc["findings"], "auditors must emit at least info findings"
    for f in doc["findings"]:
        assert {"auditor", "level", "message", "program", "op",
                "data"} <= set(f)
    assert doc["n_errors"] == 0
