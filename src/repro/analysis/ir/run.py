"""The ``python -m repro.analysis --ir`` mode: run every IR auditor
against the tier-1 programs and write ``ANALYSIS_ir_report.json``.

Two program families, matching what CI actually trains and serves:

* **sharded** — the 4-way-mesh sharded cluster attention
  (``parallel/cluster_parallel``) on the LM local+global layout, on
  fake CPU devices. Audited three ways: compiled collectives against
  the O(S/P) :func:`cluster_a2a_budget` (+ the seq-axis all-gather
  ban), the forward kernel's pallas grid triple against the concrete
  layout, and the traced program's dtype flow.
* **serve** — the :class:`~repro.serve.engine.ServeEngine` prefill +
  decode programs of the smoke LM, via ``engine.ir_audit()``.

Report schema (``IR_REPORT_SCHEMA``): ``tool`` ("repro.analysis.ir"),
``mode`` ("ir"), ``programs`` ({name: per-program detail — the
``collective_report`` / ``dtype_report`` dicts and raw finding lists}),
``findings`` (every finding, flattened, in ``IRFinding.to_json`` form:
auditor / level / message / program / op / data), ``n_errors``, and
``ok`` (no error-level findings). CI fails on ``ok == false`` — a
budget regression fails the job, not just warns.

Importing this module must stay side-effect free; ``ensure_devices``
mutates XLA_FLAGS and therefore must run before jax first touches a
backend (``repro.analysis.__main__`` imports no jax, so the CLI path
is safe).
"""

from __future__ import annotations

import json
import os

IR_REPORT_SCHEMA = ("tool", "mode", "programs", "findings", "n_errors",
                    "ok")

DEFAULT_REPORT = "ANALYSIS_ir_report.json"


def ensure_devices(p: int) -> None:
    """Give this process >= p fake CPU devices. Must run before jax
    initializes its backend — a no-op if XLA_FLAGS already forces a
    device count (CI, tests/_subproc)."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={p}").strip()


def sharded_attention_report(p: int = 4, *, seq: int = 1024, heads: int = 8,
                             d_head: int = 64, bq: int = 128) -> dict:
    """All three auditors over the p-way sharded cluster attention."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import compat
    from repro.analysis.ir import hlo as irh
    from repro.analysis.ir import pallas_check
    from repro.analysis.ir.dtype_flow import dtype_report
    from repro.core.reformation import lm_local_global_layout
    # the auditor needs the kernel's grid contract, not its dispatch.  # repro-lint: disable=REP002
    from repro.kernels.cluster_attention import fwd_stream, grid_triple
    from repro.kernels.ops import LANE
    from repro.parallel.cluster_parallel import (cluster_a2a_budget,
                                                 sharded_cluster_attention)

    label = f"sharded_attention(p={p})"
    if jax.local_device_count() < p:
        return {"label": label, "skipped":
                f"needs {p} devices, have {jax.local_device_count()} "
                f"(set XLA_FLAGS=--xla_force_host_platform_device_count)"}
    mesh = compat.make_mesh((p,), ("model",))
    lay = lm_local_global_layout(seq, bq=bq, bk=bq, window=max(2 * bq, seq // 4),
                                 n_global=bq)
    bidx = jnp.asarray(lay.block_idx)[None]
    q = jax.ShapeDtypeStruct((1, seq, heads, d_head), jnp.bfloat16)
    fn = jax.jit(lambda a, b, c: sharded_cluster_attention(
        a, b, c, bidx, mesh=mesh, axis="model", dp_axes=(), bq=bq, bk=bq,
        causal=True))
    with compat.use_mesh(mesh):
        lowered = fn.lower(q, q, q)
        hlo_text = lowered.compile().as_text()
        jaxpr = jax.make_jaxpr(fn)(q, q, q)

    budget = irh.CollectiveBudget(
        a2a_bytes=cluster_a2a_budget(q.shape, q.shape, 2, p),
        seq_dim=1, forbid_seq_allgather=True, seq_len=seq)
    coll = irh.collective_report(hlo_text, budget, label=label)

    # the forward kernel triple exactly as the per-device launch builds
    # it: local head chunk, full (post-a2a) sequence, lane-padded Dh
    nq, mb = lay.block_idx.shape
    idx, n = fwd_stream(jnp.asarray(lay.block_idx)[None], interpret=True)
    triple = grid_triple(1, seq, heads // p, heads // p,
                         d_head + (-d_head % LANE), nq, mb, int(n), bk=bq,
                         per_graph=True, return_residuals=True)
    idx = (np.asarray(idx), np.asarray(lay.block_idx, np.int32).reshape(-1))
    grid_findings = pallas_check.audit_grid(
        triple["grid"], triple["in_specs"], triple["out_specs"],
        triple["in_shapes"], triple["out_shapes"], scalar_prefetch=idx,
        label=label)

    dt = dtype_report(jaxpr, label=label)
    return {"label": label, "collectives": coll,
            "pallas_grid": {"grid": list(triple["grid"]),
                            "findings": [f.to_json()
                                         for f in grid_findings]},
            "dtype_flow": dt}


def serve_report(arch: str = "qwen3_0_6b") -> dict:
    """ServeEngine first-compile audit (collectives + dtype flow) of the
    smoke LM's prefill and decode programs."""
    import jax

    from repro.configs import get_smoke_config
    from repro.models import build
    from repro.serve import ServeEngine

    label = f"serve({arch})"
    cfg = get_smoke_config(arch)
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = ServeEngine(model, params, batch_slots=2, page=8, chunk=8,
                      max_len=32)
    findings = eng.ir_audit()
    return {"label": label,
            "findings": [f.to_json() for f in findings]}


def _collect_findings(entry: dict) -> list[dict]:
    found: list[dict] = []
    for v in entry.values():
        if isinstance(v, dict):
            found += v.get("findings", [])
        elif isinstance(v, list):
            found += [f for f in v if isinstance(f, dict)
                      and "auditor" in f]
    return found


def build_report(programs=("sharded", "serve"), *, p: int = 4) -> dict:
    """Assemble the full IR report (keys: ``IR_REPORT_SCHEMA``)."""
    out: dict = {"tool": "repro.analysis.ir", "mode": "ir",
                 "programs": {}, "findings": []}
    if "sharded" in programs:
        entry = sharded_attention_report(p)
        out["programs"]["sharded"] = entry
        out["findings"] += _collect_findings(entry)
    if "serve" in programs:
        entry = serve_report()
        out["programs"]["serve"] = entry
        out["findings"] += _collect_findings(entry)
    out["n_errors"] = sum(1 for f in out["findings"]
                          if f.get("level") == "error")
    out["ok"] = out["n_errors"] == 0
    return out


def main(report_path: str | None = None,
         programs=("sharded", "serve"), p: int = 4) -> int:
    """CLI entry (called from ``repro.analysis.__main__``): write the
    report, print a one-line summary, exit 1 iff error findings."""
    ensure_devices(p)
    rep = build_report(programs, p=p)
    path = report_path or DEFAULT_REPORT
    with open(path, "w") as f:
        json.dump(rep, f, indent=1, default=str)
    n = len(rep["findings"])
    print(f"repro.analysis --ir: {len(rep['programs'])} program(s), "
          f"{n} finding(s), {rep['n_errors']} error(s) -> {path}")
    for f in rep["findings"]:
        if f.get("level") == "error":
            print(f"  ERROR [{f.get('program', '')}] {f.get('op', '')}: "
                  f"{f.get('message', '')}")
    return 0 if rep["ok"] else 1
