#!/usr/bin/env python3
"""One run of one cell with the program's spans read.

  python3 bench/spanrun.py --workload <cell> --seed <n> --seconds <s> \
      [--trace 0|1] [--record 0|1]

Set-up, window and traced period as ``bench/run.py`` runs them (the
task, trainer and step-boundary window of bench/cell.py), with no
reference comparison after. ``--record 1`` runs set-up and training under
``repro.runtime.spans.recording()``; ``--trace 1`` adds one traced period
as run.py does. Prints one JSON line:

* always: ``nodes_per_s``, ``setup_s`` (as run.py computes them), each
  window step's ``step_s`` and ``wait_s``, ``prep_s``;
* ``--record 1``: ``compile_s`` (tracing, lowering and XLA compile up to
  the window), ``compiles_by_step`` (none should fall in the window or
  the traced period), ``step_host_ms`` (window and traced period, less
  the two steps whose hook starts or stops the profiler), the set-up
  spans and the seconds of set-up they cover, and the whole run's span
  table;
* ``--trace 1`` (with ``--record 1``): ``idle_by_program_span`` and
  ``idle_named_share`` of the traced period, beside its idle seconds and
  ``step_host_ms`` x steps.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import warnings  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))


def run(workload: str, seed: int, seconds: float, trace: bool,
        record: bool, *, t_start: float, require_tpu: bool = True,
        nodes: int | None = None) -> dict:
    import jax

    import cell
    import devtrace
    import progspans

    spec = cell.load_cell(workload)
    if nodes is not None:
        spec["config"]["nodes"] = nodes
    chips = int(spec["workload"]["chips"])
    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < chips):
        raise cell.NoChip(f"cell {workload} needs {chips} TPU chip(s)")
    os.environ["REPRO_TUNE"] = "0"
    if require_tpu:
        jax.config.update("jax_compilation_cache_dir",
                          str(cell.ROOT / ".jax_cache"))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    sys.path.insert(0, str(cell.ROOT / "src"))
    from repro.runtime import spans

    P = int(spec["traffic"]["interleave_period"])
    tdir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    window = cell.Window(P, seconds, tdir)
    rec = None
    with contextlib.ExitStack() as stack, warnings.catch_warnings():
        warnings.filterwarnings("error", message=r"repro\.kernels\.ops")
        if record:
            rec = stack.enter_context(spans.recording())
        graph, task, trainer, ckpt = cell.setup_program(spec, seed, window)
        trainer.run(cell.weight_seed(seed))

    marks, hist, end = window.marks, trainer.history, window.end
    win = hist[P:end + 1]
    window_s = marks[end] - marks[P - 1]
    out = {"workload": workload, "seed": seed, "record": record,
           "trace": trace, "device": devs[0].device_kind,
           "nodes_per_s": spec["config"]["nodes"] * len(win) / window_s,
           "setup_s": marks[P - 1] - t_start,
           "prep_s": float(task.prep_seconds),
           "step_s": [h["seconds"] for h in win],
           "wait_s": [h["wait_s"] for h in win]}
    # the harness starts the profiler inside step ``end`` and stops it
    # inside the traced period's last step (cell.Window, from the task's
    # log_extras hook): host times leave those two steps out
    host_steps = {"window": range(P, end if trace else end + 1),
                  "traced": range(end + 1, window.trace_end or end + 1)}
    if rec is not None:
        by_step = spans.compiles_by_step(rec)
        out.update(
            compile_s=progspans.compile_s(by_step, P),
            compiles_by_step={str(k): v for k, v in by_step.items()},
            compiles_in_window=sorted(
                k for k in by_step if k is not None and k >= P),
            step_host_ms={k: progspans.step_host_ms(rec, v)
                          for k, v in host_steps.items()},
            setup_spans=progspans.setup_spans(rec, P),
            setup_named_s=progspans.setup_named_s(rec, P),
            span_table=spans.table(rec.spans))
    if trace:
        xp = devtrace.find_xplane(tdir)
        plain = devtrace.load(xp)
        red = devtrace.reduce(plain)
        named = progspans.idle_by_program_span(plain, progspans.load(xp))
        if red:
            out["traced_idle_s"] = red["window_s"] - red["busy_s"]
            out["traced_window_s"] = red["window_s"]
            out["idle_gaps"] = red["idle_gaps"]
        if named:
            out["idle_by_program_span"] = named["idle_s"]
            out["idle_named_share"] = named["named_share"]
        host = (out.get("step_host_ms") or {}).get("traced")
        if host is not None:
            # the traced window holds P step lengths
            out["traced_host_s"] = host * 1e-3 * P
        shutil.rmtree(tdir, ignore_errors=True)
    shutil.rmtree(ckpt, ignore_errors=True)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--record", type=int, choices=(0, 1), default=1)
    args = ap.parse_args(argv)

    import cell

    try:
        out = run(args.workload, args.seed, args.seconds, bool(args.trace),
                  bool(args.record), t_start=T_START)
    except cell.NoChip as e:
        print(f"spanrun: {e}", file=sys.stderr)
        return 3
    print(json.dumps(out, default=str))
    return 0


if __name__ == "__main__":
    sys.exit(main())
