"""One run of one benchmark cell: graph-transformer training through the
program's own ``NodeTask`` and ``Trainer``, a measured window of whole
interleave periods, optionally one traced period, and the comparison with
the plain reference.

The harness copies no part of the trainer loop. It drives
``Trainer.run(seed)`` and watches it through the task it hands the
trainer: a ``NodeTask`` subclass whose ``variant`` (called as a step
starts) and ``log_extras`` (called once the step's metrics are read back,
so the device has finished it) mark step boundaries. At a boundary it
changes two fields of the trainer's config, which the loop reads every
step, and nothing else: ``ckpt_every`` (saves after steps 1, 2 and 3, for
the comparison, and none after) and ``steps`` (ends the run at the
window's last boundary).

Timeline of a run (P = the mix's interleave period, 8):

* set-up: graph from the seed, the task's host prep (all ladder rungs),
  the layout frozen at the mix's rung, weights from the seed inside
  ``Trainer.run``, steps 0 .. P-1 (step 0 dense, 1 .. P-1 sparse: both
  programs compile or load from the cache and run once);
* window: from the end of step P-1 to the end of the last whole period
  that fits in ``--seconds`` (at least one);
* with ``--trace 1``: one more period under the profiler;
* after the run: peak memory, then the comparison (bench/correct.py).
"""

from __future__ import annotations

import gc
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time
import warnings
from pathlib import Path

import numpy as np

import correct
import counts
import devtrace
import graphs
import layout
import peaks as peak_table
import reference

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
SEED_MOD = 2**31 - 1      # weights key: PRNGKey takes 32-bit seeds


class NoChip(RuntimeError):
    """No accelerator, or fewer chips than the cell asks for."""


def load_cell(name: str) -> dict:
    man = json.loads((ROOT / "BENCHMARK.json").read_text())
    work = {w["name"]: w for w in man["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = work[name]
    conf = {c["name"]: c for c in man["configs"]}[w["config"]]
    return {
        "manifest": man, "workload": w,
        "config": json.loads((ROOT / conf["file"]).read_text()),
        "traffic": json.loads(
            (BENCH / "traffic" / f"{w['traffic']}.json").read_text()),
        "limits": json.loads(
            (BENCH / "limits" / f"{name}.json").read_text()),
    }


def weight_seed(seed: int) -> int:
    return seed % SEED_MOD


# ------------------------------------------------------------ the window

class Window:
    """Step-boundary bookkeeping, driven by the task's hooks."""

    def __init__(self, period: int, seconds: float, trace_dir):
        self.P = period
        self.seconds = seconds
        self.trace_dir = trace_dir
        self.marks: list[float] = []
        self.trainer = None
        self.end = None            # last step of the measured window
        self.trace_end = None      # last step of the traced period
        self._open: list = []      # open host spans (innermost last)

    def _enter(self, name: str):
        import jax
        a = jax.profiler.TraceAnnotation(name)
        a.__enter__()
        self._open.append(a)

    def _exit(self):
        self._open.pop().__exit__(None, None, None)

    @property
    def tracing(self) -> bool:
        return self.trace_end is not None and len(self._open) > 0

    def step_start(self, step: int, variant: str):
        if self.tracing:
            self._exit()                          # bench:between_steps
            self._enter(f"bench:step.{variant}")

    def step_end(self):
        now = time.perf_counter()
        s = len(self.marks)
        self.marks.append(now)
        cfg = self.trainer.cfg
        cfg.ckpt_every = 1 if s < 3 else 10**9
        P = self.P
        if self.end is None:
            if (s + 1) % P or s < 2 * P - 1:
                return
            elapsed = now - self.marks[P - 1]
            last = now - self.marks[s - P]
            if elapsed + last <= self.seconds:
                return
            self.end = s
            if self.trace_dir is None:
                cfg.steps = s + 1
                return
            import jax
            jax.profiler.start_trace(self.trace_dir)
            self.trace_end = s + P
            self._enter(devtrace.WINDOW)
            self._enter("bench:between_steps")
        elif self.trace_end is not None and self._open:
            self._exit()                          # bench:step.<variant>
            if s == self.trace_end:
                self._exit()                      # the traced window
                import jax
                jax.profiler.stop_trace()
                cfg.steps = s + 1
            else:
                self._enter("bench:between_steps")


def make_task(g, cfg, block: dict, window: Window):
    from repro.tasks import NodeTask

    class BenchNodeTask(NodeTask):
        def variant(self, step, interleave_period):
            v = super().variant(step, interleave_period)
            window.step_start(step, v)
            return v

        def log_extras(self):
            window.step_end()
            return super().log_extras()

    return BenchNodeTask(g, cfg, bq=block["bq"], bk=block["bk"])


# ---------------------------------------------------- reference inputs

def lap_pe(n, src, dst, k):
    """The first k non-trivial eigenvectors of the symmetric normalised
    Laplacian (GT's positional encoding), dense eigh in float64."""
    a = np.zeros((n, n), np.float64)
    a[src, dst] = 1.0
    a = np.maximum(a, a.T)
    d = a.sum(1)
    dinv = 1.0 / np.sqrt(np.maximum(d, 1e-9))
    lap = np.eye(n) - (a * dinv[None, :]) * dinv[:, None]
    _, v = np.linalg.eigh(lap)
    pe = v[:, 1:k + 1]
    if pe.shape[1] < k:
        pe = np.pad(pe, ((0, 0), (0, k - pe.shape[1])))
    return pe.astype(np.float32)


def reference_inputs(c, graph, perm, buckets):
    """The reference's inputs in the program's sequence order (``perm``:
    node id at each position after the global tokens), built from the
    benchmark's graph; ``buckets`` is the benchmark's own (S, S) layout
    (bench/layout.py)."""
    n, src, dst, feat, labels = graph
    m = c["model"]
    ng = m["n_global"]
    S = buckets.shape[0]
    inv = np.empty(n, np.int64)
    inv[perm] = np.arange(n)
    ps, pd = inv[src], inv[dst]
    x = {"feat": np.zeros((S, m["feat_dim"]), np.float32),
         "in_deg": np.zeros(S, np.int32), "out_deg": np.zeros(S, np.int32),
         "labels": np.full(S, -1, np.int32)}
    x["feat"][ng:ng + n] = feat[perm]
    cap = m["max_degree"] - 1
    x["in_deg"][ng:ng + n] = np.minimum(np.bincount(pd, minlength=n), cap)
    x["out_deg"][ng:ng + n] = np.minimum(np.bincount(ps, minlength=n), cap)
    x["labels"][ng:ng + n] = labels[perm]
    if "lap_pe" in c["encodings"]:
        x["lap_pe"] = np.zeros((S, c["lap_pe_dim"]), np.float32)
        x["lap_pe"][ng:ng + n] = lap_pe(n, ps, pd, c["lap_pe_dim"])
    x["buckets"] = buckets
    return x


def own_layout(c, t, graph, perm):
    """The benchmark's own (S, S) layout of the cell (bench/layout.py) at
    the mix's threshold, beta_thre = the mix's multiple of beta_G."""
    n, src = graph[0], graph[1]
    ng, blk = c["model"]["n_global"], c["block"]
    align = max(blk["bq"], blk["bk"])
    S = -(-(n + ng) // align) * align
    beta_thre = t["beta_thre_over_beta_g"] * (src.size / float(n) ** 2)
    return layout.admitted(graph, perm, ng, S, beta_thre,
                           c["reform"]["clusters"], c["reform"]["tile"],
                           align)


def layout_mismatches(own, prog) -> int:
    """Pairs whose bucket differs between the benchmark's layout and the
    program's; every pair when their sizes differ."""
    if own.shape != prog.shape:
        return max(own.size, prog.size)
    return int((own != prog).sum())


def is_perm(perm, n: int) -> bool:
    p = np.asarray(perm)
    return p.shape == (n,) and bool((np.sort(p) == np.arange(n)).all())


def variants(k: int, period: int):
    return ["dense" if period > 0 and i % period == 0 else "sparse"
            for i in range(k)]


# ------------------------------------------------------------- program

def setup_program(spec: dict, seed: int, window: Window):
    """Graph, task, model and trainer as ``launch/train.py --task node``
    builds them, with the layout frozen at the mix's rung."""
    from repro.configs import get_config
    from repro.configs.base import ShapeConfig
    from repro.core.graph import Graph
    from repro.models import build
    from repro.runtime.trainer import Trainer, TrainerConfig

    c, t, w = spec["config"], spec["traffic"], spec["workload"]
    graph = graphs.make_graph(c["nodes"], t, seed)
    n, src, dst, feat, labels = graph
    cfg = get_config(c["arch"]).replace(**c["model"])
    model = build(cfg)
    task = make_task(Graph(n, src, dst, feat, labels), cfg, c["block"],
                     window)
    tuner = task.tuner
    tuner.load_state_dict(dict(tuner.state_dict(),
                               pos=int(t["beta_thre_rung"])))
    mesh = recipe = None
    if w["chips"] > 1:
        from repro.launch.mesh import make_host_mesh
        from repro.parallel.sharding import recipe_for
        mesh = make_host_mesh(model=w["chips"])
        recipe = recipe_for(ShapeConfig("graph", "train",
                                        task.layout.seq_len, 1), mesh)
    o = t["optimizer"]
    ckpt = tempfile.mkdtemp(prefix="bench_ckpt_")
    tc = TrainerConfig(steps=int(o["horizon"]), ckpt_every=1,
                       ckpt_dir=ckpt, keep=4, lr=o["lr"],
                       warmup=int(o["warmup"]),
                       weight_decay=o["weight_decay"],
                       interleave_period=int(t["interleave_period"]),
                       elastic_every=0)
    trainer = Trainer(model, tc, task=task, mesh=mesh, recipe=recipe)
    window.trainer = trainer
    return graph, task, trainer, ckpt


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        t_start: float, require_tpu: bool = True,
        nodes: int | None = None):
    """Returns (result, checks, notes); raises NoChip without a TPU.
    ``require_tpu=False`` and a small ``nodes`` run the same path on the
    host (the CPU tests); no device number is then meaningful."""
    spec = load_cell(workload)
    if nodes is not None:
        spec["config"]["nodes"] = nodes
    w, c, t = spec["workload"], spec["config"], spec["traffic"]
    chips = int(w["chips"])

    import jax

    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < chips):
        raise NoChip(f"cell {workload} needs {chips} TPU chip(s); JAX "
                     f"sees {len(devs)} {devs[0].platform} device(s)")
    devs = devs[:chips]
    os.environ["REPRO_TUNE"] = "0"
    if require_tpu:
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    sys.path.insert(0, str(ROOT / "src"))
    from repro.kernels import ops as kops

    dispatch = kops.dispatch_table()["cluster_attention"]
    if require_tpu and dispatch != "compiled":
        raise RuntimeError(f"cluster_attention dispatches {dispatch!r}")

    notes: dict = {"dispatch": dispatch}
    P = int(t["interleave_period"])
    tdir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    window = Window(P, seconds, tdir)
    seed32 = weight_seed(seed)
    with warnings.catch_warnings():
        # a silent fallback off the kernel path is a failed run
        warnings.filterwarnings("error", message=r"repro\.kernels\.ops")
        graph, task, trainer, ckpt = setup_program(spec, seed, window)
        trainer.run(seed32)

    marks, hist = window.marks, trainer.history
    end = window.end
    if end is None:
        raise RuntimeError(f"run stopped after {len(hist)} steps, before "
                           f"the window closed")
    win = hist[P:end + 1]
    window_s = marks[end] - marks[P - 1]
    setup_s = marks[P - 1] - t_start
    failed = sum(1 for h in win
                 if not math.isfinite(h["loss"]) or h["skipped"])
    mem = [d.memory_stats() or {} for d in devs]
    peak = max(int(ms.get("peak_bytes_in_use", 0)) for ms in mem)

    # ---- counts of the active layout
    lay = task.layout
    S, bq, bk = lay.seq_len, lay.bq, lay.bk
    pairs = counts.admitted_pairs(lay.block_idx, lay.buckets, bq, bk)
    m = dict(c["model"], lap_pe_dim=c["lap_pe_dim"])
    f_step = {"sparse": counts.step_flops(m, S, pairs),
              "dense": counts.step_flops(m, S, S * S)}
    n_var = {v: sum(1 for h in win if h["variant"] == v)
             for v in ("sparse", "dense")}
    pk = peak_table.peaks_for(devs[0].device_kind) if require_tpu else None
    H = m["n_heads"]
    kc = counts.attn_kernel_counts(
        seq=S, heads=H // chips,
        kv_heads=(m.get("n_kv_heads") or H) // chips, d_head=m["d_head"],
        pairs=pairs)
    notes.update(nodes=c["nodes"], seq=S, pairs=pairs,
                 live_share=counts.live_share(lay.block_idx),
                 mb_cap=int(task.mb_cap), beta_thre=float(task.beta_thre),
                 window_steps=len(win), window_s=window_s,
                 step_s=[h["seconds"] for h in win],
                 mark_gaps=[b - a for a, b in zip(marks[P - 1:end],
                                                  marks[P:end + 1])],
                 memory_stats=mem,
                 prep_s=float(task.prep_seconds))

    red = None
    if trace:
        xp = devtrace.find_xplane(tdir)
        plain = devtrace.load(xp)
        red = devtrace.reduce(plain)
    least = None
    if pk and red:
        # every traced call of a kernel does that kernel's work once,
        # the backward's recomputed forward included
        per_call = {k: counts.least_seconds(f, b, pk)
                    for k, (f, b) in kc.items()}
        notes["kernel_bound"] = {k: v[1] for k, v in per_call.items()}
        least = sum(red["kernel_calls"][k] * v[0]
                    for k, v in per_call.items())
    r = {"prep_s": float(task.prep_seconds),
         "grid_live_share": counts.live_share(lay.block_idx),
         "step_s": {v: [h["seconds"] for h in win if h["variant"] == v]
                    for v in ("sparse", "dense")},
         "window_s": window_s, "chips": chips, "peaks": pk,
         "model_flops": sum(n_var[v] * f_step[v] for v in n_var),
         "trace": red, "kernel_least_s": least,
         "traced_steps": (window.trace_end or end) - end}

    # ---- the comparison: program side, then free it, then the reference
    states = [trainer.ckpt.restore(s) for s in (1, 2, 3)]
    p0 = reference.to_host(trainer.model.init(jax.random.PRNGKey(seed32)))
    prog = correct.program_readings([h["loss"] for h in hist[:3]], p0,
                                    states)
    perm = task.prep.perm
    prog_layout = layout.program_dense(lay.block_idx, lay.buckets, S, bq,
                                       bk)
    del task, trainer, states, lay
    window.trainer = None
    gc.collect()
    own = own_layout(c, t, graph, perm)
    bad = layout_mismatches(own, prog_layout) if is_perm(
        perm, c["nodes"]) else own.size
    del prog_layout
    x = reference_inputs(c, graph, perm, own)
    x = {k: jax.device_put(v, devs[0]) for k, v in x.items()}
    t_ref = time.perf_counter()
    ref = reference.train(c, t["optimizer"], x, seed32, variants(3, P))
    notes["reference_s"] = time.perf_counter() - t_ref
    nums = correct.numbers(prog, ref)
    nums["layout"] = (bad, f"{bad} pairs")
    ok, checks = correct.judge(nums, spec["limits"])
    notes["numbers"] = {k: v[0] for k, v in nums.items()}
    notes["worst_leaf"] = {k: v[1] for k, v in nums.items()}
    notes["leaves"] = correct.leaf_table(prog, ref)
    ok = ok and failed == 0 and len(win) > 0

    # ---- the result line
    units = {e["name"]: e["unit"]
             for e in spec["manifest"]["end_to_end"]
             + spec["manifest"]["per_layer"]}
    metrics = {}
    if not trace:
        metrics = {
            "nodes_per_s": c["nodes"] * len(win) / window_s,
            "peak_hbm_gb": peak / 1e9,
            "setup_s": setup_s,
        }
    else:
        for pl in spec["manifest"]["per_layer"]:
            if workload not in pl.get("workloads", [workload]):
                continue
            v = read_metric(pl["name"], r)
            if v is not None:
                metrics[pl["name"]] = v
    dev = devs[0]
    result = {
        "correct": bool(ok), "attempted": len(win), "failed": failed,
        "metrics": {k: {"value": v, "unit": units[k]}
                    for k, v in metrics.items()},
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(devs), "memory_peak_bytes": peak},
    }
    if red:
        result["device"].update(busy_s=red["busy_s"],
                                window_s=red["window_s"])
        result["breakdown"] = {"device_ops": red["device_ops"],
                               "idle_gaps": red["idle_gaps"]}
        notes["trace"] = {k: red[k] for k in ("kernel_s", "kernel_calls",
                                              "a2a_exposed_s", "devices")}
    result["checks"] = checks
    for d in (ckpt, tdir):
        if d:
            shutil.rmtree(d, ignore_errors=True)
    return result, checks, notes


def read_metric(name: str, r: dict):
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"),
        BENCH / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(r)
