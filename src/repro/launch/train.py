"""Training driver.

CPU-scale entry point with the same wiring as a cluster launch: config ->
model -> task -> recipe/mesh -> fault-tolerant Trainer (checkpoint/restart,
straggler policy). On a real multi-host TPU deployment the only changes
are jax.distributed.initialize() + per-host data slicing (data/lm_pipeline
is already host-aware).

  PYTHONPATH=src python -m repro.launch.train --arch qwen3_0_6b --smoke \
      --steps 50 --seq 128 --batch 8

Graph-family archs (graphormer_slim/large, gt) train through the Task
layer (repro/tasks) instead of an LM stream: ``--task node`` (default,
single synthetic SBM graph), ``--task graph`` (batched mini-graph
classification) or ``--task link`` (edge scoring with negative sampling).
Every task runs the full elastic loop — the AutoTuner re-reforms the
layout every --elastic-every steps and the dense interleave step fires
every --interleave-period steps — and ``--mesh-model P`` shards the
sequence over a P-way model axis (Ulysses a2a + cluster-sparse kernel),
for graph archs exactly as for LMs:

  PYTHONPATH=src python -m repro.launch.train --arch graphormer_slim \
      --smoke --steps 60 --graph-nodes 512 [--task node|graph|link] \
      [--mesh-model 2]
"""

from __future__ import annotations

import argparse

from repro.configs import get_config, get_smoke_config
from repro.data.lm_pipeline import LMDataConfig, lm_batch
from repro.launch.mesh import make_host_mesh
from repro.models import build
from repro.parallel.sharding import recipe_for
from repro.runtime.trainer import Trainer, TrainerConfig


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3_0_6b")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train")
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--mesh-model", type=int, default=1,
                    help="model-axis size of the host mesh (graph archs "
                         "shard the graph-token sequence over it)")
    ap.add_argument("--state-dtype", default="float32",
                    choices=["float32", "bfloat16", "int8"])
    ap.add_argument("--dtype", default=None,
                    choices=["float32", "bfloat16"],
                    help="override the config's activation dtype")
    ap.add_argument("--attn-impl", default="auto",
                    choices=["auto", "ref", "interpret", "compiled"],
                    help="kernel dispatch (repro.kernels.ops): auto = "
                         "Pallas on TPU / jnp oracle elsewhere")
    ap.add_argument("--task", default="node",
                    choices=["node", "graph", "link"],
                    help="[graph archs] workload: node classification, "
                         "graph-level classification, link prediction")
    ap.add_argument("--graph-nodes", type=int, default=512,
                    help="[graph archs] synthetic SBM graph size")
    ap.add_argument("--graph-clusters", type=int, default=4)
    ap.add_argument("--graphs", type=int, default=16,
                    help="[--task graph] number of mini-graphs")
    ap.add_argument("--batch-graphs", type=int, default=0,
                    help="[--task graph] graphs per mini-batch (must "
                         "divide --graphs; 0 = one full batch, no "
                         "cycling)")
    ap.add_argument("--interleave-period", type=int, default=-1,
                    help="[graph archs] dense step every k steps "
                         "(-1 = config default, 0 = never)")
    ap.add_argument("--elastic-every", type=int, default=-1,
                    help="[graph archs] steps per AutoTuner epoch / "
                         "re-layout boundary (-1 = config default, "
                         "0 = frozen layout)")
    ap.add_argument("--retune-every", type=int, default=0,
                    help="reload the kernel-autotune winner table every "
                         "k steps (0 = never; repro.tune)")
    ap.add_argument("--tune-table", default="",
                    help="winner-table path for --retune-every "
                         "('' = REPRO_TUNE_TABLE / TUNE_winners.json)")
    ap.add_argument("--fault-plan", default="",
                    help="deterministic fault injection spec "
                         "(repro.resilience), e.g. "
                         "'nonfinite@5,preempt@7,ckpt_corrupt@10'; "
                         "REPRO_FAULTS wins when set")
    ap.add_argument("--max-bad-steps", type=int, default=3,
                    help="consecutive non-finite steps before rollback "
                         "to the last verified checkpoint (0 = "
                         "skip-only, never roll back)")
    args = ap.parse_args(argv)

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if args.dtype:
        cfg = cfg.replace(dtype=args.dtype)
    model = build(cfg)
    print(f"arch={cfg.name} params={model.n_params():,}")

    if cfg.family == "graph":
        return _graph_main(args, cfg, model)

    mesh = recipe = None
    if args.mesh_model > 1:
        from repro.configs.base import ShapeConfig
        mesh = make_host_mesh(model=args.mesh_model)
        recipe = recipe_for(
            ShapeConfig("train", "train", args.seq, args.batch), mesh)
        print(f"mesh={dict(mesh.shape)} recipe={recipe.name}")

    dc = LMDataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq,
                      global_batch=args.batch)
    tc = TrainerConfig(steps=args.steps, ckpt_every=args.ckpt_every,
                       ckpt_dir=args.ckpt_dir, lr=args.lr,
                       warmup=max(2, args.steps // 10),
                       state_dtype=args.state_dtype,
                       attn_impl=args.attn_impl,
                       retune_every=args.retune_every,
                       tune_table=args.tune_table,
                       fault_plan=args.fault_plan,
                       max_bad_steps=args.max_bad_steps)
    trainer = Trainer(model, tc, lambda s: lm_batch(dc, s),
                      mesh=mesh, recipe=recipe)
    from repro.kernels.ops import dispatch_table
    print(f"kernel dispatch: {dispatch_table()}")
    state, status = trainer.run()
    if not trainer.history:  # restored a finished run: nothing to do
        print(f"status={status} (already at step {int(state['step'])})")
        return trainer
    for h in trainer.history[:: max(1, len(trainer.history) // 10)]:
        print(f"step {h['step']:4d} loss {h['loss']:.4f} "
              f"{h['seconds']*1e3:.0f}ms")
    print(f"status={status} final_loss={trainer.history[-1]['loss']:.4f} "
          f"stragglers={len(trainer.stragglers)}")
    return trainer


def _make_graph_task(args, cfg):
    """Build the requested Task (node / graph-level / link) on synthetic
    data — the CLI spelling of the repro.tasks constructors."""
    from repro.core.graph import sbm_graph
    from repro.tasks import (GraphLevelTask, LinkTask, NodeTask,
                             synthetic_graph_level_dataset)

    if args.task == "graph":
        graphs = synthetic_graph_level_dataset(args.graphs, cfg, seed=1)
        eval_graphs = synthetic_graph_level_dataset(
            max(2, args.graphs // 2), cfg, seed=2)
        return GraphLevelTask(graphs, cfg, eval_graphs=eval_graphs,
                              batch_graphs=args.batch_graphs or None)
    g = sbm_graph(args.graph_nodes, args.graph_clusters, p_in=0.04,
                  p_out=0.002, feat_dim=cfg.feat_dim,
                  n_classes=cfg.n_classes, seed=0)
    if args.task == "link":
        return LinkTask(g, cfg)
    return NodeTask(g, cfg)


def _graph_main(args, cfg, model):
    """Graph-family training: any Task, the full elastic loop (tuner ->
    re-layout -> interleave), and — with --mesh-model > 1 — the
    sequence-sharded cluster-sparse attention path, end to end in the
    fault-tolerant Trainer."""
    interleave = cfg.interleave_period if args.interleave_period < 0 \
        else args.interleave_period
    elastic_every = cfg.elastic_every if args.elastic_every < 0 \
        else args.elastic_every
    task = _make_graph_task(args, cfg)
    lay = task.layout
    print(f"task={task.name} seq={lay.seq_len} "
          f"mini_batches={task.n_batches} "
          f"ladder={[round(b, 4) for b in task.tuner.ladder]} "
          f"mb_cap={task.mb_cap} prep={task.prep_seconds:.2f}s")

    mesh = recipe = None
    if args.mesh_model > 1:
        from repro.configs.base import ShapeConfig
        from repro.parallel.cluster_parallel import can_shard_cluster
        mesh = make_host_mesh(model=args.mesh_model)
        recipe = recipe_for(ShapeConfig(
            "graph", "train", lay.seq_len,
            task.prep.batch["feat"].shape[0]), mesh)
        ok = can_shard_cluster(cfg.n_heads, cfg.kv_heads, lay.seq_len,
                               args.mesh_model, lay.bq, lay.bk)
        sca = "on" if ok else "OFF (shape cannot shard; GSPMD fallback)"
        print(f"mesh={dict(mesh.shape)} recipe={recipe.name} "
              f"sharded_cluster_attention={sca}")

    tc = TrainerConfig(steps=args.steps, ckpt_every=args.ckpt_every,
                       ckpt_dir=args.ckpt_dir, lr=args.lr,
                       warmup=max(2, args.steps // 10),
                       state_dtype=args.state_dtype,
                       attn_impl=args.attn_impl,
                       interleave_period=interleave,
                       elastic_every=elastic_every,
                       retune_every=args.retune_every,
                       tune_table=args.tune_table,
                       fault_plan=args.fault_plan,
                       max_bad_steps=args.max_bad_steps)
    trainer = Trainer(model, tc, task=task, mesh=mesh, recipe=recipe)
    state, status = trainer.run()
    if not trainer.history:  # restored a finished run: nothing to do
        print(f"status={status} (already at step {int(state['step'])})")
        return trainer
    for h in trainer.history[:: max(1, len(trainer.history) // 10)]:
        print(f"step {h['step']:4d} [{h['variant']:6s}] "
              f"loss {h['loss']:.4f} acc {h['acc']:.3f} "
              f"beta_thre {h['beta_thre']:.4f}")
    for m in task.moves:
        print(f"ladder move @ step {m.step}: pos={m.pos} "
              f"beta_thre={m.beta_thre:.4f} (LDR {m.ldr:+.2e})")
    ev = trainer.evaluate(state["params"])
    if ev:
        print("eval: " + " ".join(f"{k}={v:.4f}" for k, v in ev.items()))
    print(f"status={status} final_loss={trainer.history[-1]['loss']:.4f} "
          f"moves={len(task.moves)} "
          f"dense_steps={sum(1 for h in trainer.history if h['dense'])}")
    return trainer


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
