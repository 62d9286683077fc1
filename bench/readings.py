#!/usr/bin/env python3
"""Readings that set a cell's limits, apart from the benchmark's own runs.

  python3 bench/readings.py --workload <cell> --seeds 1,2,3 [--out FILE]

For each seed, at the cell's own size: the graph, the program's node
order (``NodeTask`` host prep only, no training) and the benchmark's own
layout (bench/layout.py), then the plain reference
(float32, ``highest``) and, each compared with it by bench/correct.py:

* ``control``: the reference with every matmul operand rounded to float8
  e4m3, the precision below the configuration's bfloat16;
* ``half_batch``, ``token``, ``exchange``: the faults planted in the
  reference (see bench/reference.py);
* ``stale``: a step that returns its state unchanged (final parameters =
  initial ones).

The lower readings come from the program's runs (bench/run.py prints
its numbers); this prints one JSON line per seed and kind.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

KINDS = ("control", "half_batch", "token", "exchange", "stale")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--kinds", default=",".join(KINDS))
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    import cell
    import correct
    import graphs
    import reference

    spec = cell.load_cell(args.workload)
    c, t = spec["config"], spec["traffic"]
    sys.path.insert(0, str(cell.ROOT / "src"))
    from repro.configs import get_config
    from repro.core.graph import Graph
    from repro.tasks import NodeTask

    cfg = get_config(c["arch"]).replace(**c["model"])
    P = int(t["interleave_period"])
    out = open(args.out, "a") if args.out else None
    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.perf_counter()
        graph = graphs.make_graph(c["nodes"], t, seed)
        task = NodeTask(Graph(*graph), cfg, bq=c["block"]["bq"],
                        bk=c["block"]["bk"])
        perm = task.prep.perm
        del task
        x = cell.reference_inputs(c, graph, perm,
                                  cell.own_layout(c, t, graph, perm))
        x = {k: jnp.asarray(v) for k, v in x.items()}
        s32 = cell.weight_seed(seed)
        var = cell.variants(3, P)
        ref = reference.train(c, t["optimizer"], x, s32, var)
        for kind in args.kinds.split(","):
            if kind == "stale":
                got = dict(ref, p_end=ref["p0"])
            else:
                quant = jnp.float8_e4m3fn if kind == "control" else None
                fault = None if kind == "control" else kind
                got = reference.train(c, t["optimizer"], x, s32, var,
                                      quant=quant, fault=fault)
            nums = correct.numbers(got, ref)
            line = json.dumps({
                "workload": args.workload, "seed": seed, "kind": kind,
                "numbers": {k: v[0] for k, v in nums.items()},
                "leaf": {k: v[1] for k, v in nums.items()},
                "leaves": correct.leaf_table(got, ref),
                "device": jax.devices()[0].device_kind,
                "seconds": time.perf_counter() - t0})
            print(line, flush=True)
            if out:
                out.write(line + "\n")
                out.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
