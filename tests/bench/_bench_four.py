"""Host runs of a four-chip cell on four virtual CPU devices, one for each
fault named on the command line (``none`` for a sound run), in one
process; prints one JSON line per run. Run by test_bench_faults4.py in a
fresh process (the device count is fixed when JAX starts).

No cell of BENCHMARK.json asks for four chips yet, so the cell is the
first cell with ``chips`` 4: the harness's sharded path (mesh, recipe,
Ulysses all-to-alls around the sharded kernel) under a whole run."""

import json
import sys
import time

import pytest

from _bench_path import BENCH  # noqa: F401
from _bench_faults import plant

import cell



def four_chip_cell(name):
    spec = LOAD(name)
    spec["workload"] = dict(spec["workload"], chips=4)
    return spec


if __name__ == "__main__":
    LOAD = cell.load_cell
    cell.load_cell = four_chip_cell
    for fault in sys.argv[1:]:
        with pytest.MonkeyPatch.context() as mp:
            plant(mp, None if fault == "none" else fault)
            res, _, notes = cell.run("slim-arxivstat-il8", 2**31 + 23, 0.0,
                                     False, t_start=time.perf_counter(),
                                     require_tpu=False, nodes=200)
        print(json.dumps({"fault": fault, "result": res, "notes": notes},
                         default=str), flush=True)
