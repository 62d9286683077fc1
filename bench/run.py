#!/usr/bin/env python3
"""Graph-transformer training benchmark: one run of one cell.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Cells, configurations, traffic mixes and metrics are declared in
BENCHMARK.json at the repository root; each configuration, mix, limit set
and per-layer metric lives in a file of its own under bench/ that the
harness finds by name. The run needs the TPU chips its cell asks for and
exits non-zero, printing no result, without them.

The last line of standard output is one JSON object: ``correct``,
``attempted`` (steps in the measured window), ``failed`` (non-finite or
skipped steps), ``metrics`` (the end-to-end metrics with ``--trace 0``,
the per-layer ones with ``--trace 1``), ``device`` and, traced,
``breakdown``; last, ``checks``: each number compared with the reference
beside its limit. The same numbers are the last lines of standard error.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import cell

    try:
        result, checks, notes = cell.run(
            args.workload, args.seed, args.seconds, bool(args.trace),
            t_start=T_START)
    except cell.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    print("bench: " + json.dumps(notes, default=str), file=sys.stderr)
    for name, c in checks.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
