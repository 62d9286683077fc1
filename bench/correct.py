"""The comparison that decides ``correct``: what the timed path produced in
its first three steps against the plain reference (bench/reference.py).

Numbers computed; a cell compares those its limits name, each with a limit
of its own (bench/limits/<cell>.json):

* ``loss``: the worst relative gap of the three steps' losses (step 0 is
  the dense interleave step, steps 1 and 2 the sparse kernel path);
* ``grad.dense`` / ``grad.sparse``: the gradient of step 0 and of step 1
  as the optimizer received it, recovered from AdamW's first moment
  (m1 = (1 - b1) g0; m2 = b1 m1 + (1 - b1) g1). Per leaf, the gap between
  the program's norm and the reference's, over the larger of the
  reference's norm of that leaf and of the median leaf; the worst leaf;
* ``gdiff.dense`` / ``gdiff.sparse``: the same two gradients, by the norm
  of their difference from the reference's per leaf over the same
  denominator; the worst leaf. Where a fault moves a gradient's direction
  more than its length (half of the batch left out), this sees it;
* ``update``: the gap of norms of the parameters' change over the three
  steps (the state step 4 starts from), over the leaves whose reference
  gradient is at least a thousandth of the median leaf's (leaves moved by
  round-off alone under Adam are left out by that rule, not by name);
* ``layout``: pairs whose bucket in the program's layout differs from the
  benchmark's own derivation from its graph (bench/layout.py), an exact
  comparison; the reference attends over the benchmark's own pairs.
"""

from __future__ import annotations

import numpy as np

from reference import B1

GRAD_FLOOR = 1e-3     # share of the median leaf's gradient norm
NUMBERS = ("loss", "grad.dense", "grad.sparse", "gdiff.dense",
           "gdiff.sparse", "update", "layout")


def flat(tree, prefix=()) -> dict:
    if isinstance(tree, dict):
        out = {}
        for k in sorted(tree):
            out.update(flat(tree[k], prefix + (k,)))
        return out
    return {"/".join(prefix): np.asarray(tree, np.float64)}


def _norms(tree) -> dict:
    return {k: float(np.linalg.norm(v)) for k, v in flat(tree).items()}


def leaf_gaps(prog: dict, ref: dict, diff: bool = False) -> dict:
    """leaf -> | |prog| - |ref| | (with ``diff``, |prog - ref|) over
    max(|ref|, median |ref|); inf where the leaves do not match."""
    fp, fr = flat(prog), flat(ref)
    rn = {k: float(np.linalg.norm(v)) for k, v in fr.items()}
    med = float(np.median(list(rn.values())))
    out = {}
    for k in sorted(set(fp) | set(fr)):
        if k not in fp or k not in fr or fp[k].shape != fr[k].shape:
            out[k] = float("inf")
            continue
        num = (float(np.linalg.norm(fp[k] - fr[k])) if diff
               else abs(float(np.linalg.norm(fp[k])) - rn[k]))
        gap = num / max(rn[k], med, 1e-30)
        out[k] = gap if np.isfinite(gap) else float("inf")
    return out


def worst_gap(prog: dict, ref: dict, keep=None, diff: bool = False):
    """(gap, leaf): the largest of ``leaf_gaps`` over the leaves in
    ``keep`` (all by default)."""
    gaps = leaf_gaps(prog, ref, diff)
    worst, leaf = 0.0, ""
    for k, gap in gaps.items():
        if (keep is None or k in keep) and gap >= worst:
            worst, leaf = gap, k
    return worst, leaf


def moved_leaves(ref_grad) -> set:
    n = _norms(ref_grad)
    med = float(np.median(list(n.values())))
    return {k for k, v in n.items() if v >= GRAD_FLOOR * med}


def _sub(a, b):
    fa, fb = flat(a), flat(b)
    return {k: fa[k] - fb[k] for k in fa if k in fb}


def program_readings(losses, p0, states) -> dict:
    """The timed path's side: its first three losses, its initial
    parameters and its saved state after steps 1, 2 and 3."""
    m1 = flat(states[0]["opt"]["m"])
    m2 = flat(states[1]["opt"]["m"])
    g0 = {k: v / (1 - B1) for k, v in m1.items()}
    g1 = {k: (m2[k] - B1 * m1[k]) / (1 - B1) for k in m1}
    return {"losses": list(losses), "grads": [g0, g1], "p0": p0,
            "p_end": states[2]["params"]}


def numbers(prog: dict, ref: dict) -> dict:
    """name -> (value, detail) for every number compared."""
    lg = [abs(a - b) / max(abs(b), 1e-30) if np.isfinite(a) else
          float("inf") for a, b in zip(prog["losses"], ref["losses"])]
    if len(prog["losses"]) < len(ref["losses"]):
        lg.append(float("inf"))
    out = {"loss": (max(lg), f"steps {lg}")}
    for name, i in (("dense", 0), ("sparse", 1)):
        out["grad." + name] = worst_gap(prog["grads"][i], ref["grads"][i])
        out["gdiff." + name] = worst_gap(prog["grads"][i], ref["grads"][i],
                                         diff=True)
    keep = moved_leaves(ref["grads"][0])
    out["update"] = worst_gap(_sub(prog["p_end"], prog["p0"]),
                              _sub(ref["p_end"], ref["p0"]), keep)
    return out


def leaf_table(prog: dict, ref: dict) -> dict:
    """Every leaf's ``gdiff`` of both gradients, for the record."""
    return {f"gdiff.{name}": leaf_gaps(prog["grads"][i], ref["grads"][i],
                                       diff=True)
            for name, i in (("dense", 0), ("sparse", 1))}


def judge(nums: dict, limits: dict):
    """(ok, checks) with checks = {name: {"value", "limit"}} in a fixed
    order, for the numbers the cell's limits name; a named number that
    was not computed fails."""
    checks, ok = {}, True
    for name in NUMBERS:
        if name not in limits:
            continue
        v = float(nums[name][0]) if name in nums else float("inf")
        checks[name] = {"value": v, "limit": limits[name]}
        if not v <= limits[name]:
            ok = False
    return ok, checks
