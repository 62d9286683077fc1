"""From a profiler trace to the benchmark's device numbers.

``load`` turns the ``.xplane.pb`` that ``jax.profiler`` writes into a
small plain form: the op events of each device's ``XLA Ops`` line and the
harness's own host spans (``bench:*`` trace annotations), on one clock.
``reduce`` computes everything from that form, so it can be checked on a
recorded or hand-made trace without a chip.

What it matches, as seen on TPU v5 lite traces (PERF.md, section 5):

* device planes ``/device:TPU:<n>``, line ``XLA Ops``. Each event's name
  is the whole HLO instruction (``%name.N = <result type> op(...)``);
  ``load`` keeps the instruction's name and, for custom calls, its result
  type. Ops nest: a ``while`` (the scan over layers) holds the ops of its
  body, so busy time is a union of intervals and each op's time in the
  breakdown is its own time, less the ops nested inside it;
* the cluster-attention kernels by the name of the computation around
  each ``pallas_call``: ``cluster_attention`` (forward, also run again as
  the backward's recomputation) and ``_cluster_bwd``, which is the dK/dV
  kernel where its result holds two arrays of the shape of the first
  (dK, dV) and the dQ kernel otherwise (dQ and the bias-table partials);
* all-to-all ops by ``all-to-all`` in the name;
* the traced window by the host span ``bench:traced_window``; idle gaps
  are named by the innermost other ``bench:`` span that covers them.
"""

from __future__ import annotations

import glob
import re

KERNELS = ("fwd", "dq", "dkv")
A2A = "all-to-all"
WINDOW = "bench:traced_window"
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")
_SUFFIX = re.compile(r"(\.(\d+|clone))+$")
_ARRAY = re.compile(r"\b([a-z]+\d*\[[\d,]*\])")


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def split_hlo(text: str):
    """(instruction name, result type of a custom call or "") from an
    ``XLA Ops`` event name."""
    if " = " not in text:
        return text, ""
    name, rest = text.split(" = ", 1)
    sig = rest.split(" custom-call(", 1)[0] if " custom-call(" in rest \
        else ""
    return name, sig


def load(path: str) -> dict:
    """{"devices": {id: [[name, start_ns, dur_ns, sig], ...]},
    "spans": [[name, start_ns, dur_ns], ...]}."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, spans = {}, []
    for plane in pd.planes:
        m = _DEVICE.match(plane.name)
        for line in plane.lines:
            if m and line.name == "XLA Ops":
                evs = devices.setdefault(m.group(1), [])
                for e in line.events:
                    name, sig = split_hlo(e.name)
                    evs.append([name, e.start_ns, e.duration_ns, sig])
            elif not m:
                spans += [[e.name, e.start_ns, e.duration_ns]
                          for e in line.events
                          if e.name.startswith("bench:")]
    return {"devices": devices, "spans": spans}


def op_name(name: str) -> str:
    """``%_cluster_bwd.12`` -> ``_cluster_bwd``."""
    return _SUFFIX.sub("", name.lstrip("%"))


def kernel_of(name: str, sig: str = ""):
    base = op_name(name)
    if base == "cluster_attention":
        return "fwd"
    if base == "_cluster_bwd":
        arrays = _ARRAY.findall(sig)
        return "dkv" if arrays.count(arrays[0] if arrays else "") >= 2 \
            else "dq"
    return None


def _union(iv):
    out = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _length(iv) -> float:
    return sum(e - s for s, e in iv)


def _minus(a, b):
    """Measure of the union ``a`` less the union ``b`` (both sorted)."""
    total, j = 0.0, 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                total += b[k][0] - cur
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            total += e - cur
    return total


def _self_times(evs):
    """{op name: own time} of clipped events [(start, end, name)], less
    the time of the events nested inside each."""
    own: dict = {}
    stack: list = []                 # [end, name, own time]

    def close(top):
        own[top[1]] = own.get(top[1], 0.0) + max(top[2], 0.0)

    for s, e, name in sorted(evs, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            close(stack.pop())
        if stack:
            stack[-1][2] -= min(e, stack[-1][0]) - s
        stack.append([e, name, e - s])
    while stack:
        close(stack.pop())
    return own


def reduce(tr: dict) -> dict:
    """Seconds, averaged over the traced devices: the window, the busy
    time (union of op intervals), each kernel's time and calls, exposed
    all-to-all time (worst device), the ops with the most own time and
    the idle gaps by host span."""
    wins = [s for s in tr["spans"] if s[0] == WINDOW]
    if not wins or not tr["devices"]:
        return {}
    w0, w1 = wins[0][1], wins[0][1] + wins[0][2]
    spans = sorted((s for s in tr["spans"] if s[0] != WINDOW),
                   key=lambda s: s[1])
    n = len(tr["devices"])
    busy = 0.0
    kern = {k: 0.0 for k in KERNELS}
    kern_calls = {k: 0 for k in KERNELS}
    ops: dict = {}
    gaps: dict = {}
    a2a_exposed = []
    a2a_seen = False
    for evs in tr["devices"].values():
        clipped, a2a, comp = [], [], []
        for name, s, d, *rest in evs:
            s, e = max(s, w0), min(s + d, w1)
            if e <= s:
                continue
            base = op_name(name)
            k = kernel_of(name, rest[0] if rest else "")
            if k:
                kern[k] += e - s
                kern_calls[k] += 1
            clipped.append((s, e, base + (f".{k}" if k in ("dq", "dkv")
                                          else "")))
            if A2A in base:
                a2a_seen = True
                a2a.append((s, e))
            elif not base.startswith("while"):
                comp.append((s, e))
        for k, v in _self_times(clipped).items():
            ops[k] = ops.get(k, 0.0) + v
        u = _union([(s, e) for s, e, _ in clipped])
        busy += _length(u)
        a2a_exposed.append(_minus(_union(a2a), _union(comp)))
        edges = [w0] + [x for ab in u for x in ab] + [w1]
        for g0, g1 in zip(edges[::2], edges[1::2]):
            if g1 <= g0:
                continue
            mid = 0.5 * (g0 + g1)
            cover = [s[0] for s in spans if s[1] <= mid <= s[1] + s[2]]
            label = cover[-1] if cover else "bench:untracked"
            gaps[label] = gaps.get(label, 0.0) + (g1 - g0)
    ns = 1e-9
    top = sorted(ops.items(), key=lambda kv: -kv[1])[:10]
    idle = sorted(gaps.items(), key=lambda kv: -kv[1])[:10]
    return {
        "window_s": (w1 - w0) * ns,
        "busy_s": busy / n * ns,
        "kernel_s": {k: v / n * ns for k, v in kern.items()},
        "kernel_calls": {k: v // n for k, v in kern_calls.items()},
        "a2a_exposed_s": max(a2a_exposed) * ns,
        "a2a_seen": a2a_seen,
        "device_ops": [[k, v / n * ns] for k, v in top],
        "idle_gaps": [[k, v / n * ns] for k, v in idle],
        "devices": n,
    }
