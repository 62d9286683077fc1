"""The program's own spans in a benchmark run: what the host was doing
while the device sat idle, how long the host held each step, and which
step compiled.

Two inputs, both from ``repro.runtime.spans``:

* the ``repro.*`` trace annotations in the profiler's ``.xplane.pb``, on
  the device ops' clock (``load``). With ``devtrace.load``'s plain form of
  the same file they name the idle time of the traced window;
* a ``Recording`` of the run (``repro.runtime.spans.recording``): the
  span table, the set-up spans, the compile counters by step, and each
  step's host time, by step number.

Every function returns None when its input is missing, so it can be run
on a program that opens no such spans.
"""

from __future__ import annotations

import devtrace

PREFIX = "repro."
STEP = "repro.trainer.step"
WAIT = "repro.trainer.wait"
COMPILE_SECONDS = ("compile.trace_s", "compile.lower_s", "compile.xla_s")


def load(path: str) -> list:
    """``[[name, start_ns, dur_ns], ...]``: the ``repro.*`` host spans of
    the trace, on the clock of ``devtrace.load``'s device ops."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if devtrace._DEVICE.match(plane.name):
            continue
        for line in plane.lines:
            out += [[e.name, e.start_ns, e.duration_ns] for e in line.events
                    if e.name.startswith(PREFIX)]
    return out


def _gaps(evs, w0, w1):
    """Idle intervals of one device's ops inside the window."""
    iv = [(max(s, w0), min(s + d, w1)) for _, s, d, *_ in evs]
    u = devtrace._union([(s, e) for s, e in iv if e > s])
    edges = [w0] + [x for ab in u for x in ab] + [w1]
    return [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]


def _cover(spans, t):
    """Names of the spans that hold time ``t``, outermost first: by start
    time, so where spans of two threads overlap the later one is taken
    as the innermost."""
    held = [s for s in spans if s[1] <= t <= s[1] + s[2]]
    held.sort(key=lambda s: (s[1], -s[2]))
    return [s[0] for s in held]


def _pieces(spans, a, b):
    """``[a, b]`` cut at the edges of the spans inside it: (innermost
    span's name or ``untracked``, length) of each piece."""
    cuts = sorted({a, b} | {x for s in spans for x in (s[1], s[1] + s[2])
                            if a < x < b})
    for p, q in zip(cuts, cuts[1:]):
        names = _cover(spans, 0.5 * (p + q))
        yield (names[-1] if names else "untracked"), q - p


def idle_by_program_span(plain: dict, spans: list):
    """``{"idle_s": {span name: idle seconds}, "named_share": %}`` of the
    traced window, averaged over the devices. Each idle interval between
    the device's ops is cut at the edges of the ``repro.*`` spans in it
    and each piece goes to the innermost span over it (``untracked`` if
    none). ``named_share``: the share of the idle time under a span other
    than ``repro.trainer.step`` itself, that is under one of the step's
    parts (the window opens and closes inside a step, whose own span the
    profiler then does not keep)."""
    wins = [s for s in plain.get("spans", []) if s[0] == devtrace.WINDOW]
    if not wins or not plain.get("devices") or not spans:
        return None
    w0, w1 = wins[0][1], wins[0][1] + wins[0][2]
    inside = [s for s in spans if s[1] < w1 and s[1] + s[2] > w0]
    by: dict = {}
    for evs in plain["devices"].values():
        for a, b in _gaps(evs, w0, w1):
            local = [s for s in inside if s[1] < b and s[1] + s[2] > a]
            for label, d in _pieces(local, a, b):
                by[label] = by.get(label, 0.0) + d
    total = sum(by.values())
    named = total - by.get(STEP, 0.0) - by.get("untracked", 0.0)
    n = len(plain["devices"])
    return {"idle_s": {k: v / n * 1e-9 for k, v in
                       sorted(by.items(), key=lambda kv: -kv[1])},
            "named_share": 100.0 * named / total if total else None}


def _secs(s) -> float:
    return (s["end_ns"] - s["start_ns"]) * 1e-9


def step_host_ms(rec, steps):
    """Mean over ``steps`` of each ``repro.trainer.step`` span less its
    ``repro.trainer.wait`` child: host time per step with nothing queued
    on the device, in ms."""
    if rec is None:
        return None
    want = set(steps)
    host = {}
    for s in rec.spans:
        if s["name"] == STEP and s["attrs"].get("step") in want:
            host[s["id"]] = _secs(s)
    for s in rec.spans:
        if s["name"] == WAIT and s["parent"] in host:
            host[s["parent"]] -= _secs(s)
    if not host:
        return None
    return 1e3 * sum(host.values()) / len(host)


def compile_s(by_step: dict | None, first_window_step: int):
    """Seconds of tracing, lowering and XLA compile (the cache read
    included) in set-up: outside every step, and in steps before the
    window's first."""
    if not by_step:
        return None
    return sum(v.get(k, 0.0) for step, v in by_step.items()
               if step is None or step < first_window_step
               for k in COMPILE_SECONDS)


def setup_spans(rec, first_window_step: int):
    """Span table of set-up: every span that closed before the window's
    first step began."""
    if rec is None:
        return None
    from repro.runtime.spans import table

    start = [s["start_ns"] for s in rec.spans
             if s["name"] == STEP and s["attrs"].get("step") ==
             first_window_step]
    if not start:
        return None
    return table([s for s in rec.spans if s["end_ns"] <= start[0]])


def setup_named_s(rec, first_window_step: int):
    """Seconds of set-up under a span: the outermost spans of the
    trainer's thread that closed before the window's first step began."""
    if rec is None:
        return None
    first = [s for s in rec.spans if s["name"] == STEP and
             s["attrs"].get("step") == first_window_step]
    if not first:
        return None
    t, thread = first[0]["start_ns"], first[0]["thread"]
    return sum(_secs(s) for s in rec.spans if s["parent"] is None and
               s["thread"] == thread and s["end_ns"] <= t)
