"""Host spans and counters of the program, on the profiler's clock.

``span(name, **attrs)`` marks a piece of host work. It always:

* enters ``jax.profiler.TraceAnnotation(name)``, so under a profiler
  trace (``jax.profiler.start_trace``) the span lands in the same
  ``.xplane.pb`` as the device ops, on the same clock;
* times itself: ``.seconds`` is set when the block exits.

While ``recording()`` is active it also keeps one record per span:
``{"id", "name", "start_ns", "end_ns", "parent", "thread", "attrs"}``
(``perf_counter_ns`` times; ``parent`` is the id of the innermost span
open on the same thread when it started, or None). Each thread has its
own stack of open spans, so a checkpoint writer thread's spans nest
under nothing of the thread that started it.

``count(name, n)`` adds ``n`` to a counter of the innermost open span of
the calling thread, while recording. JAX's compile path reports through
``jax.monitoring``; the listener registered below turns its events into
such counters (``COMPILE_COUNTERS``): tracing, lowering and XLA compile
seconds of every program built while a span is open, the seconds spent
reading the persistent compilation cache, and its hits and misses. So
the span that compiled a program is a lookup in the recording.

Every span the program opens is named ``repro.<layer>.<what>``; the
names and the numbers that read them are listed in docs/architecture.md.
"""

from __future__ import annotations

import contextlib
import itertools
import threading
import time

import jax
from jax import monitoring

# jax.monitoring event -> counter. In JAX 0.9.0 the XLA compile event
# wraps the cache read (``compile_or_get_cached``), so ``compile.xla_s``
# already holds ``compile.cache_read_s``: add trace, lower and xla only.
COMPILE_COUNTERS = {
    "/jax/core/compile/jaxpr_trace_duration": "compile.trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "compile.lower_s",
    "/jax/core/compile/backend_compile_duration": "compile.xla_s",
    "/jax/compilation_cache/cache_retrieval_time_sec":
        "compile.cache_read_s",
}
CACHE_COUNTERS = {
    "/jax/compilation_cache/cache_hits": "compile.cache_hits",
    "/jax/compilation_cache/cache_misses": "compile.cache_misses",
}
COMPILE_SECONDS = ("compile.trace_s", "compile.lower_s", "compile.xla_s")

_local = threading.local()
_ids = itertools.count()
_recording: Recording | None = None


class Recording:
    """What ``recording()`` keeps: ``spans`` (records in the order they
    closed) and ``counters`` (``{(span id or None, name): value}``)."""

    def __init__(self):
        self.spans: list[dict] = []
        self.counters: dict[tuple, float] = {}
        self._lock = threading.Lock()

    def add(self, span_id, name: str, n: float) -> None:
        key = (span_id, name)
        with self._lock:
            self.counters[key] = self.counters.get(key, 0) + n


def _stack() -> list:
    s = getattr(_local, "stack", None)
    if s is None:
        s = _local.stack = []
    return s


class span:
    """Context manager for one span; see the module docstring."""

    __slots__ = ("name", "attrs", "seconds", "id", "parent", "_ann",
                 "_t0")

    def __init__(self, name: str, **attrs):
        self.name = name
        self.attrs = attrs
        self.seconds = None

    def __enter__(self):
        stack = _stack()
        self.id = next(_ids)
        self.parent = stack[-1].id if stack else None
        stack.append(self)
        self._ann = jax.profiler.TraceAnnotation(self.name)
        self._ann.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter_ns()
        self._ann.__exit__(*exc)
        _stack().pop()
        self.seconds = (t1 - self._t0) * 1e-9
        rec = _recording
        if rec is not None:
            rec.spans.append({
                "id": self.id, "name": self.name, "start_ns": self._t0,
                "end_ns": t1, "parent": self.parent,
                "thread": threading.current_thread().name,
                "attrs": self.attrs})
        return False


def count(name: str, n: float = 1) -> None:
    """Add ``n`` to counter ``name`` of the innermost open span."""
    rec = _recording
    if rec is not None:
        stack = _stack()
        rec.add(stack[-1].id if stack else None, name, n)


@contextlib.contextmanager
def recording():
    """Keep every span and counter of the block; yields the
    ``Recording``, complete once the block exits."""
    global _recording
    prev, rec = _recording, Recording()
    _recording = rec
    try:
        yield rec
    finally:
        _recording = prev


# ---------------------------------------------------------- compile path
# JAX reports the start of a timed compile phase as a scalar event and
# its duration at the end. A jit traced inside another's trace reports a
# nested duration; only the outermost of each phase is counted, so the
# counters add up to wall time.

def _depth() -> dict:
    d = getattr(_local, "compile_depth", None)
    if d is None:
        d = _local.compile_depth = {}
    return d


def _on_start(event: str, value, **kw) -> None:
    if event in COMPILE_COUNTERS:
        d = _depth()
        d[event] = d.get(event, 0) + 1


def _on_duration(event: str, secs: float, **kw) -> None:
    name = COMPILE_COUNTERS.get(event)
    if name is None:
        return
    d = _depth()
    depth = max(d.get(event, 0) - 1, 0)
    d[event] = depth
    if depth == 0:
        count(name, secs)


def _on_event(event: str, **kw) -> None:
    name = CACHE_COUNTERS.get(event)
    if name is not None:
        count(name)


monitoring.register_scalar_listener(_on_start)
monitoring.register_event_duration_secs_listener(_on_duration)
monitoring.register_event_listener(_on_event)


# ---------------------------------------------------------- reading
# Plain functions over a Recording, for tests, tools and the benchmark.

def table(spans: list[dict]) -> dict:
    """``{name: {"count", "total_s", "self_s"}}``: self time is a span's
    time less that of the spans whose parent it is."""
    child: dict = {}
    for s in spans:
        if s["parent"] is not None:
            child[s["parent"]] = child.get(s["parent"], 0) + (
                s["end_ns"] - s["start_ns"])
    out: dict = {}
    for s in spans:
        d = s["end_ns"] - s["start_ns"]
        row = out.setdefault(s["name"], {"count": 0, "total_s": 0.0,
                                         "self_s": 0.0})
        row["count"] += 1
        row["total_s"] += d * 1e-9
        row["self_s"] += (d - child.get(s["id"], 0)) * 1e-9
    return out


def _holder(spans: list[dict], name: str) -> dict:
    """``{span id: id of the nearest span named name that holds it, itself
    included, or None}``."""
    by_id = {s["id"]: s for s in spans}
    out = {}
    for s in spans:
        cur = s
        while cur is not None and cur["name"] != name:
            cur = by_id.get(cur["parent"])
        out[s["id"]] = None if cur is None else cur["id"]
    return out


def compiles_by_step(rec: Recording) -> dict:
    """``{step: {counter: total}}`` of the compile counters, by the
    ``step`` attribute of the ``repro.trainer.step`` span they fell in;
    key None for those outside every step (set-up)."""
    hold = _holder(rec.spans, "repro.trainer.step")
    step_of = {s["id"]: s["attrs"].get("step") for s in rec.spans
               if s["name"] == "repro.trainer.step"}
    out: dict = {}
    compile_names = set(COMPILE_COUNTERS.values()) | set(
        CACHE_COUNTERS.values())
    for (sid, name), v in rec.counters.items():
        if name not in compile_names:
            continue
        h = hold.get(sid)
        row = out.setdefault(None if h is None else step_of[h], {})
        row[name] = row.get(name, 0) + v
    return out
