"""Program spans and counters (repro.runtime.spans): nesting, self time,
per-thread stacks, the recorder switch and the compile counters."""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.runtime import spans
from repro.runtime.spans import count, recording, span


def _counters(rec, span_id):
    return {n: v for (sid, n), v in rec.counters.items() if sid == span_id}


def _by_name(rec):
    out = {}
    for s in rec.spans:
        out.setdefault(s["name"], []).append(s)
    return out


def test_nesting_parents_and_self_time():
    with recording() as rec:
        with span("repro.a", k=1) as a:
            time.sleep(0.01)
            with span("repro.b"):
                time.sleep(0.02)
            with span("repro.c"):
                with span("repro.d"):
                    time.sleep(0.01)
    by = _by_name(rec)
    assert [s["name"] for s in rec.spans] == [
        "repro.b", "repro.d", "repro.c", "repro.a"]    # in closing order
    ra, rb, rc, rd = (by[n][0] for n in ("repro.a", "repro.b", "repro.c",
                                         "repro.d"))
    assert ra["parent"] is None and ra["attrs"] == {"k": 1}
    assert rb["parent"] == rc["parent"] == ra["id"]
    assert rd["parent"] == rc["id"]
    tab = spans.table(rec.spans)
    dur = {n: (s["end_ns"] - s["start_ns"]) * 1e-9
           for n, s in (("a", ra), ("b", rb), ("c", rc), ("d", rd))}
    assert tab["repro.a"]["self_s"] == pytest.approx(
        dur["a"] - dur["b"] - dur["c"])
    assert tab["repro.c"]["self_s"] == pytest.approx(dur["c"] - dur["d"])
    assert tab["repro.d"]["self_s"] == pytest.approx(dur["d"])
    assert tab["repro.a"]["count"] == 1
    assert a.seconds == pytest.approx(dur["a"])
    for s in rec.spans:
        assert s["start_ns"] <= s["end_ns"]


def test_thread_keeps_its_own_stack():
    seen = {}

    def work():
        with span("repro.thread") as t:
            seen["stack"] = [s.name for s in spans._stack()]
        seen["s"] = t

    with recording() as rec:
        with span("repro.main") as m:
            th = threading.Thread(target=work, name="writer")
            th.start()
            th.join(timeout=30)
            assert [s.name for s in spans._stack()] == ["repro.main"]
    assert not th.is_alive()
    assert seen["stack"] == ["repro.thread"]
    by = _by_name(rec)
    assert by["repro.thread"][0]["parent"] is None
    assert by["repro.thread"][0]["thread"] == "writer"
    assert by["repro.main"][0]["id"] == m.id
    assert spans._stack() == []


def test_recorder_off_keeps_nothing_but_times():
    with recording() as rec:
        pass
    with span("repro.off") as s:
        count("things", 3)
        time.sleep(0.005)
    assert s.seconds >= 0.005
    assert rec.spans == [] and rec.counters == {}


def test_counters_attributed_to_innermost_span():
    with recording() as rec:
        count("outside")
        with span("repro.outer") as o:
            count("n", 2)
            with span("repro.inner") as i:
                count("n", 5)
                count("n", 1)
    assert rec.counters == {(None, "outside"): 1, (o.id, "n"): 2,
                            (i.id, "n"): 6}


def test_span_closes_on_exception():
    with recording() as rec:
        with pytest.raises(ValueError):
            with span("repro.boom"):
                raise ValueError("x")
    assert [s["name"] for s in rec.spans] == ["repro.boom"]
    assert spans._stack() == []


def test_fresh_jit_compile_counted_in_its_span():
    def f(x):
        return jnp.sin(x) * 3.0 + x.sum()

    g = jax.jit(f)
    x = jnp.asarray(np.arange(7, dtype=np.float32))
    with recording() as rec:
        with span("repro.first") as first:
            g(x).block_until_ready()
        with span("repro.again") as again:
            g(x).block_until_ready()
    got = _counters(rec, first.id)
    for name in spans.COMPILE_SECONDS:
        assert got.get(name, 0) > 0, (name, got)
    # the compiled program is reused: nothing compiles the second time
    assert not set(_counters(rec, again.id)) & set(spans.COMPILE_SECONDS)


def test_nested_jit_trace_counted_once():
    """A jit traced inside another's trace reports a nested duration;
    only the outermost counts, so the counter is the outer trace's time."""
    from jax import monitoring

    inner = jax.jit(lambda x: jnp.cos(x) + 1.0)

    def outer(x):
        return inner(x) * inner(x + 1.0)

    seen = []

    def listen(event, secs, **kw):
        if event == "/jax/core/compile/jaxpr_trace_duration":
            seen.append((kw.get("fun_name"), secs))

    x = jnp.ones((5,), jnp.float32)
    monitoring.register_event_duration_secs_listener(listen)
    try:
        with recording() as rec:
            with span("repro.nested") as s:
                jax.jit(outer)(x).block_until_ready()
    finally:
        monitoring.unregister_event_duration_listener(listen)
    outer_s = [d for name, d in seen if name == "outer"]
    assert len(outer_s) == 1 and len(seen) > 1     # inner traces nested
    got = _counters(rec, s.id)
    assert got["compile.trace_s"] == pytest.approx(outer_s[0])
