"""The span reader, on the CPU: device-idle time inside the program's
``tc.count`` spans, split by the innermost ``tc.*`` span.

``cpu_oneshot_spans.xplane.pb`` was recorded on the CPU backend with the
harness's profiler options: after a warm-up, two counts of ``rmat(400,
2500, seed=1)`` through ``tcim_count(edges, n=400, build="device")``, the
second with ``async_=True`` and its ``result()``, each inside a
``bench.tcim_count`` annotation.
"""
from __future__ import annotations

import json

import pytest

from bench import harness, trace
from bench import spans as spans_mod
from bench.harness import BENCH, ROOT
from bench.readers import ReadContext, device_idle, span_idle

RECORDED = BENCH / "tests" / "data" / "cpu_oneshot_spans.xplane.pb"
RECORDED_NO_SPANS = BENCH / "tests" / "data" / "cpu_oneshot.xplane.pb"
STAGES = {"tc.orient", "tc.orient.digest", "tc.orient.upload", "tc.compress",
          "tc.schedule", "tc.schedule.size_wait", "tc.schedule.pair_wait",
          "tc.execute", "tc.execute.pool", "tc.close"}


def _ev(name, s, t, dev=0):
    return trace.Event(name, float(s), float(t), dev)


def _ctx(tr, graphs, window_ns):
    out = harness.Outcome(attempted=graphs, failed=0, checks=[], metrics={},
                          graphs=graphs, pairs=[1] * graphs)
    return ReadContext(trace=tr, window_ns=float(window_ns), outcome=out,
                       peaks={}, slice_bits=64)


def test_idle_inside_counts_goes_to_the_innermost_span():
    # Device busy 0-10, 30-40, 90-100; two counts, 5-60 and 65-100.
    tr = trace.Trace(
        ops=[_ev("a", 0, 10), _ev("b", 30, 40), _ev("c", 90, 100),
             _ev("other device", 10, 90, dev=1)],
        modules=[],
        host=[_ev("bench.tcim_count", 0, 100), _ev("tc.count", 5, 60),
              _ev("tc.orient", 8, 25), _ev("tc.orient.digest", 12, 20),
              _ev("tc.schedule", 25, 60), _ev("tc.count", 65, 100),
              _ev("tc.close", 70, 95), _ev("np.asarray", 50, 52)])
    ctx = _ctx(tr, 2, 100)
    # Idle inside counts: 10-30 and 40-60 (first), 65-90 (second).
    assert span_idle.read(ctx) == pytest.approx((20 + 20 + 25) / 2 / 1e6)
    assert ctx.notes["by_span_ms"] == pytest.approx({
        "tc.orient": 7 / 2e6, "tc.orient.digest": 8 / 2e6,
        "tc.schedule": 25 / 2e6, "tc.close": 20 / 2e6, "tc.count": 5 / 2e6})
    assert ctx.notes["stage_share"] == pytest.approx(60 / 65)
    assert ctx.notes["count_spans"] == 2


def test_recorded_cpu_trace_with_spans_reads():
    tr = trace.load(str(RECORDED))
    marked = spans_mod.spans(tr.host)
    names = {e.name for e in marked}
    assert names == STAGES | {spans_mod.COUNT}
    window = max(e.end for e in tr.host)
    ctx = _ctx(tr, 2, window)
    value = span_idle.read(ctx)
    assert value > 0
    # A share of the window's idle time, inside the counts only.
    idle_ms = device_idle.read(ctx) / 100 * window / 1e6
    assert value * 2 <= idle_ms + 1e-9
    assert set(ctx.notes["by_span_ms"]) <= names
    assert sum(ctx.notes["by_span_ms"].values()) == pytest.approx(value)
    assert ctx.notes["stage_share"] > 0.9
    assert ctx.notes["count_spans"] == 3  # the async count closes in its own


def test_missing_spans_raise_only_where_the_program_emits_them(monkeypatch):
    tr = trace.load(str(RECORDED_NO_SPANS))
    window = max(e.end for e in tr.ops)
    assert spans_mod.instrumented()
    with pytest.raises(LookupError):
        span_idle.read(_ctx(tr, 1, window))
    assert span_idle.read(_ctx(tr, 0, window)) is None
    monkeypatch.setattr(spans_mod, "instrumented", lambda: False)
    assert span_idle.read(_ctx(tr, 1, window)) is None


def test_spans_are_the_tc_host_events():
    host = [_ev("tc.count", 0, 1), _ev("tc.orient", 0, 1),
            _ev("bench.tcim_count", 0, 1), _ev("tcx", 0, 1)]
    assert [e.name for e in spans_mod.spans(host)] == ["tc.count", "tc.orient"]


def test_host_idle_metric_reads_the_spans_in_the_oneshot_cell():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    (m,) = [m for m in spec["per_layer"] if m["name"] == "host_idle_ms.oneshot"]
    assert m["workloads"] == ["youtube.oneshot"] and m["moves"] == "count_s"
    metric = json.loads((BENCH / "metrics" / "host_idle_ms.oneshot.json").read_text())
    assert metric == {"reader": "span_idle"}
