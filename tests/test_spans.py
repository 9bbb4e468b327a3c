"""Spans and device scopes on the profiler's clock.

The count path names its host stages with ``tc.*`` spans
(``repro.runtime.spans``) and the device build's worklist step with the
``tc_expand``/``tc_search``/``tc_compact`` scopes; the benchmark's readers
match those names, so these tests pin them.
"""
import glob
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core import executor as ex_mod
from repro.core import tcim_count
from repro.core.build import _get_jits
from repro.graphs import rmat
from repro.runtime.spans import next_count_id, span

SCOPES = ("tc_expand", "tc_search", "tc_compact")


def _record(tmp_path, fn):
    """Run ``fn`` under the profiler; the host events named ``tc.*`` of the
    recording, in order of start, as (name, start, end, stats)."""
    fn()  # compile outside the recording
    with jax.profiler.trace(str(tmp_path)):
        out = fn()
    data = ProfileData.from_file(
        glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[0])
    events = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns, dict(ev.stats))
              for plane in data.planes if plane.name.startswith("/host:")
              for line in plane.lines for ev in line.events
              if ev.name.startswith("tc.")]
    return out, sorted(events, key=lambda e: e[1])


def _inside(child, parent) -> bool:
    return parent[1] <= child[1] and child[2] <= parent[2]


def test_span_writes_host_time_under_its_key():
    timings = {}
    with span("tc.unit", timings, "unit"):
        pass
    with span("tc.unit.nokey", timings):
        pass
    with span("tc.unit.untimed"):
        pass
    assert set(timings) == {"unit", "tc.unit.nokey"}
    assert all(v >= 0.0 for v in timings.values())
    with pytest.raises(RuntimeError), span("tc.unit.raises", timings, "raised"):
        raise RuntimeError("stage failed")
    assert "raised" in timings
    assert next_count_id() < next_count_id()


def test_span_emits_a_named_annotation_with_its_arguments(tmp_path):
    def run():
        timings = {}
        with span("tc.unit", timings, "unit", count_id=7):
            jnp.ones(4).block_until_ready()
        return timings

    timings, events = _record(tmp_path, run)
    assert [e[0] for e in events] == ["tc.unit"]
    assert events[0][3].get("count_id") == 7
    assert timings["unit"] * 1e9 >= 0.5 * (events[0][2] - events[0][1])


def test_traced_device_count_nests_its_stage_spans(tmp_path):
    edges = rmat(300, 1800, seed=5)
    res, events = _record(tmp_path, lambda: tcim_count(edges, n=300, build="device"))
    names = [e[0] for e in events]
    assert names.count("tc.count") == 1
    count = events[names.index("tc.count")]
    stages = ["tc.orient", "tc.compress", "tc.schedule", "tc.execute"]
    got = [e for e in events if e[0] in stages]
    assert [e[0] for e in got] == stages  # once each, in order of start
    assert all(_inside(e, count) for e in events)
    for a, b in zip(got, got[1:]):
        assert a[2] <= b[1]
    parent = {"tc.orient.digest": "tc.orient", "tc.orient.upload": "tc.orient",
              "tc.schedule.size_wait": "tc.schedule",
              "tc.schedule.pair_wait": "tc.schedule",
              "tc.execute.pool": "tc.execute"}
    for child, outer in parent.items():
        assert names.count(child) == 1, child
        assert _inside(events[names.index(child)], events[names.index(outer)])
    assert set(names) == {"tc.count", *stages, *parent}
    # timings_s keeps its keys: the stage names without "tc.", plus plan.
    assert list(res.timings_s) == ["orient", "compress", "schedule", "plan",
                                   "execute"]


def test_async_close_reopens_the_count_with_its_id(tmp_path):
    edges = rmat(300, 1800, seed=6)

    def run():
        fut = tcim_count(edges, n=300, build="device", async_=True)
        return fut, fut.result()

    (fut, res), events = _record(tmp_path, run)
    counts = [e for e in events if e[0] == "tc.count"]
    assert len(counts) == 2
    assert counts[0][3]["count_id"] == counts[1][3]["count_id"] == fut.count_id
    close = next(e for e in events if e[0] == "tc.close")
    assert _inside(close, counts[1]) and not _inside(close, counts[0])
    assert "close" in res.timings_s


def test_host_build_count_opens_one_count_span(tmp_path):
    edges = rmat(200, 900, seed=7)
    res, events = _record(tmp_path, lambda: tcim_count(edges, build="host"))
    names = [e[0] for e in events]
    assert names.count("tc.count") == 1
    for stage in ("tc.orient", "tc.compress", "tc.schedule", "tc.plan",
                  "tc.execute"):
        assert names.count(stage) == 1, stage
    assert list(res.timings_s)[:3] == ["orient", "compress", "schedule"]


def _worklist_args():
    i32 = jnp.int32
    return (jnp.zeros(16, i32), jnp.zeros(16, i32), 3, jnp.zeros(9, i32),
            jnp.zeros(8, i32), jnp.zeros(9, i32), jnp.zeros(8, i32), 32)


def test_worklist_step_ops_fall_under_exactly_one_scope():
    step = _get_jits()["worklist"]
    closed = jax.make_jaxpr(step, static_argnums=(7,))(*_worklist_args())
    (call,) = closed.jaxpr.eqns
    eqns = call.params["jaxpr"].jaxpr.eqns
    tags = [[s for s in SCOPES if s in str(e.source_info.name_stack)] for e in eqns]
    assert all(len(t) == 1 for t in tags), [
        (e.primitive.name, str(e.source_info.name_stack))
        for e, t in zip(eqns, tags) if len(t) != 1]
    assert {t[0] for t in tags} == set(SCOPES)
    # The scopes reach the compiled module's op metadata, which the
    # profiler's trace carries.
    hlo = step.lower(*_worklist_args()).compile().as_text()
    paths = [p for p in re.findall(r'op_name="([^"]*)"', hlo)
             if p.startswith("jit(worklist_step)/")]
    assert paths and all(sum(s in p for s in SCOPES) == 1 for p in paths)
    assert {s for p in paths for s in SCOPES if s in p} == set(SCOPES)


def test_worklist_expansion_compiles_to_no_loop():
    """The lane->edge map is one scatter and a running max: the compiled
    worklist step holds a single loop, the search's, and no op of
    ``tc_expand`` is a loop (a log-depth gather loop cost ~13 s per
    com-Youtube count on a TPU v5e)."""
    step = _get_jits()["worklist"]
    hlo = step.lower(*_worklist_args()).compile().as_text()
    loops = [re.search(r'op_name="([^"]*)"', line).group(1)
             for line in hlo.splitlines() if re.search(r"\swhile\(", line)]
    assert len(loops) == 1 and "tc_search" in loops[0], loops


def _module_name(lowered) -> str:
    return re.search(r"HloModule (\S+?),", lowered.as_text(dialect="hlo")).group(1)


def test_chunk_and_fused_steps_lower_to_distinct_modules():
    store = jnp.zeros((8, 2), jnp.uint32)
    idx = jnp.zeros(4, jnp.int32)
    chunk = ex_mod._chunk_step_fn("jnp", None, None, "none")
    fused = ex_mod._fused_step_fn(4, None, None)
    names = {
        _module_name(chunk.lower(store, store, idx, idx, jnp.int32(0))),
        _module_name(fused.lower(store, store, idx, idx)),
    }
    assert names == {"jit_tc_chunk_step", "jit_tc_fused_step"}


def test_mesh_steps_lower_to_distinct_modules():
    from repro.distributed import tc as dtc

    mesh1 = jax.make_mesh((1,), ("d",))
    mesh2 = jax.make_mesh((1, 1), ("r", "c"))
    store = jnp.zeros((8, 2), jnp.uint32)
    idx = jnp.zeros(4, jnp.int32)
    steps = [dtc.make_tc_step(mesh1, ("d",)),
             dtc.make_sharded_cols_step(mesh1, ("d",)),
             dtc.make_sharded_2d_step(mesh2, ("r", "c"))]
    names = [_module_name(s.lower(store, store, idx, idx)) for s in steps]
    assert names == ["jit_tc_mesh_replicated_step", "jit_tc_mesh_cols_step",
                     "jit_tc_mesh_2d_step"]


def test_scoped_worklist_step_matches_the_host_worklist():
    """The scopes change metadata only: the device worklist still equals
    the host build's."""
    from repro.core import build_sbf, build_worklist, device_build_graph
    from repro.graphs import build_graph

    g = build_graph(rmat(300, 1800, seed=8), reorder=True)
    db = device_build_graph(g, 64)
    wl = build_worklist(g, build_sbf(g, 64))
    host = db.worklist.to_host()
    np.testing.assert_array_equal(host.pair_row_pos, wl.pair_row_pos)
    np.testing.assert_array_equal(host.pair_col_pos, wl.pair_col_pos)
