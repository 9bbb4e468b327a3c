"""The benchmark's own pieces, on the CPU: the reference and pair counts,
the traffic, the trace reducer, the roofline bytes, BENCHMARK.json, and the
runner's refusal to run without a TPU."""
from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from bench import graphs, harness, roofline, trace
from bench.drivers import closed_oneshot
from bench.readers import ReadContext, device_ms, kernel_roofline
from bench.harness import BENCH, ROOT

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
RECORDED_CPU = BENCH / "tests" / "data" / "cpu_oneshot.xplane.pb"


# ------------------------------------------------------- reference counts
@pytest.mark.parametrize("n,m,seed", [(64, 300, 1), (300, 2000, 2), (1000, 9000, 3)])
def test_reference_matches_program_oracle(n, m, seed):
    from repro.graphs import build_graph, rmat
    from repro.graphs.exact import triangles_bruteforce, triangles_intersection

    edges = graphs.rmat(n, m, seed)
    np.testing.assert_array_equal(edges, rmat(n, m, seed=seed))
    g = build_graph(edges, n=n, reorder=True)
    want = triangles_intersection(g)
    assert graphs.triangles(edges, n) == want
    if n <= 300:
        assert triangles_bruteforce(build_graph(edges, n=n)) == want


@pytest.mark.parametrize("seed", [0, 2**31 + 11])
def test_relabelled_copies_keep_the_count_and_change_the_edges(seed):
    n, m = 500, 4000
    edges = graphs.rmat(n, m, 5)
    rng = np.random.default_rng([seed, 1])
    a, b = graphs.relabel(edges, n, rng), graphs.relabel(edges, n, rng)
    for copy in (a, b):
        assert (copy[:, 0] < copy[:, 1]).all()
        assert len(np.unique(copy, axis=0)) == len(edges)
        assert graphs.triangles(copy, n) == graphs.triangles(edges, n)
    assert not np.array_equal(a, b)


@pytest.mark.parametrize("n,m,bits", [(300, 2000, 64), (2000, 20000, 64), (2000, 20000, 32)])
def test_slice_pairs_match_the_program_worklist(n, m, bits):
    from repro.core.sbf import build_sbf, build_worklist
    from repro.graphs import build_graph

    edges = graphs.relabel(graphs.rmat(n, m, 7), n, np.random.default_rng(3))
    g = build_graph(edges, n=n, reorder=True)
    wl = build_worklist(g, build_sbf(g, bits))
    assert graphs.slice_pairs(edges, n, bits) == wl.num_pairs


# ---------------------------------------------------------------- traffic
def test_traffic_is_fixed_by_the_seed():
    """The seed draws the relabellings alone: the same seed gives the same
    copies, another seed other copies of the same graph."""
    n, m = 400, 3000
    base = graphs.rmat(n, m, 9)

    def copies(seed):
        stream = closed_oneshot.copies(base, n, seed)
        return [next(stream) for _ in range(3)]

    a, b, c = copies(2**31 + 5), copies(2**31 + 5), copies(2**31 + 6)
    for x, y, z in zip(a, b, c):
        np.testing.assert_array_equal(x, y)
        assert not np.array_equal(x, z)
        assert len(z) == len(base)


# ----------------------------------------------------------- trace reducer
def _ev(name, s, t, dev=0, module=""):
    return trace.Event(name, float(s), float(t), dev, module)


def test_busy_is_the_union_and_idle_gaps_are_named():
    tr = trace.Trace(
        ops=[_ev("a", 0, 10), _ev("b", 5, 20), _ev("c", 40, 50), _ev("d", 95, 120)],
        modules=[_ev("jit_step", 0, 20), _ev("jit_other", 40, 50)],
        host=[_ev("whole run", 0, 100), _ev("prepare", 18, 41), _ev("tiny", 60, 61)])
    assert trace.busy_ns(tr, 100) == 20 + 10 + 5
    gaps = trace.idle_gaps(tr, 100)
    assert [g[1] for g in gaps] == [45e-9, 20e-9]
    assert gaps[0][0] == "whole run"  # nothing shorter covers most of it
    assert gaps[1][0] == "prepare"
    assert trace.time_ns(trace.matching(tr.modules, "^jit_step$"), 100) == 20
    assert trace.top_ops(tr, 100)[0] == ["b", 15e-9]


def test_module_names_drop_run_suffixes():
    assert trace.module_name("jit_worklist_step(42)") == "jit_worklist_step"
    assert trace.module_name("jit_sbf_step.3") == "jit_sbf_step"


def _metric_args(name: str) -> dict:
    return json.loads((BENCH / "metrics" / f"{name}.json").read_text())["args"]


def test_recorded_cpu_trace_reduces():
    """A trace recorded on the CPU backend: one small one-shot count through
    the device build (``build="device"``) and the jnp mirror, inside a
    ``bench.tcim_count`` annotation."""
    tr = trace.load(str(RECORDED_CPU))
    window = max(e.end for e in tr.ops)
    busy = trace.busy_ns(tr, window)
    assert 0 < busy <= window
    assert busy <= sum(e.dur for e in tr.ops)
    build = trace.matching(tr.modules, _metric_args("build_device_ms.oneshot")["modules"])
    assert {e.name for e in build} == {"jit_orient", "jit_prefix", "jit_sbf_step",
                                       "jit_worklist_step"}
    assert 0 < trace.time_ns(build, window) < busy
    # The jnp mirror is no Pallas kernel: the roofline reader finds nothing
    # to read and says so.
    args = _metric_args("tc_gather_popcount_roofline.oneshot")
    assert not trace.matching(tr.ops, args["kernel"], field="text")
    with pytest.raises(LookupError):
        kernel_roofline.read(_ctx(tr, 1, [1]), **args)
    step = trace.matching(tr.ops, "^jit_step$", field="module")
    assert step and all("hlo_op=" in e.detail for e in step)
    gaps = trace.idle_gaps(tr, window)
    assert gaps and {g[0] for g in gaps} <= {e.name for e in tr.host} | {"host idle"}
    assert sum(g[1] for g in gaps) <= (window - busy) / 1e9 + 1e-12


def _ctx(tr, graphs_=0, pairs=()):
    out = harness.Outcome(attempted=graphs_, failed=0, checks=[], metrics={},
                          graphs=graphs_, pairs=list(pairs))
    return ReadContext(trace=tr, window_ns=100.0, outcome=out,
                       peaks={"hbm_bytes_per_s": 819e9}, slice_bits=64)


def test_kernel_roofline_reads_the_named_kernel_only():
    tr = trace.Trace(
        ops=[_ev("gather_total_pallas.1", 10, 30), _ev("gather_total_pallas.1", 50, 60),
             _ev("fusion.3", 0, 100)],
        modules=[], host=[])
    ctx = _ctx(tr, 2, [1000, 3000])
    want = 100 * roofline.least_time_s(4000, 64, 2, 819e9) / 30e-9
    assert kernel_roofline.read(ctx, "gather_total_pallas") == pytest.approx(want)
    assert ctx.notes == {"matched": {"gather_total_pallas": 2}, "ops": 2}
    assert kernel_roofline.read(_ctx(tr), "gather_total_pallas") is None
    with pytest.raises(LookupError):
        kernel_roofline.read(_ctx(tr, 2, [1000, 3000]), "gather_segment_totals_pallas")


def test_device_ms_reads_the_named_modules_per_graph():
    tr = trace.Trace(ops=[], host=[],
                     modules=[_ev("jit_sbf_step", 0, 40), _ev("jit_step", 40, 90)])
    args = _metric_args("build_device_ms.oneshot")
    ctx = _ctx(tr, 2, [1, 1])
    assert device_ms.read(ctx, **args) == pytest.approx(40 / 2 / 1e6)
    assert ctx.notes == {"matched": {"jit_sbf_step": 1}}
    assert device_ms.read(_ctx(tr), **args) is None
    with pytest.raises(LookupError):
        device_ms.read(_ctx(tr, 1, [1]), "^jit_nothing$")


# ---------------------------------------------------------------- roofline
def test_roofline_bytes_count_the_pairs_not_the_tiles():
    assert roofline.kernel_bytes(1, 64, 1) == 2 * 8 + 2 * 4 + 4
    assert roofline.kernel_bytes(1000, 64, 3) == 1000 * 24 + 12
    assert roofline.kernel_bytes(1000, 128, 1) == 1000 * 40 + 4
    assert roofline.least_time_s(819, 64, 0, 819e9) == pytest.approx(24 / 1e9)


# ---------------------------------------------------------- BENCHMARK.json
def test_benchmark_json_follows_the_contract():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs", "workloads",
                         "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["bench"] and 1 <= SPEC["run_seconds"] <= 51
    names = [c["name"] for c in SPEC["configs"]]
    cells = [w["name"] for w in SPEC["workloads"]]
    metrics = [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    for group in (names, cells, metrics):
        assert len(group) == len(set(group))
        assert all(NAME.match(x) for x in group)
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert (ROOT / c["file"]).is_file() and c["file"].startswith("bench/")
        assert c["name"] in {w["config"] for w in SPEC["workloads"]}
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["traffic"]) and w["config"] in names
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
        t = json.loads((BENCH / "traffic" / f"{w['traffic']}.json").read_text())
        assert (BENCH / "drivers" / f"{t['driver']}.py").is_file()
    for m in SPEC["end_to_end"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
        assert set(m["workloads"]) <= set(cells) if "workloads" in m else True
    assert "setup_s" in metrics
    for m in SPEC["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves",
                          "workloads"}
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["moves"] in {e["name"] for e in SPEC["end_to_end"]}
        spec = json.loads((BENCH / "metrics" / f"{m['name']}.json").read_text())
        assert (BENCH / "readers" / f"{spec['reader']}.py").is_file()
        e2e = next(e for e in SPEC["end_to_end"] if e["name"] == m["moves"])
        assert set(m["workloads"]) <= set(e2e.get("workloads", cells))
        if m["name"].endswith("_roofline") or "_roofline." in m["name"]:
            assert m["unit"] == "%"
    for w in cells:
        from bench.run import end_to_end, per_layer_metrics

        assert len(end_to_end(SPEC, w)) >= 2 and per_layer_metrics(SPEC, w)
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


# ------------------------------------------------------------- no device
def _run(cwd: Path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "-m", "bench.run", "--workload", "youtube.oneshot",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_runner_exits_nonzero_without_a_tpu():
    proc = _run(ROOT)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "needs a TPU" in proc.stderr


def test_runner_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(tmp_path)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
