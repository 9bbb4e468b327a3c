"""The per-vertex cell on the CPU, with the chip check skipped: a sound run
is ``correct``, the control and the planted fault of ``bench/control_lcc.py``
are not; the reference, the byte count and the roofline reader besides."""
from __future__ import annotations

import copy
import json

import numpy as np
import pytest

from bench import control_lcc, graphs, harness, lcc_ref, run, trace, vertex_bytes
from bench.drivers import closed_lcc
from bench.readers import ReadContext, vertex_roofline

SEED = 2**31 + 29
CELL = "youtube.lcc"


@pytest.fixture(autouse=True)
def _no_chip_check(monkeypatch):
    monkeypatch.setattr(harness.Harness, "check_devices", lambda self, d: None)


def _small():
    spec, w, config, traffic = run._cell_files(CELL)
    config, traffic = copy.deepcopy(config), copy.deepcopy(traffic)
    config["graph"].update(n=3000, m=20000)
    # The CPU builds on the host ("auto"); what runs the pairs is the same.
    traffic["expect"]["build"] = "host"
    return spec, w, config, traffic


def _line(capsys, fault: str | None = None) -> dict:
    spec, w, config, traffic = _small()
    kw = dict(seed=SEED, seconds=1.5, trace=False)
    if fault is None:
        assert run.run_cell(spec, w, config, traffic, **kw) == 0
    else:
        with control_lcc.planted(fault, traffic["driver"]):
            assert run.run_cell(spec, w, config, traffic, **kw) == 0
    out = capsys.readouterr()
    line = json.loads(out.out.strip().splitlines()[-1])
    assert list(line)[-1] == "checks"
    assert out.err.strip().splitlines()[-1].startswith("check ")
    return line


@pytest.mark.parametrize("fault", [None, "control", "vertex_plus_one"])
def test_correct_fails_under_every_fault(fault, capsys):
    line = _line(capsys, fault)
    assert line["attempted"] > 0
    checks = line["checks"]
    assert set(checks) == {"wrong_vertices", "max_triangle_gap", "max_lcc_gap",
                           "off_path_counts"}
    assert set(line["metrics"]) == {"setup_s", "count_s"}
    if fault is None:
        assert line["correct"] is True and line["failed"] == 0
        assert all(c["value"] == 0 for c in checks.values())
    else:
        assert line["correct"] is False and line["failed"] > 0
        assert checks["wrong_vertices"]["value"] > 0
        assert checks["max_triangle_gap"]["value"] > 0
    if fault == "vertex_plus_one":
        assert checks["max_triangle_gap"]["value"] == 1
        assert checks["wrong_vertices"]["value"] == line["attempted"]


def test_faults_are_refused_for_other_drivers():
    with pytest.raises(ValueError):
        with control_lcc.planted("control", "closed_oneshot"):
            pass


@pytest.mark.parametrize("n,m,seed", [(64, 300, 1), (500, 4000, 2), (3000, 20000, 3)])
def test_reference_matches_the_program_oracle(n, m, seed):
    from repro.graphs import build_graph
    from repro.graphs.exact import local_clustering, vertex_triangles

    edges = graphs.relabel(graphs.rmat(n, m, seed), n, np.random.default_rng(seed))
    g = build_graph(edges, n=n)
    t = lcc_ref.vertex_triangles(edges, n)
    np.testing.assert_array_equal(t, vertex_triangles(g))
    np.testing.assert_array_equal(lcc_ref.local_clustering(edges, n, t),
                                  local_clustering(g))
    assert t.sum() == 3 * graphs.triangles(edges, n)


def test_copies_are_fixed_by_the_seed_and_carry_their_permutation():
    n = 400
    base = graphs.rmat(n, 3000, 9)

    def first(seed):
        return next(closed_lcc.copies(base, n, seed))

    (pa, a), (pb, b), (pc, c) = first(2**31 + 5), first(2**31 + 5), first(2**31 + 6)
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, c)
    t_base = lcc_ref.vertex_triangles(base, n)
    t_copy = lcc_ref.vertex_triangles(c, n)
    np.testing.assert_array_equal(t_copy[pc], t_base)


def test_vertex_bytes_count_pairs_and_triangles():
    assert vertex_bytes.vertex_bytes(1, 0, 64) == 2 * 8 + 6 * 4
    assert vertex_bytes.vertex_bytes(0, 1, 64) == 12
    assert vertex_bytes.vertex_bytes(1000, 10, 128) == 1000 * 56 + 120
    assert vertex_bytes.least_time_s(819, 0, 64, 819e9) == pytest.approx(40 / 1e9)


def _ev(name, s, t):
    return trace.Event(name, float(s), float(t), 0, "")


def test_vertex_roofline_reads_the_named_modules():
    tr = trace.Trace(ops=[], host=[], modules=[
        _ev("jit_tc_vertex_step", 0, 30), _ev("jit_tc_vertex_step", 50, 60),
        _ev("jit_worklist_step", 60, 100)])
    args = json.loads((harness.BENCH / "metrics" / "vertex_roofline.lcc.json")
                      .read_text())["args"]

    def ctx(pairs):
        out = harness.Outcome(attempted=len(pairs), failed=0, checks=[], metrics={},
                              graphs=len(pairs), pairs=list(pairs),
                              notes={"reference_triangles": 7})
        return ReadContext(trace=tr, window_ns=100.0, outcome=out,
                           peaks={"hbm_bytes_per_s": 819e9}, slice_bits=64)

    c = ctx([1000, 3000])
    want = 100 * vertex_bytes.least_time_s(4000, 14, 64, 819e9) / 40e-9
    assert vertex_roofline.read(c, **args) == pytest.approx(want)
    assert c.notes == {"matched": {"jit_tc_vertex_step": 2}}
    assert vertex_roofline.read(ctx([]), **args) is None
    with pytest.raises(LookupError):
        vertex_roofline.read(ctx([1]), "^jit_nothing$")
