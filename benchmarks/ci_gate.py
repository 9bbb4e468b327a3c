"""CI bench gate: emit ``BENCH_ci.json``; enforce imbalance + step bounds.

Runs the table5 smoke row (smallest bench graph, end-to-end with triangle
counts asserted > 0), the planner's weighted-vs-even split imbalance on the
degree-ordered bench graphs, the stripe scheduler's psum-step counts
(packed vs lockstep) on the imbalanced fixed-bounds fixture, and the build
front ends (host NumPy vs jitted device) side by side, writes everything to
``BENCH_ci.json`` (uploaded as a CI artifact — the repo's bench
trajectory), and exits nonzero on any gate violation:

    PYTHONPATH=src:. python benchmarks/ci_gate.py [out.json]

Gates:
  * **imbalance** — weighted (pair-count-balanced) ranges keep
    ``plan.imbalance`` <= ``IMBALANCE_GATE`` on every owner grid CI
    exercises, where the legacy contiguous even split shows 2-5x.
  * **stripe steps** — the packed schedule never issues more psum steps
    than lockstep on ANY gate config, and on the designated imbalanced
    fixed-bounds fixture (``STEP_FIXTURE``: the even split's skewed blocks
    re-planned as caller-pinned bounds) it issues at least
    ``STEP_GATE_REDUCTION`` fewer. Counts are bit-identical across
    policies (pinned by the distributed test suites); the gate pins the
    dispatch count.
  * **staged lanes** — the compact emitter (drained shards share one
    cached sentinel buffer instead of staging fresh rows) never stages
    more index lanes than the dense ``[S, bucket]`` block on ANY gate
    config, and on ``STEP_FIXTURE`` it stages at least
    ``STAGED_GATE_REDUCTION`` fewer — the step-bytes regression gate for
    budget-aware packed widths.
  * **serve** — ``bench_serve.run()``: the fused multi-graph server
    sustains >= ``bench_serve.SERVE_GATE_RATIO`` (2x) the per-graph
    ``count_async`` loop's graphs/sec on a 32-graph mix, every count
    bit-identical to the jnp oracle, and admission control must reject
    (and report) over-budget tenants in the tiny-budget scenario.
  * **serve recovery** — ``bench_serve.run_durable()``: durable-serving
    rows. WAL-on delta throughput stays within
    ``bench_serve.WAL_OVERHEAD_GATE`` (10%) of WAL-off at snapshot
    cadence 8; a killed WAL-backed server restores to the bit-identical
    stream count replaying <= ``checkpoint_every`` deltas; one injected
    dispatch failure per wave still yields every count exact through the
    bounded solo-retry path.
  * **build parity** — the device build's worklist size and triangle count
    equal the host build's on every gate graph (the ``build`` rows also
    carry ``build_host_s``/``build_device_s`` per-stage timings so the
    bench trajectory attributes wall-clock to the build front end).
  * **recovery** — the resilience layer's cost and correctness, run in a
    subprocess with 8 forced host devices (XLA locks the device count at
    init, so the parent stays single-device for the other rows). Per mesh
    ((1, 4) and (4, 2)): steady-state checkpoint overhead at cadence 8
    (min-of-3 resumable vs plain count, snapshot pre-written and reported
    separately as ``snapshot_s``) must stay under
    ``RECOVERY_OVERHEAD_GATE``; a kill-a-device run (fail mid-schedule,
    shrink-remesh, resume from the cursor) must reproduce the exact count
    with ``steps_replayed <= checkpoint_every``; rows carry the replay
    count and recovery wall-clock for the bench trajectory.
  * **lint** — tclint over ``src/`` against ``tools/tclint/baseline.json``
    (kept empty): zero non-baseline invariant violations; stale baseline
    entries are reported as shrinkage so fixes retire their
    grandfathering in the same PR. Rows land in the ``lint`` section.
  * **streaming** — ``bench_streaming.run()``: exact running-count parity
    on every fixture/batch size, and delta batches >=
    ``bench_streaming.STREAM_GATE_SPEEDUP`` (3x) faster than a full
    recount at the 1% batch size on the gate fixtures (edges/sec rows).

All sections land in ``BENCH_ci.json`` through the shared append-safe
writer (``benchmarks.common.emit_bench_json``), one merge + atomic
replace per section.

Plan/schedule checks are pure numpy and the build check is two small
end-to-end counts, so the gate runs in seconds on one device.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

IMBALANCE_GATE = 1.25
STEP_GATE_REDUCTION = 0.30
# Degree-ordered bench graphs small enough for a fast CI job.
GATE_GRAPHS = ("ego-facebook", "email-enron")
# (row_shards, col_shards) owner grids the gate checks, 1-D and 2-D.
GATE_GRIDS = ((1, 4), (1, 8), (2, 2), (4, 2))
# The imbalanced fixed-bounds fixture rows that must show the packed win:
# even-split blocks on these grids are >= 2x imbalanced on ego-facebook.
STEP_FIXTURE = ("ego-facebook", (4, 2))
# Budget sizing: lockstep walks the longest stripe in ~this many windows.
STEP_GATE_WINDOWS = 16
# Compact staging must drop at least this fraction of the dense index
# lanes on STEP_FIXTURE (measured ~0.62 there; 0.39+ on every gate config).
STAGED_GATE_REDUCTION = 0.30
# Resilience gates: steady-state checkpoint overhead ceiling at cadence 8,
# on a fixture big enough that per-step work dominates the commit cost.
RECOVERY_OVERHEAD_GATE = 0.10
RECOVERY_CHECKPOINT_EVERY = 8

# Runs with 8 forced host devices in a fresh interpreter; prints one JSON
# line ("ROWS <json>") the parent parses. Kept as source (not a function)
# because the parent process must not import jax with a forced device count.
_RECOVERY_SRC = """
import json, os, sys, tempfile, time
import numpy as np
import jax
from jax.sharding import Mesh

from repro.core import build_sbf, build_worklist
from repro.graphs import build_graph, rmat
from repro.distributed import ResilienceConfig, TCCheckpoint, resilient_tc_count
from repro.distributed.resilient import _build_executor
from repro.runtime import FailureInjector

EVERY = %(every)d
g = build_graph(rmat(4000, 60000, seed=7), reorder=True)
sbf = build_sbf(g, 256)
wl = build_worklist(g, sbf)
devs = jax.devices()
assert len(devs) == 8, devs

rows = []
for grid, lose in (((1, 4), 1), ((4, 2), 2)):
    mesh = Mesh(np.asarray(devs[:grid[0] * grid[1]], dtype=object)
                .reshape(grid), ('rows', 'cols'))
    ex, plan = _build_executor(sbf, wl, mesh, chunk_pairs=4096,
                               schedule='packed')
    steps = ex.stripe_schedule(plan).num_steps
    want = ex.count_plan(plan)  # warm + reference
    with tempfile.TemporaryDirectory() as d:
        ckpt = TCCheckpoint(os.path.join(d, 'warm'))
        t0 = time.perf_counter()
        ckpt.save_snapshot(sbf, plan, attempt=0, base_total=0)
        ckpt.wait()
        snapshot_s = time.perf_counter() - t0
        # Interleaved min-of-5 so machine noise hits both sides equally.
        base_ts, resum_ts = [], []
        for _ in range(5):
            t0 = time.perf_counter()
            ex.count_plan(plan)
            base_ts.append(time.perf_counter() - t0)
            t0 = time.perf_counter()
            got, info = ex.count_plan_resumable(
                plan, checkpoint_every=EVERY, checkpointer=ckpt)
            ckpt.wait()
            resum_ts.append(time.perf_counter() - t0)
        baseline_s = min(base_ts)
        resumable_s = min(resum_ts)
        # Kill-a-device: fail mid-schedule, shrink, resume from the cursor.
        cfg = ResilienceConfig(
            checkpoint_dir=os.path.join(d, 'kill'), checkpoint_every=EVERY,
            injector=FailureInjector(fail_at_steps=(steps // 2 + 1,)),
            lose_devices=lose)
        t0 = time.perf_counter()
        recovered, rinfo = resilient_tc_count(sbf, wl, mesh, cfg,
                                              chunk_pairs=4096)
        kill_total_s = time.perf_counter() - t0
    rows.append({
        'grid': list(grid),
        'steps': steps,
        'checkpoint_every': EVERY,
        'commits': info['checkpoints'],
        'baseline_s': round(baseline_s, 4),
        'resumable_s': round(resumable_s, 4),
        'overhead': round(resumable_s / baseline_s - 1.0, 4),
        'snapshot_s': round(snapshot_s, 4),
        'count_ok': bool(got == want),
        'recover_grid': rinfo['grid'],
        'steps_replayed': rinfo['steps_replayed'],
        'recovery_s': round(rinfo['recovery_s'], 4),
        'kill_total_s': round(kill_total_s, 4),
        'recovered_ok': bool(recovered == want),
    })
print('ROWS ' + json.dumps(rows))
"""


def _recovery_rows() -> list[dict]:
    """Recovery bench on 8 forced host devices via a fresh interpreter."""
    env = dict(os.environ)
    # A CPU gate by design: the child pins the CPU backend so it never
    # contends with this process (or anything else) for an accelerator.
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    src_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(src_root, "src"), src_root,
         env.get("PYTHONPATH", "")])
    out = subprocess.run(
        [sys.executable, "-c",
         _RECOVERY_SRC % {"every": RECOVERY_CHECKPOINT_EVERY}],
        capture_output=True, text=True, env=env, timeout=560,
    )
    if out.returncode != 0:
        raise RuntimeError(f"recovery bench failed:\n{out.stderr[-3000:]}")
    for line in out.stdout.splitlines():
        if line.startswith("ROWS "):
            return json.loads(line[len("ROWS "):])
    raise RuntimeError(f"recovery bench emitted no ROWS line:\n{out.stdout}")


def _stripe_step_row(name, grid, plan) -> dict:
    """Packed-vs-lockstep psum step counts for one (graph, grid) plan."""
    from benchmarks.common import fixture_step_budget
    from repro.core import build_stripe_schedule

    lens = [s.num_pairs for s in plan.stripes]
    budget = fixture_step_budget(lens, plan.num_shards, STEP_GATE_WINDOWS)
    lock = build_stripe_schedule(lens, budget, policy="lockstep")
    pack = build_stripe_schedule(lens, budget, policy="packed")
    assert lock.total_pairs == pack.total_pairs == plan.total_pairs
    return {
        "graph": name,
        "grid": list(grid),
        "split": plan.split,
        "num_pairs": plan.total_pairs,
        "imbalance": round(plan.imbalance, 4),
        "budget": budget,
        "steps_lockstep": lock.num_steps,
        "steps_packed": pack.num_steps,
        "reduction": round(
            1.0 - pack.num_steps / max(lock.num_steps, 1), 4
        ),
        "lanes_lockstep": lock.total_lanes,
        "lanes_packed": pack.total_lanes,
        # Budget-aware staging: drained shards' sentinel rows are served
        # from one shared cached buffer, so only shards with live pairs in
        # a step stage fresh index lanes. ``staged`` <= ``lanes`` always;
        # the gap is the upload traffic the compact emitter saves.
        "staged_lockstep": lock.staged_lanes,
        "staged_packed": pack.staged_lanes,
        "staged_reduction": round(
            1.0 - pack.staged_lanes / max(pack.total_lanes, 1), 4
        ),
    }


def _build_row(name, g, wl) -> dict:
    """Host-vs-device build timings + parity for one gate graph."""
    from benchmarks.common import timer
    from repro.core import build_sbf, build_worklist, device_build_graph
    from repro.core.tcim import tcim_count_graph

    device_build_graph(g, 64)  # warm: compile the build traces off the clock
    with timer() as t_dev:
        db = device_build_graph(g, 64)
    with timer() as t_host:
        sb_h = build_sbf(g, 64)
        wl_h = build_worklist(g, sb_h)
    res_h = tcim_count_graph(g, build="host", collect_stats=False)
    res_d = tcim_count_graph(g, build="device", collect_stats=False)
    return {
        "graph": name,
        "build_host_s": round(t_host.s, 4),
        "build_device_s": round(t_dev.s, 4),
        "pairs_host": wl_h.num_pairs,
        "pairs_device": db.worklist.num_pairs,
        "triangles_host": res_h.triangles,
        "triangles_device": res_d.triangles,
        "host_timings": {k: round(v, 4) for k, v in res_h.timings_s.items()},
        "device_timings": {k: round(v, 4) for k, v in res_d.timings_s.items()},
    }


def _lint_result():
    """tclint over src/ against the repo baseline (pure-AST, sub-second)."""
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if repo_root not in sys.path:
        sys.path.insert(0, repo_root)
    from tools.tclint import load_baseline, run_lint

    baseline = load_baseline(
        os.path.join(repo_root, "tools", "tclint", "baseline.json")
    )
    result = run_lint(["src"], root=repo_root, baseline=baseline)
    rows = [
        {"rule": rule, "violations": count}
        for rule, count in result.counts.items()
    ]
    rows.append(
        {
            "rule": "total",
            "violations": len(result.violations),
            "baseline": len(baseline),
            "baselined_hits": len(result.baselined),
            "stale_baseline": len(result.stale_baseline),
            "suppressed_pragmas": result.suppressed,
            "files_scanned": result.files_scanned,
        }
    )
    return result, rows


def run(out_path: str = "BENCH_ci.json") -> int:
    from benchmarks.common import bench_graphs, emit_bench_json
    from benchmarks.table5_runtime import run as table5_run
    from repro.core import DeviceTopology, plan_execution

    rows = table5_run(["ego-facebook"])
    assert rows and rows[0]["triangles"] > 0, rows
    # Every section goes through the one append-safe writer (merge +
    # atomic replace), emitted as soon as it is computed — concurrent or
    # partial gate jobs can add their sections without clobbering these.
    emit_bench_json(out_path, "table5", rows, gates={
        "gate": IMBALANCE_GATE,
        "step_gate_reduction": STEP_GATE_REDUCTION,
        "staged_gate_reduction": STAGED_GATE_REDUCTION,
        "recovery_overhead_gate": RECOVERY_OVERHEAD_GATE,
    })

    lint_result, lint_rows = _lint_result()
    emit_bench_json(out_path, "lint", lint_rows)

    imbalance = []
    stripe_steps = []
    build_rows = []
    for name, cfg, scaled, g, sbf, wl in bench_graphs(GATE_GRAPHS):
        build_rows.append(_build_row(name, g, wl))
        for rows_s, cols_s in GATE_GRIDS:
            topo = DeviceTopology(num_devices=rows_s * cols_s)
            plans = {
                split: plan_execution(
                    sbf, wl, topo, placement="sharded_2d",
                    grid=(rows_s, cols_s), split=split,
                )
                for split in ("weighted", "even")
            }
            imbalance.append(
                {
                    "graph": name,
                    "grid": [rows_s, cols_s],
                    "num_pairs": wl.num_pairs,
                    "imbalance_weighted": round(plans["weighted"].imbalance, 4),
                    "imbalance_even": round(plans["even"].imbalance, 4),
                }
            )
            # The even split's skewed blocks, re-planned as caller-pinned
            # (fixed) bounds — the exact shape a pooled executor serves when
            # new work lists re-plan against resident stores.
            fixed = plan_execution(
                sbf, wl, topo, placement="sharded_2d", grid=(rows_s, cols_s),
                row_bounds=plans["even"].row_bounds,
                col_bounds=plans["even"].col_bounds,
            )
            assert fixed.split == "fixed"
            stripe_steps.append(
                _stripe_step_row(name, (rows_s, cols_s), fixed)
            )

    emit_bench_json(out_path, "imbalance", imbalance)
    emit_bench_json(out_path, "stripe_steps", stripe_steps)
    emit_bench_json(out_path, "build", build_rows)

    recovery_rows = _recovery_rows()
    emit_bench_json(out_path, "recovery", recovery_rows)

    from benchmarks.bench_serve import (
        SERVE_GATE_RATIO,
        WAL_CHECKPOINT_EVERY,
        WAL_OVERHEAD_GATE,
        run_durable as serve_durable_run,
    )
    from benchmarks.bench_serve import run as serve_run

    serve_rows, serve_failures = serve_run()
    emit_bench_json(out_path, "serve", serve_rows,
                    gates={"serve_gate_ratio": SERVE_GATE_RATIO})

    serve_rec_rows, serve_rec_failures = serve_durable_run()
    emit_bench_json(out_path, "serve_recovery", serve_rec_rows,
                    gates={"wal_overhead": WAL_OVERHEAD_GATE,
                           "checkpoint_every": WAL_CHECKPOINT_EVERY})

    from benchmarks.bench_streaming import STREAM_GATE_SPEEDUP
    from benchmarks.bench_streaming import print_rows as stream_print
    from benchmarks.bench_streaming import run as stream_run

    stream_rows, stream_failures = stream_run()
    emit_bench_json(out_path, "streaming", stream_rows,
                    gates={"streaming_gate_speedup": STREAM_GATE_SPEEDUP})

    print(f"wrote {out_path}: {len(rows)} table5 rows, "
          f"{len(imbalance)} imbalance configs, "
          f"{len(stripe_steps)} stripe-step configs, "
          f"{len(build_rows)} build configs, "
          f"{len(recovery_rows)} recovery configs, "
          f"{len(serve_rows)} serve configs, "
          f"{len(serve_rec_rows)} serve-recovery scenarios, "
          f"{len(stream_rows)} streaming configs")

    failures = [
        r for r in imbalance if r["imbalance_weighted"] > IMBALANCE_GATE
    ]
    for r in imbalance:
        status = "FAIL" if r in failures else "ok"
        print(
            f"  [{status}] {r['graph']} {r['grid'][0]}x{r['grid'][1]}: "
            f"weighted={r['imbalance_weighted']:.2f} "
            f"even={r['imbalance_even']:.2f} (gate {IMBALANCE_GATE})"
        )

    step_failures = []
    for r in stripe_steps:
        bad = r["steps_packed"] > r["steps_lockstep"]
        bad = bad or r["staged_packed"] > r["lanes_packed"]
        bad = bad or r["staged_lockstep"] > r["lanes_lockstep"]
        if (r["graph"], tuple(r["grid"])) == STEP_FIXTURE:
            bad = bad or r["reduction"] < STEP_GATE_REDUCTION
            bad = bad or r["staged_reduction"] < STAGED_GATE_REDUCTION
        if bad:
            step_failures.append(r)
        status = "FAIL" if bad else "ok"
        print(
            f"  [{status}] steps {r['graph']} {r['grid'][0]}x{r['grid'][1]} "
            f"({r['split']}, imb={r['imbalance']:.2f}): "
            f"lockstep={r['steps_lockstep']} packed={r['steps_packed']} "
            f"(-{100 * r['reduction']:.0f}%) "
            f"staged={r['staged_packed']}/{r['lanes_packed']} "
            f"(-{100 * r['staged_reduction']:.0f}%)"
        )

    build_failures = []
    for r in build_rows:
        bad = (
            r["pairs_host"] != r["pairs_device"]
            or r["triangles_host"] != r["triangles_device"]
        )
        if bad:
            build_failures.append(r)
        status = "FAIL" if bad else "ok"
        print(
            f"  [{status}] build {r['graph']}: host={r['build_host_s']:.3f}s "
            f"device={r['build_device_s']:.3f}s pairs "
            f"{r['pairs_host']}/{r['pairs_device']} triangles "
            f"{r['triangles_host']}/{r['triangles_device']}"
        )

    recovery_failures = []
    for r in recovery_rows:
        bad = (
            not r["count_ok"]
            or not r["recovered_ok"]
            or r["overhead"] > RECOVERY_OVERHEAD_GATE
            or r["steps_replayed"] > r["checkpoint_every"]
        )
        if bad:
            recovery_failures.append(r)
        status = "FAIL" if bad else "ok"
        print(
            f"  [{status}] recovery {r['grid'][0]}x{r['grid'][1]}: "
            f"overhead={100 * r['overhead']:.1f}% "
            f"(gate {100 * RECOVERY_OVERHEAD_GATE:.0f}%, "
            f"{r['commits']} commits/{r['steps']} steps, "
            f"snapshot {r['snapshot_s']:.3f}s) kill -> "
            f"{r['recover_grid'][0]}x{r['recover_grid'][1]} "
            f"replayed={r['steps_replayed']} "
            f"recovery={r['recovery_s']:.3f}s "
            f"counts {'match' if r['recovered_ok'] else 'MISMATCH'}"
        )

    for r in serve_rows:
        bad = r in serve_failures
        status = "FAIL" if bad else "ok"
        adm = r["admission"]
        print(
            f"  [{status}] serve {r['mix']}: "
            f"fused={r['graphs_per_s_fused']:.0f} g/s "
            f"unfused={r['graphs_per_s_unfused']:.0f} g/s "
            f"ratio={r['ratio']:.2f}x (gate {SERVE_GATE_RATIO}x) "
            f"p50/p99 {r['p50_fused_ms']:.1f}/{r['p99_fused_ms']:.1f}ms "
            f"counts {'match' if r['counts_ok'] else 'MISMATCH'} "
            f"rejects={adm['rejected']}/{adm['submitted']}"
        )

    for r in serve_rec_rows:
        status = "FAIL" if r in serve_rec_failures else "ok"
        if r["scenario"] == "wal_overhead":
            print(
                f"  [{status}] serve_recovery wal_overhead: "
                f"{r['deltas_per_s_wal_on']:.0f} vs "
                f"{r['deltas_per_s_wal_off']:.0f} deltas/s "
                f"({100 * r['wal_overhead']:+.1f}%, gate "
                f"{100 * WAL_OVERHEAD_GATE:.0f}% at cadence "
                f"{r['checkpoint_every']}) p50/p99 WAL-on "
                f"{r['p50_wal_on_ms']:.1f}/{r['p99_wal_on_ms']:.1f}ms "
                f"counts {'match' if r['counts_ok'] else 'MISMATCH'}"
            )
        elif r["scenario"] == "kill_restore":
            print(
                f"  [{status}] serve_recovery kill_restore: "
                f"replayed={r['replayed']} "
                f"(gate <= {r['checkpoint_every']}) "
                f"requeued={r['requeued']} "
                f"restore={r['restore_ms']:.1f}ms counts "
                f"{'identical' if r['counts_identical'] else 'MISMATCH'}"
            )
        else:
            print(
                f"  [{status}] serve_recovery faulted_wave: "
                f"{r['injected_failures']} injected / "
                f"{r['retries']} retried over {r['rounds']} waves, "
                f"{r['graphs_per_s']:.0f} g/s p50/p99 "
                f"{r['p50_ms']:.1f}/{r['p99_ms']:.1f}ms counts "
                f"{'match' if r['counts_ok'] else 'MISMATCH'}"
            )

    stream_print(stream_rows, stream_failures)

    lint_failures = lint_result.violations
    status = "FAIL" if lint_failures else "ok"
    counts = " ".join(f"{r}={c}" for r, c in lint_result.counts.items())
    print(
        f"  [{status}] lint: {len(lint_failures)} non-baseline violation(s) "
        f"({counts}) | {lint_result.suppressed} pragma-suppressed | "
        f"{len(lint_result.baselined)} baselined"
    )
    for v in lint_failures:
        print(f"      {v.path}:{v.line}: {v.rule} {v.message}")
    if lint_result.stale_baseline:
        # Shrinkage is not a failure, but it is actionable: the fixed
        # violations should leave the baseline in the same PR.
        print(
            f"      baseline can shrink by "
            f"{len(lint_result.stale_baseline)} stale entr"
            f"{'y' if len(lint_result.stale_baseline) == 1 else 'ies'}:"
        )
        for fp in lint_result.stale_baseline:
            print(f"        {fp}")

    if failures:
        print(f"imbalance gate FAILED for {len(failures)} config(s)")
    else:
        print("imbalance gate passed")
    if step_failures:
        print(f"stripe-step gate FAILED for {len(step_failures)} config(s)")
    else:
        print("stripe-step gate passed")
    if build_failures:
        print(f"build-parity gate FAILED for {len(build_failures)} config(s)")
    else:
        print("build-parity gate passed")
    if recovery_failures:
        print(f"recovery gate FAILED for {len(recovery_failures)} config(s)")
    else:
        print("recovery gate passed")
    if serve_failures:
        print(f"serve gate FAILED for {len(serve_failures)} config(s)")
    else:
        print("serve gate passed")
    if serve_rec_failures:
        print(f"serve-recovery gate FAILED for "
              f"{len(serve_rec_failures)} scenario(s)")
    else:
        print("serve-recovery gate passed")
    if stream_failures:
        print(f"streaming gate FAILED for {len(stream_failures)} config(s)")
    else:
        print("streaming gate passed")
    if lint_failures:
        print(f"lint gate FAILED: {len(lint_failures)} non-baseline "
              f"violation(s)")
    else:
        print("lint gate passed")
    return 1 if (
        failures or step_failures or build_failures or recovery_failures
        or serve_failures or serve_rec_failures or stream_failures
        or lint_failures
    ) else 0


if __name__ == "__main__":
    from repro.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    sys.exit(run(*sys.argv[1:2]))
