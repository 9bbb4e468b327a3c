"""Serving throughput: fused multi-graph dispatch vs the per-graph loop.

The many-small-graphs regime GraphChallenge scores (sustained throughput
over a stream of graphs) is exactly where per-graph dispatch overhead
dominates: a fleet of SNAP-scale-or-smaller tenants pays one jit dispatch,
two index uploads, and one readback per graph even with ``count_async``
overlap. ``launch.tc_serve``'s cross-graph fusion retires a whole batch in
ONE dispatch; this bench measures the win and gates it:

  * **unfused baseline** — the per-graph ``ExecutorPool.count_async`` loop
    (dispatch every graph, then resolve every future), steady-state: the
    pool already holds every graph's device stores.
  * **fused serving** — ``TCServer.serve`` over the same mix,
    steady-state: the fused batch cache already holds the stacked stores
    and index blocks, so each round is one dispatch + one readback.

Rows report sustained graphs/sec, per-graph p50/p99 latency, the
fused-vs-unfused throughput ratio (gated >= ``SERVE_GATE_RATIO``), count
parity against the independent jnp oracle (gated exact), and the
admission-control scenario's reject count. ``run()`` returns
``(rows, failures)`` so ``ci_gate.py`` embeds the same rows in
``BENCH_ci.json``.

``run_durable()`` adds the durable-serving rows (``serve_recovery``
section): WAL-on vs WAL-off delta throughput at snapshot cadence 8 (gated
<= 10% overhead), a kill/restore scenario (gated: replay <=
``checkpoint_every`` and a bit-identical restored count), and a
fault-injected wave scenario (one ``FailureInjector`` failure per wave,
gated: every count still exact through the bounded solo-retry path).

    PYTHONPATH=src:. python benchmarks/bench_serve.py
"""
from __future__ import annotations

import sys
import time

SERVE_GATE_RATIO = 2.0
# Durable serving gates (``run_durable`` -> the ``serve_recovery`` section):
# WAL-on delta throughput within 10% of WAL-off at snapshot cadence 8, a
# killed server replays <= the cadence, restored counts bit-identical, and
# one injected failure per wave leaves every count exact.
WAL_OVERHEAD_GATE = 0.10
WAL_CHECKPOINT_EVERY = 8
NUM_GRAPHS = 32
ROUNDS = 5
# The mix: n cycles through these, m ~ EDGE_FACTOR * n, seeds all distinct.
MIX_N = (64, 96, 128, 192, 256, 384, 512, 768)
EDGE_FACTOR = 6


def _mix(num_graphs: int = NUM_GRAPHS, seed: int = 0):
    """Deterministic heterogeneous small-graph mix + jnp-oracle counts."""
    from repro.core import build_sbf, build_worklist
    from repro.core.executor import Executor
    from repro.graphs import build_graph, rmat

    jobs, oracle = [], []
    for i in range(num_graphs):
        n = MIX_N[i % len(MIX_N)]
        g = build_graph(rmat(n, EDGE_FACTOR * n, seed=seed + i))
        sb = build_sbf(g, 64)
        wl = build_worklist(g, sb)
        jobs.append((sb, wl))
        oracle.append(Executor(sb, mode="jnp").count(wl))
    return jobs, oracle


def _pct(sorted_vals, q: float) -> float:
    if not sorted_vals:
        return 0.0
    k = min(int(q * (len(sorted_vals) - 1) + 0.5), len(sorted_vals) - 1)
    return sorted_vals[k]


def _bench_unfused(jobs, rounds: int):
    """Per-graph ``count_async`` loop: dispatch all, resolve all."""
    from repro.core.executor import ExecutorPool

    pool = ExecutorPool(max_graphs=len(jobs) + 1)
    counts = [pool.count_async(sb, wl).result() for sb, wl in jobs]  # warm
    lats: list[float] = []
    t_all = time.perf_counter()
    for _ in range(rounds):
        t0 = time.perf_counter()
        futs = [pool.count_async(sb, wl) for sb, wl in jobs]
        got = []
        for f in futs:
            got.append(f.result())
            lats.append(time.perf_counter() - t0)
        assert got == counts
    total_s = time.perf_counter() - t_all
    return counts, total_s, sorted(lats)


def _bench_fused(jobs, rounds: int):
    """``TCServer.serve`` over the same mix (fused batches, cached)."""
    from repro.launch.tc_serve import ServeConfig, TCServer

    srv = TCServer(
        ServeConfig(
            max_fused_pairs=1 << 16,
            max_fused_graphs=len(jobs),
        )
    )
    warm = {r.request_id: r.count for r in srv.serve(jobs)}
    counts = [warm[i] for i in sorted(warm)]
    lats: list[float] = []
    t_all = time.perf_counter()
    for _ in range(rounds):
        results = srv.serve(jobs)
        assert all(r.status == "ok" for r in results)
        lats.extend(r.latency_s for r in results)
    total_s = time.perf_counter() - t_all
    return counts, total_s, sorted(lats), srv


def _admission_row(jobs) -> dict:
    """Tiny-budget scenario: rejects reported, admitted counts still exact."""
    from repro.core.plan import pow2_ceil
    from repro.launch.tc_serve import ServeConfig, TCServer

    footprints = sorted(
        (
            pow2_ceil(max(int(sb.row_slice_data.shape[0]), 1)) * 8
            + pow2_ceil(max(int(sb.col_slice_data.shape[0]), 1)) * 8
            + pow2_ceil(max(wl.num_pairs, 1)) * 8
            for sb, wl in jobs
        )
    )
    # Budget sized so the largest graphs can never fit but the median can.
    budget = footprints[len(footprints) // 2] * 2
    srv = TCServer(
        ServeConfig(memory_budget_bytes=budget, max_fused_pairs=1 << 16)
    )
    results = srv.serve(jobs)
    return {
        "budget_bytes": budget,
        "submitted": len(jobs),
        "rejected": srv.stats.get("rejected", 0),
        "served": sum(1 for r in results if r.status == "ok"),
        "waves": srv.stats.get("waves", 0),
    }


def _edge_pool(n: int, seed: int):
    """Shuffled pool of distinct undirected edges over ``n`` vertices.

    Slicing the pool yields disjoint batches, so every add is novel and the
    stream validation layer never rejects — deltas hit the apply path."""
    import itertools

    import numpy as np

    pool = np.array(list(itertools.combinations(range(n), 2)), dtype=np.int32)
    rng = np.random.default_rng(seed)
    rng.shuffle(pool)
    return pool


def _bench_stream(pool, *, seed_edges: int, batches: int, batch: int,
                  wal_dir=None, checkpoint_every: int = WAL_CHECKPOINT_EVERY):
    """One durable-stream pass: seed, then ``batches`` delta waves.

    Returns ``(final_count, total_s, sorted_latencies, server, sid)``; the
    caller is responsible for closing/abandoning the server."""
    from repro.launch.tc_serve import ServeConfig, TCServer

    n = int(pool.max()) + 1
    srv = TCServer(ServeConfig(
        wal_dir=None if wal_dir is None else str(wal_dir),
        checkpoint_every=checkpoint_every,
    ))
    sid = srv.create_stream(pool[:seed_edges], n=n)
    lats: list[float] = []
    t_all = time.perf_counter()
    for b in range(batches):
        lo = seed_edges + b * batch
        t0 = time.perf_counter()
        rid = srv.submit_delta(sid, added=pool[lo:lo + batch])
        res = {r.request_id: r for r in srv.drain()}[rid]
        assert res.status == "ok", res.detail
        lats.append(time.perf_counter() - t0)
    total_s = time.perf_counter() - t_all
    return srv.stream_count(sid), total_s, sorted(lats), srv, sid


def _durable_overhead_row(pool, *, seed_edges: int, batches: int,
                          batch: int, tmp) -> dict:
    """WAL on (cadence 8) vs WAL off over the identical delta schedule."""
    # Throwaway pass so jit warmup doesn't land on the WAL-off timing.
    _bench_stream(pool, seed_edges=seed_edges, batches=batches, batch=batch)
    count_off, off_s, off_lats, srv_off, _ = _bench_stream(
        pool, seed_edges=seed_edges, batches=batches, batch=batch)
    count_on, on_s, on_lats, srv_on, sid = _bench_stream(
        pool, seed_edges=seed_edges, batches=batches, batch=batch,
        wal_dir=tmp / "overhead")
    srv_on._streams[sid].wal.snaps.wait()  # drain async snapshot writes
    overhead = on_s / max(off_s, 1e-9) - 1.0
    return {
        "scenario": "wal_overhead",
        "deltas": batches,
        "batch_edges": batch,
        "checkpoint_every": WAL_CHECKPOINT_EVERY,
        "deltas_per_s_wal_off": round(batches / max(off_s, 1e-9), 2),
        "deltas_per_s_wal_on": round(batches / max(on_s, 1e-9), 2),
        "wal_overhead": round(overhead, 4),
        "p50_wal_off_ms": round(1e3 * _pct(off_lats, 0.50), 3),
        "p99_wal_off_ms": round(1e3 * _pct(off_lats, 0.99), 3),
        "p50_wal_on_ms": round(1e3 * _pct(on_lats, 0.50), 3),
        "p99_wal_on_ms": round(1e3 * _pct(on_lats, 0.99), 3),
        "counts_ok": bool(count_on == count_off),
        "gate_overhead": WAL_OVERHEAD_GATE,
    }


def _durable_kill_restore_row(pool, *, seed_edges: int, batches: int,
                              batch: int, tmp) -> dict:
    """Abandon a WAL-backed server mid-stream; restore must replay <=
    ``checkpoint_every`` deltas to the bit-identical count."""
    from repro.launch.tc_serve import TCServer

    wal_dir = tmp / "kill"
    live_count, _, _, srv, sid = _bench_stream(
        pool, seed_edges=seed_edges, batches=batches, batch=batch,
        wal_dir=wal_dir)
    srv._streams[sid].wal.snaps.wait()
    del srv  # simulated kill: no close_stream, no checkpoint()
    t0 = time.perf_counter()
    srv2 = TCServer.restore(str(wal_dir))
    restore_s = time.perf_counter() - t0
    info = srv2.restore_info["streams"][sid]
    return {
        "scenario": "kill_restore",
        "deltas": batches,
        "checkpoint_every": WAL_CHECKPOINT_EVERY,
        "replayed": info["replayed"],
        "requeued": info["requeued"],
        "restore_ms": round(1e3 * restore_s, 3),
        "counts_identical": bool(srv2.stream_count(sid) == live_count),
    }


def _durable_faulted_wave_row(num_graphs: int, rounds: int) -> dict:
    """One injected dispatch failure per wave; every count must still be
    exact via the bounded solo retry path."""
    from repro.launch.tc_serve import ServeConfig, TCServer
    from repro.runtime.fault import FailureInjector

    jobs, oracle = _mix(num_graphs, seed=7000)
    inj = FailureInjector(fail_every=num_graphs)  # one request id per wave
    srv = TCServer(ServeConfig(max_fused_pairs=1 << 16,
                               max_fused_graphs=num_graphs, injector=inj))
    lats: list[float] = []
    exact = 0
    t_all = time.perf_counter()
    for _ in range(rounds):
        results = sorted(srv.serve(jobs), key=lambda r: r.request_id)
        lats.extend(r.latency_s for r in results)
        exact += sum(1 for r, want in zip(results, oracle)
                     if r.status == "ok" and r.count == want)
    total_s = time.perf_counter() - t_all
    lats.sort()
    n_served = num_graphs * rounds
    return {
        "scenario": "faulted_wave",
        "rounds": rounds,
        "graphs_per_round": num_graphs,
        "injected_failures": inj.failures,
        "retries": srv.stats.get("retries", 0),
        "graphs_per_s": round(n_served / max(total_s, 1e-9), 2),
        "p50_ms": round(1e3 * _pct(lats, 0.50), 3),
        "p99_ms": round(1e3 * _pct(lats, 0.99), 3),
        "counts_ok": bool(exact == n_served),
    }


def run_durable(num_graphs: int = 16, rounds: int = 4):
    """Durable-serving rows for the ``serve_recovery`` section of
    ``BENCH_ci.json``; returns ``(rows, failures)``.

    Gates: WAL overhead <= ``WAL_OVERHEAD_GATE`` at cadence
    ``WAL_CHECKPOINT_EVERY``, kill/restore replay <= the cadence with a
    bit-identical count, and exact counts under one injected failure per
    wave."""
    import tempfile
    from pathlib import Path

    from benchmarks.common import emit

    tmp = Path(tempfile.mkdtemp(prefix="bench_serve_wal_"))
    pool = _edge_pool(256, seed=11)
    overhead = _durable_overhead_row(
        pool, seed_edges=2048, batches=24, batch=96, tmp=tmp)
    # 26 deltas at cadence 8: last snapshot covers 24, so restore must
    # replay a real (but bounded) 2-delta tail.
    kill = _durable_kill_restore_row(
        pool, seed_edges=2048, batches=26, batch=96, tmp=tmp)
    fault = _durable_faulted_wave_row(num_graphs, rounds)
    rows = [overhead, kill, fault]
    failures = []
    if (not overhead["counts_ok"]
            or overhead["wal_overhead"] > WAL_OVERHEAD_GATE):
        failures.append(overhead)
    if (not kill["counts_identical"]
            or kill["replayed"] > WAL_CHECKPOINT_EVERY):
        failures.append(kill)
    # fail_every skips request id 0, so "one per wave" yields rounds - 1.
    if (not fault["counts_ok"] or fault["injected_failures"] < rounds - 1):
        failures.append(fault)
    emit(
        "serve_wal_overhead",
        1e4 * max(overhead["wal_overhead"], 0.0),
        f"{overhead['deltas_per_s_wal_on']:.0f}dps_"
        f"replay{kill['replayed']}_"
        f"{'ok' if not failures else 'GATE_FAIL'}",
    )
    return rows, failures


def run(num_graphs: int = NUM_GRAPHS, rounds: int = ROUNDS):
    """Returns ``(rows, failures)``; rows are the ``serve`` entries for
    ``BENCH_ci.json`` and failures the gate-violating subset."""
    from benchmarks.common import emit

    jobs, oracle = _mix(num_graphs)
    base_counts, base_s, base_lats = _bench_unfused(jobs, rounds)
    fused_counts, fused_s, fused_lats, srv = _bench_fused(jobs, rounds)

    n_served = num_graphs * rounds
    base_gps = n_served / max(base_s, 1e-9)
    fused_gps = n_served / max(fused_s, 1e-9)
    ratio = fused_gps / max(base_gps, 1e-9)
    counts_ok = list(base_counts) == oracle and list(fused_counts) == oracle
    admission = _admission_row(jobs)
    row = {
        "mix": f"{num_graphs}x rmat n<= {max(MIX_N)}",
        "rounds": rounds,
        "graphs_per_s_unfused": round(base_gps, 2),
        "graphs_per_s_fused": round(fused_gps, 2),
        "ratio": round(ratio, 3),
        "p50_unfused_ms": round(1e3 * _pct(base_lats, 0.50), 3),
        "p99_unfused_ms": round(1e3 * _pct(base_lats, 0.99), 3),
        "p50_fused_ms": round(1e3 * _pct(fused_lats, 0.50), 3),
        "p99_fused_ms": round(1e3 * _pct(fused_lats, 0.99), 3),
        "counts_ok": bool(counts_ok),
        "fused_batches": srv.stats.get("fused_batches", 0),
        "admission": admission,
        "gate_ratio": SERVE_GATE_RATIO,
    }
    bad = (not counts_ok) or ratio < SERVE_GATE_RATIO or (
        admission["rejected"] == 0
        or admission["served"] + admission["rejected"] != admission["submitted"]
    )
    emit(
        "serve_fused_vs_loop",
        1e6 * fused_s / n_served,
        f"{fused_gps:.0f}_gps_{ratio:.2f}x_"
        f"p99_{row['p99_fused_ms']:.1f}ms_"
        f"{'ok' if counts_ok else 'COUNT_MISMATCH'}",
    )
    return [row], ([row] if bad else [])


if __name__ == "__main__":
    from repro.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    from benchmarks.common import emit_bench_json

    out = sys.argv[1] if len(sys.argv) > 1 else "BENCH_ci.json"
    rows, failures = run()
    emit_bench_json(out, "serve", rows,
                    gates={"serve_gate_ratio": SERVE_GATE_RATIO})
    r = rows[0]
    print(
        f"  [{'FAIL' if failures else 'ok'}] serve {r['mix']}: "
        f"fused={r['graphs_per_s_fused']:.0f} g/s "
        f"unfused={r['graphs_per_s_unfused']:.0f} g/s "
        f"ratio={r['ratio']:.2f}x (gate {SERVE_GATE_RATIO}x) "
        f"p50/p99 fused {r['p50_fused_ms']:.1f}/{r['p99_fused_ms']:.1f}ms "
        f"counts {'match' if r['counts_ok'] else 'MISMATCH'} "
        f"rejects={r['admission']['rejected']}"
    )
    drows, dfail = run_durable()
    emit_bench_json(out, "serve_recovery", drows,
                    gates={"wal_overhead": WAL_OVERHEAD_GATE,
                           "checkpoint_every": WAL_CHECKPOINT_EVERY})
    for d in drows:
        bad = d in dfail
        print(f"  [{'FAIL' if bad else 'ok'}] serve_recovery "
              f"{d['scenario']}: " + " ".join(
                  f"{k}={v}" for k, v in d.items() if k != "scenario"))
    failures += dfail
    print(f"wrote {out}: {len(rows)} serve + {len(drows)} serve_recovery rows")
    sys.exit(1 if failures else 0)
