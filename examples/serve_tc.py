"""Triangle-count-as-a-service: fused multi-graph serving demo.

Queues 32 heterogeneous small graphs into ``launch.tc_serve.TCServer``
and drains them through cross-graph fused dispatches (stacked slice
stores + one shared segment index block per batch — every graph's count
comes back from ONE kernel launch per batch), then reruns the same mix
through the per-graph ``ExecutorPool.count_async`` loop to show the
throughput delta. A second server with a deliberately tiny memory budget
shows admission control: over-budget tenants are rejected (reported, not
silently dropped) and the rest wave through within the budget.

The durable-serving act: a WAL-backed stream takes delta batches, the
server is killed mid-stream (dropped without ``close_stream`` or
``checkpoint()``, exactly as a crashed process would leave the
directory), and ``TCServer.restore`` replays the delta tail past the
last committed snapshot — at most ``checkpoint_every`` records — back to
the bit-identical running count, then keeps serving deltas as if nothing
happened.

    PYTHONPATH=src python examples/serve_tc.py
"""
import itertools
import tempfile
import time

import numpy as np

from repro.core import Executor, build_sbf, build_worklist
from repro.core.executor import ExecutorPool
from repro.graphs import build_graph, rmat
from repro.launch.tc_serve import ServeConfig, TCServer
from repro.runtime.compile_cache import enable_compile_cache

NUM_GRAPHS = 32
ROUNDS = 3
SIZES = (64, 96, 128, 192, 256, 384, 512, 768)


def build_mix():
    jobs = []
    for i in range(NUM_GRAPHS):
        n = SIZES[i % len(SIZES)]
        g = build_graph(rmat(n, 6 * n, seed=i))
        sbf = build_sbf(g, 64)
        jobs.append((sbf, build_worklist(g, sbf)))
    return jobs


def main():
    jobs = build_mix()
    pairs = [wl.num_pairs for _, wl in jobs]
    print(f"mix: {NUM_GRAPHS} graphs, {min(pairs)}-{max(pairs)} slice pairs")

    # -------- fused serving --------------------------------------------
    srv = TCServer(ServeConfig(max_fused_pairs=1 << 16,
                               max_fused_graphs=NUM_GRAPHS))
    results = srv.serve(jobs)  # warm pass: stage stores, trace the steps
    t0 = time.perf_counter()
    for _ in range(ROUNDS):
        results = srv.serve(jobs)
    fused_s = time.perf_counter() - t0
    fused_gps = NUM_GRAPHS * ROUNDS / fused_s
    batches = srv.stats["fused_batches"]
    print(f"fused:   {fused_gps:8.0f} graphs/s "
          f"({batches} batched dispatches total)")

    # -------- per-graph loop (the unfused baseline) --------------------
    pool = ExecutorPool(max_graphs=NUM_GRAPHS + 1)
    loop = [pool.count_async(sb, wl).result() for sb, wl in jobs]  # warm
    t0 = time.perf_counter()
    for _ in range(ROUNDS):
        futs = [pool.count_async(sb, wl) for sb, wl in jobs]
        loop = [f.result() for f in futs]
    base_s = time.perf_counter() - t0
    base_gps = NUM_GRAPHS * ROUNDS / base_s
    print(f"unfused: {base_gps:8.0f} graphs/s "
          f"-> fusion win {fused_gps / base_gps:.1f}x")

    # Bit-identical counts, independently checked against the jnp oracle.
    # (request ids increment across rounds; map the last round's back.)
    base_id = min(r.request_id for r in results)
    served = {r.request_id - base_id: r.count for r in results}
    for rid, (sb, wl) in enumerate(jobs):
        want = Executor(sb, mode="jnp").count(wl)
        assert served[rid] == loop[rid] == want, rid
    print(f"counts:  all {NUM_GRAPHS} bit-identical to the jnp oracle")

    # -------- admission control ----------------------------------------
    tiny = TCServer(ServeConfig(memory_budget_bytes=40_000,
                                max_fused_pairs=1 << 16))
    results = tiny.serve(jobs)
    ok = [r for r in results if r.status == "ok"]
    rejected = [r for r in results if r.status == "rejected"]
    print(f"admission (40KB budget): {len(ok)} served over "
          f"{tiny.stats['waves']} waves, {len(rejected)} rejected")
    for r in rejected[:3]:
        print(f"  rejected request {r.request_id}: {r.detail}")
    assert all(served[r.request_id] == r.count for r in ok)

    # -------- kill and restore (durable streams) -----------------------
    # Disjoint batches from a shuffled edge pool: every add is novel, so
    # each delta lands on the apply path (and in the WAL).
    pool_edges = np.array(list(itertools.combinations(range(96), 2)),
                          dtype=np.int32)
    np.random.default_rng(3).shuffle(pool_edges)
    wal_dir = tempfile.mkdtemp(prefix="serve_tc_wal_")
    cadence = 4

    durable = TCServer(ServeConfig(wal_dir=wal_dir,
                                   checkpoint_every=cadence))
    sid = durable.create_stream(pool_edges[:600], n=96)
    for b in range(10):  # 10 deltas at cadence 4: 2 past the snapshot
        lo = 600 + 48 * b
        durable.submit_delta(sid, added=pool_edges[lo:lo + 48])
        durable.drain()
    live = durable.stream_count(sid)
    durable._streams[sid].wal.snaps.wait()  # let the async snapshot land
    del durable  # kill: no close_stream, no checkpoint() — just gone
    print(f"durable: killed mid-stream at count {live} "
          f"(WAL at {wal_dir})")

    revived = TCServer.restore(wal_dir)
    info = revived.restore_info["streams"][sid]
    print(f"restore: replayed {info['replayed']} delta(s) "
          f"(<= cadence {cadence}), count {revived.stream_count(sid)}")
    assert revived.stream_count(sid) == live
    assert info["replayed"] <= cadence

    # The revived server keeps taking deltas where the dead one left off.
    revived.submit_delta(sid, added=pool_edges[1080:1128])
    res = revived.drain()[0]
    print(f"resume:  next delta ok, count {res.count} "
          f"(retries={res.retries})")
    revived.close_stream(sid)


if __name__ == "__main__":
    enable_compile_cache()
    main()
