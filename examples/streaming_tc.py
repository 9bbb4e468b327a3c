"""Streaming incremental triangle counting — edge batches in, deltas out.

A resident :class:`repro.core.StreamingTCState` keeps the SBF stores on
device and maintains a running triangle count across add/remove edge
batches WITHOUT full recounts: each batch scatters word-level lane updates
into the resident stores, enumerates only the slice pairs the batch's
endpoints touch, and closes a signed correction

    delta = count(touched pairs, after) - count(touched pairs, before)

in two fused dispatches — O(touched pairs) per batch, not O(all pairs).
Every term from an untouched pair cancels exactly, so the running count is
bit-identical to a from-scratch ``tcim_count`` of the current edge set (the
demo checks this after every batch, and times the delta against the full
recount a non-incremental system would pay).

    PYTHONPATH=src python examples/streaming_tc.py
"""
import time

import numpy as np

from repro.core import StreamingTCState, tcim_count, tcim_count_delta
from repro.graphs import build_graph, erdos_renyi
from repro.runtime.compile_cache import enable_compile_cache


def main():
    # An Erdős–Rényi graph with ~1%-of-edges batches: the streaming
    # sweet spot, where a batch's endpoints touch a small fraction of the
    # slice pairs. (On hub-dense power-law graphs a large random batch can
    # touch most pairs — there a recount wins; see benchmarks/
    # bench_streaming.py, which reports both regimes.)
    g = build_graph(erdos_renyi(30000, 150000, seed=0), reorder=False)
    rng = np.random.default_rng(0)
    order = rng.permutation(g.m)
    cut = int(g.m * 0.99)
    base, pool = g.edges[order[:cut]], g.edges[order[cut:]]

    t0 = time.perf_counter()
    state = StreamingTCState(base, n=g.n)
    print(f"seed: {state.num_edges} edges, {state.triangles} triangles "
          f"({time.perf_counter() - t0:.3f}s full count, resident stores)")

    # Stream the pool in, then mixed add/remove churn, then drain it out.
    batches = [
        {"added": pool},
        {"added": None, "removed": pool[: len(pool) // 2]},
        {"added": pool[: len(pool) // 2], "removed": pool[len(pool) // 2:]},
        {"removed": pool[: len(pool) // 2]},
    ]
    for i, kw in enumerate(batches):
        t0 = time.perf_counter()
        res = tcim_count_delta(state, kw.get("added"), kw.get("removed"))
        dt = time.perf_counter() - t0
        t0 = time.perf_counter()
        full = tcim_count(state.current_edges(), n=g.n).triangles
        rt = time.perf_counter() - t0
        assert full == res.triangles, (full, res.triangles)
        print(f"batch {i}: +{res.added} -{res.removed} edges -> "
              f"delta {res.delta:+d} ({res.pairs_after} touched pairs, "
              f"{dt * 1e3:.1f}ms delta vs {rt * 1e3:.1f}ms recount, "
              f"{rt / max(dt, 1e-9):.1f}x) running={res.triangles}")

    state.verify()  # bit-identical invariant, asserted one last time
    print(f"final: {state.num_edges} edges, {state.triangles} triangles "
          f"— running count matches from-scratch tcim_count")


if __name__ == "__main__":
    enable_compile_cache()
    main()
