#!/usr/bin/env python3
"""Smoke run of the triangle-count path on a TPU, through its public entry
points, at the published size of com-youtube (|V| = 1,134,890, |E| =
2,987,624; ``configs/tcim_graphs.py``).

    python chip_smoke.py [--seed N]      # one chip: phases a, b, c
    python chip_smoke.py --chips 4       # four chips: the sharded_2d phase

Phases on one chip, each checked against the host exact oracle
(``graphs/exact.py``):

  a. ``tcim_count`` with the default backend and ``build="auto"``: the
     count must come from the device build and the Pallas kernel.
  b. one ``TCServer.serve`` wave: 32 seeded tenants of ego-facebook's
     published size, fused into one dispatch, plus com-youtube solo.
  c. a ``StreamingTCState`` on com-youtube taking three add/remove batches
     of 1% of |E|; the running count must equal ``verify()`` after each.

With ``--chips 4`` only com-youtube runs, with ``placement="sharded_2d"``
on a 2x2 mesh of ``jax.devices()``, and the script prints which device
holds each store shard.

Graphs are generated from the config seeds offset by ``--seed``; with the
default seed com-youtube has 3,090,378 triangles. Per-phase lines give wall
and compile seconds, compilation-cache hits, the execute implementation,
the device bytes in use before and after the phase and the process's peak
so far: smoke timings from one run, not benchmark numbers. The last line of standard output is one JSON object naming the
device. Any failed check exits non-zero before that line is printed, and so
does a run with no TPU: this script never falls back to the CPU.
"""
from __future__ import annotations

import argparse
import collections
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
TENANTS = 32


class SmokeFailure(RuntimeError):
    """A phase produced a wrong or unexpected result."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def say(msg: str) -> None:
    print(msg, flush=True)


class Phase:
    """Wall and compile seconds, and cache hits/misses, over one phase."""

    def __init__(self, jax):
        self._jax = jax
        self.counts = collections.Counter()
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, name, **_):
        if name.startswith("/jax/compilation_cache/cache_"):
            self.counts[name.rsplit("/", 1)[-1]] += 1

    def _duration(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            self.counts["compile_s"] += secs

    def _memory(self) -> dict:
        return self._jax.devices()[0].memory_stats() or {}

    def run(self, label: str, fn) -> None:
        """Run one phase; ``fn`` returns the execute implementation used.

        The device keeps one running peak for the whole process, so the
        peak printed is the largest use up to the end of this phase, not
        this phase's own; the bytes in use before and after are its own.
        """
        before = self.counts.copy()
        in_use = self._memory().get("bytes_in_use", "n/a")
        t0 = time.perf_counter()
        impl = fn()
        wall = time.perf_counter() - t0
        delta = self.counts - before
        stats = self._memory()
        say(
            f"[smoke timing, not a benchmark] phase={label} wall_s={wall:.3f} "
            f"compile_s={delta['compile_s']:.3f} "
            f"cache_hits={delta['cache_hits']} "
            f"cache_misses={delta['cache_misses']} "
            f"execute_impl={impl} "
            f"bytes_in_use_before={in_use} "
            f"bytes_in_use_after={stats.get('bytes_in_use', 'n/a')} "
            f"process_peak_bytes_so_far={stats.get('peak_bytes_in_use', 'n/a')}"
        )


def _rounded(timings: dict) -> dict:
    return {k: round(v, 3) for k, v in timings.items()}


def youtube_edges(seed: int):
    from repro.configs.tcim_graphs import GRAPHS
    from repro.graphs import rmat

    cfg = GRAPHS["com-youtube"]
    return rmat(cfg.n, cfg.m, seed=cfg.seed + seed)


def exact_count(edges):
    """Host oracle; the degree relabel keeps its candidate lists small."""
    from repro.graphs import build_graph
    from repro.graphs.exact import triangles_intersection

    g = build_graph(edges, reorder=True)
    return g, triangles_intersection(g)


def phase_one_shot(jax, edges, exact):
    import jax.numpy as jnp

    from repro.core import tcim_count
    from repro.kernels import ops

    res = tcim_count(edges)
    say(f"a: tcim_count={res.triangles} exact={exact} "
        f"build={res.stats['build']} execute_impl={res.stats['execute_impl']} "
        f"stages_s={_rounded(res.timings_s)}")
    check(res.triangles == exact, f"one-shot count {res.triangles} != {exact}")
    check(res.stats["build"] == "device",
          f"build fell back to {res.stats['build']!r}")
    check(res.stats["execute_impl"] == "pallas",
          f"execute ran {res.stats['execute_impl']!r}, not the Pallas kernel")
    # The default kernel choice lowers to a Mosaic custom call on this chip.
    store = jax.ShapeDtypeStruct((1 << 22, 2), jnp.uint32)
    idx = jax.ShapeDtypeStruct((1 << 20,), jnp.int32)
    text = jax.jit(ops.popcount_and_gather_total).lower(
        store, store, idx, idx).as_text()
    check("tpu_custom_call" in text, "default gather did not lower to Pallas")
    return res.stats["execute_impl"]


def phase_serving(seed, g_youtube, exact_youtube):
    from repro.configs.tcim_graphs import GRAPHS
    from repro.core import build_sbf, build_worklist
    from repro.graphs import build_graph, rmat
    from repro.graphs.exact import triangles_intersection
    from repro.launch.tc_serve import ServeConfig, TCServer

    ego = GRAPHS["ego-facebook"]
    jobs, want = [], []
    for i in range(TENANTS):
        g = build_graph(
            rmat(ego.n, ego.m, seed=1000 + 64 * seed + i), reorder=True)
        sb = build_sbf(g)
        jobs.append((sb, build_worklist(g, sb)))
        want.append(triangles_intersection(g))
    sb = build_sbf(g_youtube)
    jobs.append((sb, build_worklist(g_youtube, sb)))
    want.append(exact_youtube)
    biggest = max(wl.num_pairs for _, wl in jobs[:TENANTS])
    # Every tenant's worklist fits one fused segment; com-youtube does not.
    srv = TCServer(ServeConfig(max_fused_pairs=1 << biggest.bit_length()))
    results = sorted(srv.serve(jobs), key=lambda r: r.request_id)
    check(len(results) == len(jobs), f"{len(results)} results for {len(jobs)}")
    for r, w in zip(results, want):
        check(r.status == "ok", f"request {r.request_id}: {r.status} {r.detail}")
        check(r.count == w, f"request {r.request_id}: {r.count} != {w}")
    placements = collections.Counter(r.placement for r in results[:TENANTS])
    check(placements == {"fused": TENANTS}, f"tenant placements {placements}")
    stats = srv.server_stats()
    say(f"b: {TENANTS} tenants fused in {stats['fused_batches']} dispatch(es), "
        f"counts match exact; com-youtube {results[-1].placement}: "
        f"{results[-1].count} == {exact_youtube}")
    impls = {stats["fused"]["execute_impl"], *stats["pool"]["execute_impls"]}
    check(impls == {"pallas"}, f"serving ran {sorted(impls)}")
    return "pallas"


def phase_streaming(seed, g_youtube, exact_youtube):
    import numpy as np

    from repro.core import StreamingTCState

    rng = np.random.default_rng(seed)
    n = g_youtube.n
    t0 = time.perf_counter()
    state = StreamingTCState(g_youtube.edges, n=n)
    say(f"c: stream seeded with {state.triangles} triangles in "
        f"{time.perf_counter() - t0:.3f}s")
    check(state.triangles == exact_youtube,
          f"stream seed count {state.triangles} != {exact_youtube}")
    k = g_youtube.m // 100
    for b in range(3):
        cur = state.current_edges()
        keys = cur[:, 0] * np.int64(n) + cur[:, 1]
        removed = cur[rng.choice(len(cur), k, replace=False)]
        cand = np.sort(rng.integers(0, n, (2 * k, 2)), axis=1)
        cand = cand[cand[:, 0] != cand[:, 1]]
        ck = np.unique(cand[:, 0] * np.int64(n) + cand[:, 1])
        ck = rng.permutation(ck[~np.isin(ck, keys)])[:k]
        added = np.stack([ck // n, ck % n], axis=1)
        res = state.apply_batch(added, removed)
        t0 = time.perf_counter()
        verified = state.verify()
        say(f"c: batch {b}: +{res.added} -{res.removed} edges touch "
            f"{res.touched_edges} edges, {res.pairs_after} pairs; running "
            f"count {res.triangles}, verify() {verified}; "
            f"stages_s={_rounded(res.timings_s)} "
            f"verify_s={time.perf_counter() - t0:.3f}")
        check(res.triangles == verified, f"batch {b}: stream drifted")
    return state.executor.execute_impl


def phase_sharded_2d(jax, edges, exact):
    from repro.core import tcim_count

    devices = jax.devices()
    # The mesh a user builds (README): jax.make_mesh, Explicit axes.
    mesh = jax.make_mesh((2, 2), ("r", "c"), devices=devices[:4])
    res = tcim_count(edges, placement="sharded_2d", mesh=mesh)
    say(f"sharded_2d: tcim_count={res.triangles} exact={exact} "
        f"placement={res.stats['placement']} "
        f"mesh_axis_types={mesh.axis_types} "
        f"stages_s={_rounded(res.timings_s)}")
    check(res.triangles == exact, f"sharded_2d count {res.triangles} != {exact}")
    check(res.stats["placement"] == "sharded_2d",
          f"placement {res.stats['placement']}")
    holders = set()
    for a in jax.live_arrays():
        if a.ndim == 2 and len(a.sharding.device_set) == 4 and not (
                a.sharding.is_fully_replicated):
            shards = sorted(
                (s.device.id, tuple((i.start, i.stop) for i in s.index))
                for s in a.addressable_shards)
            holders |= {d for d, _ in shards}
            say(f"sharded_2d: store {a.shape} spec={a.sharding.spec} "
                f"shards (device id, rows, cols)={shards}")
    check(holders == {d.id for d in devices[:4]},
          f"store shards on devices {sorted(holders)}")
    return res.stats["execute_impl"]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke: no repro package under {ROOT / 'src'}; run it "
              "from a checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))

    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"chip_smoke: no accelerator: {e}", file=sys.stderr)
        return 1
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, found {devices[0].platform}",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} device(s)",
              file=sys.stderr)
        return 1

    from repro.runtime.compile_cache import enable_compile_cache

    say(f"compile cache: {enable_compile_cache()}")
    say(f"devices: {devices}")
    phase = Phase(jax)
    t0 = time.perf_counter()
    edges = youtube_edges(args.seed)
    g, exact = exact_count(edges)
    say(f"com-youtube: |V|={g.n} |E|={g.m} exact={exact} "
        f"(host generation + oracle {time.perf_counter() - t0:.1f}s)")
    if args.chips == 4:
        phase.run("sharded_2d", lambda: phase_sharded_2d(jax, edges, exact))
    else:
        phase.run("a_one_shot", lambda: phase_one_shot(jax, edges, exact))
        phase.run("b_serving", lambda: phase_serving(args.seed, g, exact))
        phase.run("c_streaming", lambda: phase_streaming(args.seed, g, exact))
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
