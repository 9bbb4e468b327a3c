"""Kernel microbenchmarks.

    PYTHONPATH=src python -m benchmarks.kernel_micro          # any backend
    PYTHONPATH=src python -m benchmarks.kernel_micro --chip   # TPU only

Without ``--chip`` the rows run on whatever backend JAX finds; on the CPU
they are interpret-mode or jnp-mirror timings of the correctness path, not
device numbers.

The execute-stage rows compare the fused gather–AND–popcount path against
the legacy gather-then-kernel path at two levels:

  * ``execute/fused_*`` vs ``execute/unfused_*`` — one chunk, kernel-level:
    fused computes straight off the device-resident stores; unfused first
    materializes gathered [P, W] operands, then reduces them.
  * ``executor/*_multichunk`` — pipeline-level: the Executor (pow2 buckets,
    device accumulator, one host sync) vs the old per-chunk ``int()``-sync
    loop with its ragged-tail retrace.

``hbm=`` derived fields carry the modeled execute-stage HBM bytes (see
``tc_gather_popcount.modeled_hbm_bytes``).

``--chip`` times the execute kernel alone on a TPU: the Pallas gather
kernel against the jnp mirror, in ns per pair, over com-youtube worklist
pairs at published size and over as many uniformly random pairs.
"""
from __future__ import annotations

import argparse
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit
from repro.core.executor import Executor
from repro.core.sbf import SlicedBitmap
from repro.kernels import ops, ref
from repro.kernels.tc_gather_popcount import modeled_hbm_bytes
from repro.runtime.compile_cache import enable_compile_cache


def _time(fn, *args, iters=3):
    fn(*args).block_until_ready()  # compile + warm
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6


def _time_host(fn, iters=3):
    """Wall-clock for paths that end in a host int (sync included)."""
    fn()  # warm/compile
    t0 = time.perf_counter()
    for _ in range(iters):
        fn()
    return (time.perf_counter() - t0) / iters * 1e6


def _synthetic_store(rng, n_rows: int, w: int, slice_bits: int = 64):
    """A SlicedBitmap-shaped store pair for executor benchmarks."""
    mk = lambda: rng.integers(0, 2**32, (n_rows, w), dtype=np.uint32)
    ptr = np.zeros(2, dtype=np.int64)
    idx = np.zeros(0, dtype=np.int32)
    return SlicedBitmap(
        slice_bits=slice_bits,
        n=1,
        n_slices=1,
        row_ptr=ptr,
        row_slice_idx=idx,
        row_slice_data=mk(),
        col_ptr=ptr,
        col_slice_idx=idx,
        col_slice_data=mk(),
    )


def _legacy_execute(row_data, col_data, row_pos, col_pos, chunk: int) -> int:
    """The pre-Executor loop: XLA gather + kernel + per-chunk host sync,
    ragged last chunk retracing. Kept here as the benchmark baseline."""
    total = 0
    for start in range(0, len(row_pos), chunk):
        rows = jnp.take(row_data, jnp.asarray(row_pos[start : start + chunk]), axis=0)
        cols = jnp.take(col_data, jnp.asarray(col_pos[start : start + chunk]), axis=0)
        total += int(ops.popcount_and_total(rows, cols))
    return total


def run() -> None:
    rng = np.random.default_rng(0)
    p, w = 1 << 16, 2
    rows = jnp.asarray(rng.integers(0, 2**32, (p, w), dtype=np.uint32))
    cols = jnp.asarray(rng.integers(0, 2**32, (p, w), dtype=np.uint32))
    us = _time(lambda a, b: ops.popcount_and_total(a, b), rows, cols)
    emit("kernel/popcount_and_total_64kpairs", us, f"words={p*w}")
    us = _time(lambda a, b: ref.ref_popcount_and_total(a, b), rows, cols)
    emit("kernel/ref_popcount_total_64kpairs", us, "oracle")

    # Execute stage, one chunk: fused gather–AND–popcount vs gather-then-kernel.
    n_rows = 1 << 14
    sb = _synthetic_store(rng, n_rows, w)
    row_data = jnp.asarray(sb.row_slice_data)
    col_data = jnp.asarray(sb.col_slice_data)
    ridx = jnp.asarray(rng.integers(0, n_rows, p, dtype=np.int32))
    cidx = jnp.asarray(rng.integers(0, n_rows, p, dtype=np.int32))
    fused = jax.jit(
        lambda rd, cd, r, c: ops.popcount_and_gather_total(rd, cd, r, c)
    )
    us_f = _time(fused, row_data, col_data, ridx, cidx, iters=10)
    emit(
        "execute/fused_gather_popcount_64kpairs",
        us_f,
        f"hbm={modeled_hbm_bytes(p, w, fused=True)}",
    )
    unfused = jax.jit(
        lambda rd, cd, r, c: ops.popcount_and_total(
            jnp.take(rd, r, axis=0), jnp.take(cd, c, axis=0)
        )
    )
    us_u = _time(unfused, row_data, col_data, ridx, cidx, iters=10)
    emit(
        "execute/unfused_gather_then_kernel_64kpairs",
        us_u,
        f"hbm={modeled_hbm_bytes(p, w, fused=False)};"
        f"fused_speedup={us_u / max(us_f, 1e-9):.2f}x",
    )

    # Execute stage, multi-chunk: Executor pipeline vs per-chunk-sync loop,
    # with and without async double-buffering of the index uploads.
    pm = 200_000  # ragged: 3 full 64k chunks + a 3k tail
    chunk = 1 << 16
    rpos = rng.integers(0, n_rows, pm, dtype=np.int64)
    cpos = rng.integers(0, n_rows, pm, dtype=np.int64)
    ex = Executor(sb, chunk_pairs=chunk)
    ex_serial = Executor(sb, chunk_pairs=chunk, double_buffer=False)
    want = ex.execute_indices(rpos, cpos)  # warm + reference
    got = _legacy_execute(row_data, col_data, rpos, cpos, chunk)
    assert got == want, (got, want)
    assert ex_serial.execute_indices(rpos, cpos) == want
    us_ex = _time_host(lambda: ex.execute_indices(rpos, cpos), iters=5)
    emit(
        "executor/fused_multichunk_200kpairs",
        us_ex,
        f"chunks=4;host_syncs=1;double_buffer=1;hbm={ex.modeled_hbm_bytes(pm)}",
    )
    us_ser = _time_host(lambda: ex_serial.execute_indices(rpos, cpos), iters=5)
    emit(
        "executor/serial_upload_multichunk_200kpairs",
        us_ser,
        f"chunks=4;host_syncs=1;double_buffer=0;"
        f"buffered_speedup={us_ser / max(us_ex, 1e-9):.2f}x",
    )
    us_old = _time_host(
        lambda: _legacy_execute(row_data, col_data, rpos, cpos, chunk), iters=5
    )
    emit(
        "executor/legacy_perchunk_sync_200kpairs",
        us_old,
        f"chunks=4;host_syncs=4;hbm={ex.modeled_hbm_bytes(pm, fused=False)};"
        f"fused_speedup={us_old / max(us_ex, 1e-9):.2f}x",
    )

    x = jnp.asarray(rng.integers(0, 2**32, (512, 16), dtype=np.uint32))
    us = _time(lambda a: ops.bitgemm(a, a), x)
    emit("kernel/bitgemm_512x512x16w", us, "")
    n = 512
    a = jnp.asarray(np.triu(rng.random((n, n)) < 0.05, 1).astype(np.float32))
    us = _time(lambda m: ops.dense_mxu_tc(m, block=128), a)
    emit("kernel/dense_mxu_tc_512", us, "")


def run_chip(pairs: int = 1 << 20, iters: int = 3) -> None:
    """The execute kernel alone on a TPU, against the jnp mirror.

    ``gather_total_pallas`` and ``gather_total_reference`` each run the
    first ``pairs`` com-youtube worklist pairs, then ``pairs`` uniformly
    random pairs, against the graph's pow2-padded device stores. A row's
    time is the best of ``iters`` runs after a warm-up; the two paths must
    give the same total.
    """
    from repro.configs.tcim_graphs import GRAPHS
    from repro.core import build_sbf, build_worklist
    from repro.graphs import build_graph, rmat
    from repro.kernels.tc_gather_popcount import (
        gather_total_pallas,
        gather_total_reference,
    )

    cfg = GRAPHS["com-youtube"]
    t0 = time.perf_counter()
    g = build_graph(rmat(cfg.n, cfg.m, seed=cfg.seed), reorder=True)
    sb = build_sbf(g, 64)
    wl = build_worklist(g, sb)
    print(f"# com-youtube host build {time.perf_counter() - t0:.1f}s, "
          f"{wl.num_pairs} pairs", flush=True)
    ex = Executor(sb)  # device-resident, pow2-padded stores
    rng = np.random.default_rng(0)
    index_sets = {
        "worklist": (wl.pair_row_pos[:pairs], wl.pair_col_pos[:pairs]),
        "random": (
            rng.integers(0, sb.row_slice_data.shape[0], pairs),
            rng.integers(0, sb.col_slice_data.shape[0], pairs),
        ),
    }
    for label, (r, c) in index_sets.items():
        args = (
            ex.row_data, ex.col_data,
            jax.device_put(np.asarray(r, np.int32)),
            jax.device_put(np.asarray(c, np.int32)),
        )
        totals = {}
        for impl, fn in (
            ("mirror", gather_total_reference), ("pallas", gather_total_pallas)
        ):
            compiled = jax.jit(fn).lower(*args).compile()
            totals[impl] = int(compiled(*args))
            best = min(
                _time(compiled, *args, iters=1) for _ in range(iters)
            )
            emit(
                f"chip/{impl}_{label}_{len(r)}pairs",
                best,
                f"ns_per_pair={best * 1e3 / len(r)};"
                f"total={totals[impl]};device={jax.devices()[0].device_kind}",
            )
        assert totals["pallas"] == totals["mirror"], (label, totals)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="kernel microbenchmarks")
    ap.add_argument("--chip", action="store_true",
                    help="time the execute kernel alone on a TPU")
    args = ap.parse_args(argv)
    enable_compile_cache()
    if not args.chip:
        run()
        return 0
    if jax.devices()[0].platform != "tpu":
        print("kernel_micro --chip needs a TPU", file=sys.stderr)
        return 1
    run_chip()
    return 0


if __name__ == "__main__":
    sys.exit(main())
