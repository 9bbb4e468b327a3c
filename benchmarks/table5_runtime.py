"""Table V: runtime comparison — CPU baseline vs w/o-PIM vs TCIM.

Columns reproduced:
  * cpu_s      — the intersection-based baseline, measured here (vectorized
                 numpy on one core; the paper's was Spark GraphX on an E5430,
                 so absolute values differ — the *ratios* are the claim).
  * wo_pim_s   — our full slicing+reuse pipeline on the host, measured
                 (compress + schedule + jnp execute).
  * tcim_s     — behavioral-model latency of the MRAM array (energymodel).
  * fused_s    — beyond-paper: measured end-to-end time with the fused
                 gather–AND–popcount executor (the default pallas_total
                 backend; vectorized mirror on CPU, Mosaic kernel on TPU).
  * unfused_s  — same pipeline with the legacy gather-then-kernel execute
                 stage (operands travel to the compute — the anti-pattern
                 the fused executor removes); the exec_*/hbm_* derived
                 fields put the execute-stage time and modeled HBM traffic
                 of the two side by side.
  * exec_buffered_s / exec_serial_s — steady-state execute-stage time with
                 and without async double-buffering (chunk i+1's index
                 upload overlapping chunk i's kernel).
  * build_host_s / build_device_s — the orient-free build front end
                 (compress + schedule) on the host NumPy reference vs the
                 jitted device build (core.build; warm traces — the steady
                 state a fleet serves from), same bit-identical outputs.
  * sharded_s  — replicated-vs-sharded placement: the same count through
                 ``sharded_cols`` (column store NamedSharding-sharded over a
                 mesh of every visible device; nshards=1 in a single-device
                 container — see bench_sharded.py for a real shard sweep).
  * paper_*    — the paper's reported numbers for reference.
"""
from __future__ import annotations

import jax

from benchmarks.common import bench_graphs, emit, timer
from repro.core import baselines, build_sbf, build_worklist, device_build_graph
from repro.core.cachesim import simulate_lru
from repro.core.energymodel import PAPER_TABLE5, tcim_latency_energy
from repro.core.executor import Executor
from repro.core.tcim import tcim_count_graph
from repro.kernels.tc_gather_popcount import modeled_hbm_bytes
from repro.runtime.compile_cache import enable_compile_cache


def run(names=None) -> list[dict]:
    rows = []
    mesh = jax.make_mesh((len(jax.devices()),), ("d",))
    nshards = len(jax.devices())
    for name, cfg, scaled, g, sbf, wl in bench_graphs(names):
        # CPU intersection baseline (measured).
        with timer() as t_cpu:
            tri_cpu = baselines.intersection_tc(g)
        # w/o PIM: the whole sliced pipeline on host (jnp backend).
        with timer() as t_wo:
            res = tcim_count_graph(g, backend="jnp")
        # TCIM: behavioral MRAM model using worklist + cache sim stats.
        cache = simulate_lru(sbf, wl)
        tcim_s, tcim_j = tcim_latency_energy(wl.num_pairs, cache.misses, g.m)
        # Beyond-paper: fused executor vs legacy gather-then-kernel execute.
        with timer() as t_fused:
            res_f = tcim_count_graph(g, backend="pallas_total", collect_stats=False)
        with timer() as t_unf:
            res_u = tcim_count_graph(g, backend="pallas_unfused", collect_stats=False)
        # Buffered vs serial execute (steady state: stores up, traces warm).
        ex_buf = Executor(sbf, double_buffer=True)
        ex_ser = Executor(sbf, double_buffer=False)
        tri_buf = ex_buf.count(wl)  # warm
        tri_ser = ex_ser.count(wl)
        with timer() as t_buf:
            ex_buf.count(wl)
        with timer() as t_ser:
            ex_ser.count(wl)
        # Replicated vs sharded placement through the engine API.
        with timer() as t_sh:
            res_s = tcim_count_graph(
                g, placement="sharded_cols", mesh=mesh, collect_stats=False
            )
        # Host vs device build front end (warm device traces: steady state).
        db = device_build_graph(g, 64)
        with timer() as t_bdev:
            db = device_build_graph(g, 64)
        with timer() as t_bhost:
            sbf_h = build_sbf(g, 64)
            wl_h = build_worklist(g, sbf_h)
        assert db.worklist.num_pairs == wl_h.num_pairs, name
        assert res.triangles == tri_cpu == res_f.triangles == res_u.triangles, (
            name, res.triangles, tri_cpu, res_f.triangles, res_u.triangles)
        assert res.triangles == tri_buf == tri_ser == res_s.triangles, (
            name, res.triangles, tri_buf, tri_ser, res_s.triangles)
        wps = sbf.words_per_slice
        hbm_f = modeled_hbm_bytes(wl.num_pairs, wps, fused=True)
        hbm_u = modeled_hbm_bytes(wl.num_pairs, wps, fused=False)
        exec_f = res_f.timings_s["execute"]
        exec_u = res_u.timings_s["execute"]
        paper = PAPER_TABLE5.get(name, (None,) * 5)
        derived = (
            f"triangles={res.triangles};cpu_s={t_cpu.s:.3f};wo_pim_s={t_wo.s:.3f};"
            f"tcim_model_s={tcim_s:.4f};fused_s={t_fused.s:.3f};"
            f"unfused_s={t_unf.s:.3f};exec_fused_s={exec_f:.4f};"
            f"exec_unfused_s={exec_u:.4f};hbm_fused={hbm_f};hbm_unfused={hbm_u};"
            f"exec_buffered_s={t_buf.s:.4f};exec_serial_s={t_ser.s:.4f};"
            f"sharded_s={t_sh.s:.3f};nshards={nshards};"
            f"build_host_s={t_bhost.s:.4f};build_device_s={t_bdev.s:.4f};"
            f"speedup_cpu_over_tcim={t_cpu.s / max(tcim_s, 1e-12):.1f};"
            f"paper_cpu={paper[0]};paper_gpu={paper[1]};paper_fpga={paper[2]};"
            f"paper_wo_pim={paper[3]};paper_tcim={paper[4]}"
        )
        emit(f"table5/{name}", tcim_s * 1e6, derived)
        rows.append(
            {
                "name": name,
                "triangles": res.triangles,
                "cpu_s": t_cpu.s,
                "wo_pim_s": t_wo.s,
                "tcim_model_s": tcim_s,
                "tcim_model_j": tcim_j,
                "fused_s": t_fused.s,
                "unfused_s": t_unf.s,
                "exec_fused_s": exec_f,
                "exec_unfused_s": exec_u,
                "hbm_fused_bytes": hbm_f,
                "hbm_unfused_bytes": hbm_u,
                "exec_buffered_s": t_buf.s,
                "exec_serial_s": t_ser.s,
                "sharded_s": t_sh.s,
                "nshards": nshards,
                "build_host_s": t_bhost.s,
                "build_device_s": t_bdev.s,
                "paper": paper,
            }
        )
    return rows


if __name__ == "__main__":
    enable_compile_cache()
    run()
