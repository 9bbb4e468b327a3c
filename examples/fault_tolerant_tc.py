"""Fault-tolerant sharded triangle counting: kill a device mid-count,
shrink the mesh, resume from the checkpointed cursor.

The count starts on a (4, 2) mesh with both slice stores sharded over the
owner grid. Every ``checkpoint_every`` psum steps the driver commits: it
reads back the pending per-step scalars into the exact partial total and
writes the schedule cursor (per-stripe consumed-pair offsets) through the
async checkpointer. A failure injected mid-schedule surfaces as
``CountInterrupted`` carrying the last committed cursor; the supervisor
then drops two devices, picks a (3, 2) mesh via ``tc_remesh_plan``,
restores the stores from the snapshot onto the survivors
(``load_checkpoint(shardings=...)``), re-partitions the remaining pairs,
and finishes. Because the reduction is a commutative integer monoid over
disjoint pair windows, the resumed count is bit-identical to an
uninterrupted run — at most ``checkpoint_every`` steps are replayed.

A CPU-only demo: it pins the CPU backend and forces 8 host devices, so it
is genuinely multi-device and never takes an accelerator by accident.

    PYTHONPATH=src python examples/fault_tolerant_tc.py
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import shutil  # noqa: E402
import tempfile  # noqa: E402

import jax  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh  # noqa: E402

from repro.core import Executor, build_sbf, build_worklist  # noqa: E402
from repro.distributed import (  # noqa: E402
    ResilienceConfig,
    resilient_tc_count,
    resume_tc_count,
)
from repro.graphs import build_graph, rmat  # noqa: E402
from repro.runtime import FailureInjector  # noqa: E402
from repro.runtime.compile_cache import enable_compile_cache  # noqa: E402


def main():
    g = build_graph(rmat(4000, 60_000, seed=11), reorder=True)
    sbf = build_sbf(g)
    wl = build_worklist(g, sbf)
    oracle = Executor(sbf, mode="jnp").count(wl)
    print(f"graph: n={g.n} m={g.m} pairs={wl.num_pairs} oracle={oracle}")

    devs = jax.devices()
    mesh = Mesh(
        np.asarray(devs[:8], dtype=object).reshape(4, 2), ("rows", "cols")
    )
    ckpt_dir = tempfile.mkdtemp(prefix="repro_tc_ckpt_")
    try:
        print("\n== kill 2 of 8 devices at step 9, recover in-process ==")
        cfg = ResilienceConfig(
            checkpoint_dir=ckpt_dir,
            checkpoint_every=8,
            injector=FailureInjector(fail_at_steps=(9,)),
            lose_devices=2,
        )
        total, info = resilient_tc_count(sbf, wl, mesh, cfg,
                                         chunk_pairs=4096)
        r = info["remeshes"][0]
        print(f"failed at step {r['failed_step']} "
              f"(committed {r['committed_step']}), "
              f"remeshed 4x2 -> {r['grid'][0]}x{r['grid'][1]}, "
              f"replayed {info['steps_replayed']} step(s) "
              f"in {info['recovery_s']:.3f}s")
        print(f"count={total} exact={total == oracle}")
        assert total == oracle, (total, oracle)

        print("\n== the process itself dies: resume from disk alone ==")
        shutil.rmtree(ckpt_dir)
        cfg = ResilienceConfig(
            checkpoint_dir=ckpt_dir,
            checkpoint_every=8,
            injector=FailureInjector(fail_at_steps=(9,)),
            max_failures=0,  # don't recover in-process — simulate a crash
        )
        try:
            resilient_tc_count(sbf, wl, mesh, cfg, chunk_pairs=4096)
        except Exception as e:
            print(f"count died: {e}")
        small = Mesh(
            np.asarray(devs[:6], dtype=object).reshape(3, 2),
            ("rows", "cols"),
        )
        total, info = resume_tc_count(ckpt_dir, small)
        print(f"resumed attempt {info['attempt']} on "
              f"{info['grid'][0]}x{info['grid'][1]}: "
              f"{info['steps']} steps remaining")
        print(f"count={total} exact={total == oracle}")
        assert total == oracle, (total, oracle)
    finally:
        shutil.rmtree(ckpt_dir, ignore_errors=True)


if __name__ == "__main__":
    enable_compile_cache()
    main()
