"""Checkpointing, fault tolerance, straggler detection, elastic planning."""
from pathlib import Path

import numpy as np
import pytest

from repro.checkpoint import CheckpointManager, load_checkpoint, save_checkpoint
from repro.checkpoint.store import latest_step, list_steps
from repro.launch.train import TrainLoop, run_with_auto_resume
from repro.optim import AdamWConfig
from repro.runtime import FailureInjector, StragglerMonitor
from repro.runtime.elastic import elastic_remesh_plan, tc_remesh_plan
from repro.runtime.fault import CountInterrupted, SimulatedFailure


def _tree(rng):
    return {
        "a": rng.normal(size=(4, 8)).astype(np.float32),
        "b": {"c": rng.integers(0, 100, (3,)).astype(np.int32),
              "d": rng.normal(size=()).astype(np.float32)},
    }


def test_checkpoint_roundtrip(tmp_path, rng):
    tree = _tree(rng)
    save_checkpoint(tmp_path, 7, tree, extra={"note": "x"})
    restored, step, extra = load_checkpoint(tmp_path, tree)
    assert step == 7 and extra == {"note": "x"}
    np.testing.assert_array_equal(restored["a"], tree["a"])
    np.testing.assert_array_equal(restored["b"]["c"], tree["b"]["c"])


def test_checkpoint_atomic_commit_and_retention(tmp_path, rng):
    mgr = CheckpointManager(tmp_path, keep_last=2)
    tree = _tree(rng)
    for s in (10, 20, 30):
        mgr.save(s, tree)
    assert latest_step(tmp_path) == 30
    steps = sorted(p.name for p in tmp_path.iterdir() if p.name.startswith("step_"))
    assert steps == ["step_00000020", "step_00000030"]  # keep_last=2
    # An uncommitted dir must be invisible.
    bogus = tmp_path / "step_00000099"
    bogus.mkdir()
    (bogus / "manifest.json").write_text("{}")
    assert latest_step(tmp_path) == 30


def test_checkpoint_async(tmp_path, rng):
    mgr = CheckpointManager(tmp_path)
    tree = _tree(rng)
    mgr.save_async(5, tree)
    mgr.wait()
    restored, step, _ = mgr.restore(tree)
    assert step == 5
    np.testing.assert_array_equal(restored["a"], tree["a"])


def test_failure_injection_and_exact_resume(tmp_path):
    """Auto-resume after an injected failure reproduces the uninterrupted
    run exactly (deterministic data + checkpoint restore)."""
    common = dict(smoke=True, global_batch=2, seq=16, ckpt_every=10,
                  opt=AdamWConfig(lr=1e-3, weight_decay=0.0))
    steps = 30
    loop_a = TrainLoop("smollm-135m", ckpt_dir=None, **common)
    loop_a.run(steps, log_every=steps)
    loss_a = loop_a.metrics_log[-1]["loss"]

    loop_b = TrainLoop("smollm-135m", ckpt_dir=str(tmp_path), **common)
    injector = FailureInjector(fail_at_steps=(17,))
    (_, _, _), restarts = run_with_auto_resume(loop_b, steps, injector)
    assert restarts == 1
    loss_b = loop_b.metrics_log[-1]["loss"]
    assert abs(loss_a - loss_b) < 1e-5, (loss_a, loss_b)


def test_injector_raises_once():
    inj = FailureInjector(fail_at_steps=(3,))
    inj.check(2)
    with pytest.raises(SimulatedFailure):
        inj.check(3)
    inj.check(3)  # second visit: no raise (the "node" was replaced)


def test_straggler_monitor_flags_persistent_slowdown():
    mon = StragglerMonitor(alpha=0.2, threshold=2.0, patience=3)
    flagged = [mon.observe(1.0) for _ in range(10)]
    assert not any(flagged)
    flags = [mon.observe(5.0) for _ in range(4)]
    assert flags[-1], "persistent straggler not flagged"
    # Single transient spike does not flag.
    mon2 = StragglerMonitor(patience=3)
    for _ in range(5):
        mon2.observe(1.0)
    assert not mon2.observe(10.0)


def test_elastic_remesh_plans():
    # Lose one pod: 512 -> 271 available keeps model=16, shrinks data.
    plan = elastic_remesh_plan((2, 16, 16), ("pod", "data", "model"), 271, 256)
    assert plan.ok and plan.new_shape[2] == 16
    assert plan.new_device_count <= 271
    # Too few devices to keep TP.
    plan2 = elastic_remesh_plan((2, 16, 16), ("pod", "data", "model"), 8, 256)
    assert not plan2.ok
    # Exact single pod.
    plan3 = elastic_remesh_plan((2, 16, 16), ("pod", "data", "model"), 256, 256)
    assert plan3.ok and plan3.new_device_count == 256


def test_elastic_remesh_unknown_axes_pass_through():
    """Axes outside {pod, data, model} keep their extent instead of raising
    (the historical KeyError on e.g. TC's (rows, cols) meshes)."""
    plan = elastic_remesh_plan((4, 2), ("rows", "cols"), 8, 8)
    assert plan.ok and plan.new_shape == (4, 2)
    # Pass-through axes that alone exceed the surviving fleet are flagged
    # infeasible, not silently oversubscribed.
    plan2 = elastic_remesh_plan((4, 2), ("rows", "cols"), 6, 8)
    assert not plan2.ok
    assert any("pass-through" in r for r in plan2.reasons)


def test_tc_remesh_plan_shrinks_toward_old_grid():
    # Lose 2 of 8: (4, 2) -> (3, 2) keeps the column extent.
    plan = tc_remesh_plan((4, 2), 6)
    assert plan.ok and plan.new_shape == (3, 2) and plan.new_device_count == 6
    # 1-D mesh stays 1-D: (1, 4) -> (1, 3).
    assert tc_remesh_plan((1, 4), 3).new_shape == (1, 3)
    # Nothing lost: identity.
    assert tc_remesh_plan((4, 2), 8).new_shape == (4, 2)
    # Awkward survivor counts still use every device (prime -> 1-D).
    plan7 = tc_remesh_plan((4, 2), 7)
    assert plan7.ok and plan7.new_device_count == 7
    assert tc_remesh_plan((4, 2), 0).ok is False


def test_count_interrupted_carries_cursor_context():
    err = CountInterrupted(
        "boom", failed_step=11, committed_step=8, committed_total=42,
        shard_cursors=(3, 5), reason="failure", attempt=1,
    )
    assert isinstance(err, RuntimeError)
    assert err.steps_replayed == 3
    assert err.shard_cursors == (3, 5) and err.committed_total == 42
    # Replay never goes negative (straggler commits through the flagged step).
    flagged = CountInterrupted("slow", failed_step=4, committed_step=4,
                               reason="straggler")
    assert flagged.steps_replayed == 0


def test_checkpoint_bfloat16_roundtrip(tmp_path):
    import ml_dtypes

    tree = {
        "bf16": np.arange(24, dtype=np.float32).reshape(4, 6).astype(
            ml_dtypes.bfloat16),
        "f8": np.linspace(-2, 2, 8, dtype=np.float32).astype(
            ml_dtypes.float8_e4m3fn),
        "plain": np.arange(5, dtype=np.int64),
    }
    save_checkpoint(tmp_path, 3, tree)
    restored, step, _ = load_checkpoint(tmp_path, tree)
    assert step == 3
    assert restored["bf16"].dtype == ml_dtypes.bfloat16
    assert restored["f8"].dtype == ml_dtypes.float8_e4m3fn
    np.testing.assert_array_equal(
        np.asarray(restored["bf16"], dtype=np.float32),
        np.asarray(tree["bf16"], dtype=np.float32))
    np.testing.assert_array_equal(
        restored["f8"].view(np.uint8), tree["f8"].view(np.uint8))


def test_crash_mid_save_tmp_dir_invisible_and_collected(tmp_path, rng):
    """A writer that died mid-save leaves .tmp_step_*; it must be invisible
    to discovery and swept by the next manager save."""
    mgr = CheckpointManager(tmp_path, keep_last=2)
    tree = _tree(rng)
    mgr.save(5, tree)
    # Simulate the crash: a staging dir with a manifest but no sentinel.
    wreck = tmp_path / ".tmp_step_00000009"
    wreck.mkdir()
    (wreck / "manifest.json").write_text("{}")
    (wreck / "leaf_00000.npy").write_bytes(b"partial")
    assert latest_step(tmp_path) == 5
    assert list_steps(tmp_path) == [5]
    with pytest.raises(FileNotFoundError):
        load_checkpoint(tmp_path, tree, step=9)
    mgr.save(6, tree)
    assert not wreck.exists(), "stale staging dir survived GC"
    assert list_steps(tmp_path) == [5, 6]


def test_async_writer_failure_surfaces_on_wait(tmp_path, rng):
    mgr = CheckpointManager(tmp_path)
    # Make the staging dir creation fail: occupy the .tmp path with a file.
    blocker = tmp_path / ".tmp_step_00000004"
    blocker.write_text("not a directory")
    mgr.save_async(4, _tree(rng))
    with pytest.raises(RuntimeError, match="async checkpoint write"):
        mgr.wait()
    # The error is consumed: the manager is reusable afterwards.
    blocker.unlink()
    mgr.save_async(4, _tree(rng))
    mgr.wait()
    assert latest_step(tmp_path) == 4


def test_restore_with_shardings_onto_mesh(tmp_path, rng):
    """shardings= reshards restored leaves onto a caller mesh whose shape
    differs from whatever wrote the checkpoint (here: host arrays ->
    2-axis device mesh). The real multi-device shrink restore is covered
    by tests/test_resilient.py on forced devices."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    tree = _tree(rng)
    save_checkpoint(tmp_path, 1, tree)
    mesh = Mesh(
        np.asarray(jax.devices()[:1], dtype=object).reshape(1, 1),
        ("rows", "cols"),
    )
    shardings = jax.tree.map(lambda _: NamedSharding(mesh, P()), tree)
    restored, _, _ = load_checkpoint(tmp_path, tree, shardings=shardings)
    leaf = restored["a"]
    assert isinstance(leaf, jax.Array)
    assert leaf.sharding.mesh.shape == {"rows": 1, "cols": 1}
    np.testing.assert_array_equal(np.asarray(leaf), tree["a"])


def test_compile_cache_dir_env_wins_else_checkout(monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR, when set, is the cache and no other
    directory is configured; otherwise the cache is the fixed directory in
    the checkout. Thresholds drop so sub-second kernel compiles cache."""
    import jax

    from repro.runtime.compile_cache import CHECKOUT_CACHE_DIR, enable_compile_cache

    names = (
        "jax_compilation_cache_dir",
        "jax_enable_compilation_cache",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes",
    )
    saved = {n: getattr(jax.config, n) for n in names}
    try:
        jax.config.update("jax_compilation_cache_dir", None)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir is None
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert enable_compile_cache() == str(CHECKOUT_CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == str(CHECKOUT_CACHE_DIR)
        assert CHECKOUT_CACHE_DIR.parent == Path(__file__).resolve().parents[1]
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
    finally:
        for n, v in saved.items():
            jax.config.update(n, v)
