"""Fixture tests for the tclint rules (TCL001-TCL006).

Each rule gets a bad fixture (must fire) and a good fixture (must stay
quiet), plus pragma-suppression, baseline round-trip, and a
repo-stays-clean gate that mirrors the CI lint job.
"""

from __future__ import annotations

import sys
import textwrap
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from tools.tclint import (  # noqa: E402
    Config,
    load_baseline,
    lint_source,
    run_lint,
    save_baseline,
)

# Fixtures are linted under an execute-path module name so the scoped rules
# apply; NOT one of the sanctioned transfer modules, so TCL002 fires too.
EXEC_PATH = "repro/core/streaming.py"


def lint(src: str, path: str = EXEC_PATH):
    violations, suppressed = lint_source(textwrap.dedent(src), path)
    return [v.rule for v in violations], suppressed


# ---------------------------------------------------------------- TCL001


def test_tcl001_fires_on_scalarized_device_value():
    rules, _ = lint(
        """
        import jax.numpy as jnp

        def f(wl):
            total = jnp.sum(wl)
            return int(total)
        """
    )
    assert rules == ["TCL001"]


def test_tcl001_fires_on_np_asarray_of_device_store():
    rules, _ = lint(
        """
        import numpy as np

        def f(self):
            return np.asarray(self.row_slice_data)
        """
    )
    assert rules == ["TCL001"]


def test_tcl001_quiet_on_host_values_and_shape_metadata():
    rules, _ = lint(
        """
        import numpy as np
        import jax.numpy as jnp

        def f(self, xs):
            n = int(np.sum(xs))            # numpy is host data
            k = int(self.row_data.shape[0])  # shape metadata has no readback
            total = jnp.sum(jnp.asarray(xs))
            return n + k, total            # device value returned, not synced
        """
    )
    assert rules == []


def test_tcl001_quiet_outside_execute_modules():
    rules, _ = lint(
        """
        import jax.numpy as jnp

        def f(x):
            return int(jnp.sum(x))
        """,
        path="repro/core/metrics.py",
    )
    assert rules == []


# ---------------------------------------------------------------- TCL002


def test_tcl002_fires_on_device_put_outside_staging_modules():
    rules, _ = lint(
        """
        import jax

        def stage(x):
            return jax.device_put(x)
        """,
        path="repro/launch/tc_serve.py",
    )
    assert "TCL002" in rules


def test_tcl002_quiet_in_sanctioned_build_module():
    rules, _ = lint(
        """
        import jax

        def stage(x):
            return jax.device_put(x)
        """,
        path="repro/core/build.py",
    )
    assert "TCL002" not in rules


# ---------------------------------------------------------------- TCL003


def test_tcl003_fires_on_eager_variable_slice_of_device_value():
    rules, _ = lint(
        """
        import jax.numpy as jnp

        def window(store, hi):
            data = jnp.asarray(store)
            return data[:hi]
        """
    )
    assert rules == ["TCL003"]


def test_tcl003_quiet_inside_jit_and_on_const_bounds():
    rules, _ = lint(
        """
        import jax
        import jax.numpy as jnp

        def _side(store, hi):
            data = jnp.asarray(store)
            return data[:hi]          # static during tracing

        step = jax.jit(_side)

        def eager(store):
            data = jnp.asarray(store)
            return data[:-1]          # -1 is a parse-time constant
        """
    )
    assert rules == []


def test_tcl003_fires_on_non_pow2_literal_shape():
    rules, _ = lint(
        """
        import jax.numpy as jnp

        def pad():
            return jnp.zeros((13, 64), jnp.uint32)
        """
    )
    assert rules == ["TCL003"]


# ---------------------------------------------------------------- TCL004


def test_tcl004_fires_on_unguarded_quantity_product():
    rules, _ = lint(
        """
        def budget(num_pairs, words_per_slice):
            return num_pairs * words_per_slice * 32
        """
    )
    assert "TCL004" in rules


def test_tcl004_quiet_when_guard_in_scope():
    rules, _ = lint(
        """
        from repro.kernels.ops import INT32_SAFE_WORDS

        def budget(num_pairs, words_per_slice):
            assert num_pairs * words_per_slice <= INT32_SAFE_WORDS
            return num_pairs * words_per_slice * 32
        """
    )
    assert rules == []


# ---------------------------------------------------------------- TCL005


def test_tcl005_fires_on_reuse_after_donation():
    rules, _ = lint(
        """
        import jax

        step = jax.jit(_step, donate_argnums=(1,))

        def run(wl, acc):
            out = step(wl, acc)
            return out + acc.sum()
        """
    )
    assert rules == ["TCL005"]


def test_tcl005_quiet_on_rebind_idiom():
    rules, _ = lint(
        """
        import jax

        step = jax.jit(_step, donate_argnums=(1,))

        def run(wl, acc):
            acc = step(wl, acc)
            return acc
        """
    )
    assert rules == []


# ---------------------------------------------------------------- TCL006


@pytest.fixture()
def export_tree(tmp_path):
    pkg = tmp_path / "src" / "repro"
    pkg.mkdir(parents=True)
    (pkg / "__init__.py").write_text("")
    tests_dir = tmp_path / "tests"
    tests_dir.mkdir()
    return tmp_path, pkg, tests_dir


def test_tcl006_fires_on_dead_export_and_honors_liveness(export_tree):
    root, pkg, tests_dir = export_tree
    (pkg / "mod.py").write_text(
        textwrap.dedent(
            """
            class Result:
                pass

            def count():
                return Result()

            def orphan():
                return None
            """
        )
    )
    (tests_dir / "test_mod.py").write_text(
        "from repro.mod import count\n"
    )
    result = run_lint(["src"], root=root, config=Config())
    dead = [v for v in result.violations if v.rule == "TCL006"]
    # `count` is used, `Result` is alive through `count`, `orphan` is dead.
    assert [v.message.split("'")[1] for v in dead] == ["orphan"]


def test_tcl006_pure_reexport_init_is_not_a_use(export_tree):
    root, pkg, _ = export_tree
    sub = pkg / "sub"
    sub.mkdir()
    (sub / "__init__.py").write_text("from repro.sub.mod import helper\n")
    (sub / "mod.py").write_text("def helper():\n    return 1\n")
    result = run_lint(["src"], root=root, config=Config())
    assert [v.rule for v in result.violations] == ["TCL006"]


# ------------------------------------------------------- pragmas, baseline


def test_pragma_suppresses_with_reason_only():
    src = """
        import jax.numpy as jnp

        def f(wl):
            total = jnp.sum(wl)
            return int(total)  # tclint: sync-ok(fixture close)
    """
    rules, suppressed = lint(src)
    assert rules == [] and suppressed == 1
    # An empty reason is not a pragma.
    rules, suppressed = lint(src.replace("(fixture close)", "()"))
    assert rules == ["TCL001"] and suppressed == 0


def test_pragma_on_line_above_suppresses():
    rules, suppressed = lint(
        """
        import jax.numpy as jnp

        def f(wl):
            total = jnp.sum(wl)
            # tclint: sync-ok(fixture close)
            return int(total)
        """
    )
    assert rules == [] and suppressed == 1


def test_baseline_round_trip_and_stale_reporting(tmp_path):
    src = textwrap.dedent(
        """
        import jax.numpy as jnp

        def f(wl):
            return int(jnp.sum(wl))
        """
    )
    f = tmp_path / "repro" / "core" / "streaming.py"
    f.parent.mkdir(parents=True)
    f.write_text(src)
    first = run_lint([str(f)], root=tmp_path, dead_exports=False)
    assert [v.rule for v in first.violations] == ["TCL001"]

    bl = tmp_path / "baseline.json"
    save_baseline(bl, [v.fingerprint for v in first.violations])
    entries = load_baseline(bl)
    second = run_lint(
        [str(f)], root=tmp_path, baseline=entries, dead_exports=False
    )
    assert second.ok and len(second.baselined) == 1

    # Fix the code: the entry goes stale and is reported for removal.
    f.write_text(src.replace("int(jnp.sum(wl))", "jnp.sum(wl)"))
    third = run_lint(
        [str(f)], root=tmp_path, baseline=entries, dead_exports=False
    )
    assert third.ok and third.stale_baseline == sorted(entries)


# ------------------------------------------------------------- repo gate


def test_repo_is_clean_against_empty_baseline():
    baseline = load_baseline(REPO / "tools" / "tclint" / "baseline.json")
    assert baseline == set(), "baseline must stay empty: pragma new exceptions"
    result = run_lint(["src"], root=REPO, baseline=baseline)
    assert result.ok, "\n".join(
        f"{v.path}:{v.line}: {v.rule} {v.message}" for v in result.violations
    )
