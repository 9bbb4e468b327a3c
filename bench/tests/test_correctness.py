"""``correct`` on the CPU, with the chip check skipped: a sound run passes,
and the control and every planted fault of ``bench/control.py`` fail."""
from __future__ import annotations

import copy
import json

import pytest

from bench import control, harness, run

SEED = 2**31 + 17


@pytest.fixture(autouse=True)
def _no_chip_check(monkeypatch):
    monkeypatch.setattr(harness.Harness, "check_devices", lambda self, d: None)


def _small(cell: str):
    spec, w, config, traffic = run._cell_files(cell)
    config, traffic = copy.deepcopy(config), copy.deepcopy(traffic)
    config["graph"].update(n=3000, m=20000)
    traffic["expect"] = {"build": "host", "execute_impl": "jnp_mirror"}
    return spec, w, config, traffic


def _line(cell: str, capsys, fault: str | None = None) -> dict:
    spec, w, config, traffic = _small(cell)
    kw = dict(seed=SEED, seconds=1.5, trace=False)
    if fault is None:
        assert run.run_cell(spec, w, config, traffic, **kw) == 0
    else:
        with control.planted(fault, traffic["driver"]):
            assert run.run_cell(spec, w, config, traffic, **kw) == 0
    out = capsys.readouterr()
    line = json.loads(out.out.strip().splitlines()[-1])
    assert list(line)[-1] == "checks"
    assert out.err.strip().splitlines()[-1].startswith("check ")
    return line


@pytest.mark.parametrize("cell,fault", [
    ("youtube.oneshot", None),
    ("youtube.oneshot", "control"),
    ("youtube.oneshot", "count_plus_one"),
])
def test_correct_fails_under_every_fault(cell, fault, capsys):
    line = _line(cell, capsys, fault)
    assert line["attempted"] > 0
    if fault is None:
        assert line["correct"] is True and line["failed"] == 0
        assert all(c["value"] <= c["limit"] for c in line["checks"].values())
    else:
        assert line["correct"] is False and line["failed"] > 0
        assert line["checks"]["wrong_counts"]["value"] > 0
