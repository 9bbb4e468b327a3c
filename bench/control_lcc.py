"""The correctness control and the planted fault of the per-vertex cell.

    python3 -m bench.control_lcc --workload <cell> --seed <n> --seconds <s> [--fault <name>]

Runs one cell of the ``closed_lcc`` driver as ``bench.run`` does, with the
timed path replaced or broken underneath, and prints the same result line;
``correct`` has to come out false. The benchmark's own runs never import
this module.

- ``control``: the configuration states every T(v) exact, so the control
  estimates them, the shortcut a later change might take: the reference
  on a sample of the edges (each kept with probability ``SAMPLE``), every
  count scaled by ``1 / SAMPLE**3`` (DOULION, per vertex), the LCC
  computed from those estimates.
- ``vertex_plus_one``: one vertex's T altered by one where it is produced.
"""
from __future__ import annotations

import contextlib
import dataclasses
import sys

import numpy as np

from bench import lcc_ref

SAMPLE = 0.99
FAULTS = ("control", "vertex_plus_one")


@contextlib.contextmanager
def planted(fault: str, driver: str):
    """Break the timed path of ``driver`` with ``fault`` while inside."""
    if fault not in FAULTS:
        raise ValueError(f"fault {fault!r} not in {FAULTS}")
    if driver != "closed_lcc":
        raise ValueError(f"no planted faults for driver {driver!r}")
    import repro.core as core

    real = core.tcim_vertex_counts
    first: list = []

    def vertex_counts(edges, n=None, **kw):
        if fault == "vertex_plus_one" or not first:
            # The control's first call (the warm-up) runs the program,
            # whose stats every later answer then carries.
            res = real(edges, n=n, **kw)
            first.append(res)
            if fault == "vertex_plus_one":
                t = res.vertex_triangles.copy()
                t[int(np.argmax(t))] += 1
                return dataclasses.replace(res, vertex_triangles=t)
            return res
        keep = np.random.default_rng(len(edges)).random(len(edges)) < SAMPLE
        t = np.rint(lcc_ref.vertex_triangles(edges[keep], n) / SAMPLE ** 3)
        t = t.astype(np.int64)
        return dataclasses.replace(
            first[0], triangles=int(t.sum()) // 3, vertex_triangles=t,
            lcc=lcc_ref.local_clustering(edges, n, t))

    core.tcim_vertex_counts = vertex_counts
    try:
        yield
    finally:
        core.tcim_vertex_counts = real


def main(argv=None) -> int:
    import argparse

    from bench import run

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--fault", choices=FAULTS, default="control")
    args = ap.parse_args(argv)
    spec, cell, config, traffic = run._cell_files(args.workload)
    sys.path.insert(0, str(run.ROOT / "src"))
    with planted(args.fault, traffic["driver"]):
        return run.run_cell(spec, cell, config, traffic, seed=args.seed,
                            seconds=args.seconds, trace=False)


if __name__ == "__main__":
    sys.exit(main())
