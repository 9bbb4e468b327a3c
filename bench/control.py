"""The correctness control and the planted fault.

    python3 -m bench.control --workload <cell> --seed <n> --seconds <s> [--fault <name>]

Runs one cell as ``bench.run`` does, with the timed path replaced or
broken underneath, and prints the same result line; ``correct`` has to
come out false. The benchmark's own runs never import this module.

- ``control``: the guarantee the configuration states is an exact count,
  so the control counts approximately, the shortcut a later change might
  take: the reference counter on a sample of the edges (each kept with
  probability ``SAMPLE``, the count scaled by ``1 / SAMPLE**3``, DOULION).
- ``count_plus_one``: every answer altered by one where it is produced.
"""
from __future__ import annotations

import contextlib
import dataclasses
import sys

import numpy as np

from bench import graphs

SAMPLE = 0.99
FAULTS = ("control", "count_plus_one")


@contextlib.contextmanager
def planted(fault: str, driver: str):
    """Break the timed path of ``driver`` with ``fault`` while inside."""
    if fault not in FAULTS:
        raise ValueError(f"fault {fault!r} not in {FAULTS}")
    if driver != "closed_oneshot":
        raise ValueError(f"no planted faults for driver {driver!r}")
    import repro.core as core

    real = core.tcim_count
    first: list = []

    def count(edges, n=None, **kw):
        if fault == "count_plus_one" or not first:
            # The control's first call (the warm-up) runs the program,
            # whose stats every later answer then carries.
            res = real(edges, n=n, **kw)
            first.append(res)
            if fault == "count_plus_one":
                return dataclasses.replace(res, triangles=res.triangles + 1)
            return res
        keep = np.random.default_rng(len(edges)).random(len(edges)) < SAMPLE
        approx = graphs.triangles(edges[keep], n) / SAMPLE ** 3
        return dataclasses.replace(first[0], triangles=int(round(approx)))

    core.tcim_count = count
    try:
        yield
    finally:
        core.tcim_count = real


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
