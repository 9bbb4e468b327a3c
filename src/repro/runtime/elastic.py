"""Elastic re-meshing: resume a triangle count on a different mesh.

Checkpoints are stored unsharded-logical (host numpy per leaf), so a count
that lost devices resumes on whatever mesh the survivors form: the work
list is re-dealt (`shard_worklist`) over the new device count, and since
the reduction is a commutative monoid any re-partition of pair stripes is
exact. ``tc_remesh_plan`` picks the new ``(rows, cols)`` owner grid.
"""
from __future__ import annotations

import dataclasses

__all__ = ["tc_remesh_plan", "RemeshPlan"]


@dataclasses.dataclass(frozen=True)
class RemeshPlan:
    old_shape: tuple[int, ...]
    new_shape: tuple[int, ...]
    axis_names: tuple[str, ...]
    ok: bool
    reasons: tuple[str, ...]

    @property
    def new_device_count(self) -> int:
        out = 1
        for s in self.new_shape:
            out *= s
        return out


def tc_remesh_plan(
    grid: tuple[int, int],
    available_devices: int,
    axis_names: tuple[str, str] = ("rows", "cols"),
) -> RemeshPlan:
    """Shrink a TC ``(rows, cols)`` owner grid onto the surviving devices.

    The grid has no divisibility constraints — the reduction is a
    commutative monoid over pair stripes, so ANY ``r x c`` factorization is
    exact after a re-deal. Pick the factorization
    using the most surviving devices, tie-broken toward the old aspect
    (fewest store blocks move on restore): ``(4, 2)`` with 6 survivors
    becomes ``(3, 2)``; ``(1, 4)`` with 3 becomes ``(1, 3)``.
    """
    rows, cols = int(grid[0]), int(grid[1])
    old = (rows, cols)
    if available_devices < 1:
        return RemeshPlan(
            old, old, tuple(axis_names), False, ("no surviving devices",)
        )
    best_key, best = None, old
    for c in range(1, available_devices + 1):
        r = available_devices // c
        key = (r * c, -abs(c - cols), -abs(r - rows))
        if best_key is None or key > best_key:
            best_key, best = key, (r, c)
    reasons = (
        ()
        if best == old
        else (
            f"grid {rows}x{cols} -> {best[0]}x{best[1]} "
            f"({available_devices} surviving devices)",
        )
    )
    return RemeshPlan(old, best, tuple(axis_names), True, reasons)
