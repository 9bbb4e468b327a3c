"""Fault-tolerance runtime pieces: failure injection + straggler detection.

At 1000+ nodes the mean time between hardware failures is minutes-to-hours;
the design here is checkpoint/restart (the only strategy that composes with
XLA SPMD's gang-scheduled execution) plus:

  * ``FailureInjector`` — deterministic chaos-monkey for tests/examples:
    raises SimulatedFailure at configured steps; the resilient count's
    restart path (examples/fault_tolerant_tc.py, tests/test_resilient.py)
    proves an exact resume from the last committed checkpoint.
  * ``StragglerMonitor`` — EWMA step-time tracker. On real pods, persistent
    stragglers (failing HBM, thermal throttling) show up as a stable
    multiplicative slowdown of the whole gang; the monitor flags them and
    the driver's policy is to checkpoint + evict + re-mesh (see
    elastic.py), which is how production fleets handle it. TC workloads
    additionally over-decompose the work list (4x blocks per device) so a
    re-deal rebalances without recompute.
"""
from __future__ import annotations

import dataclasses
import time

__all__ = [
    "SimulatedFailure",
    "CountInterrupted",
    "FailureInjector",
    "StragglerMonitor",
]


class SimulatedFailure(RuntimeError):
    """Injected node failure (tests / examples)."""


class CountInterrupted(RuntimeError):
    """A sharded count died mid-flight — with everything needed to resume.

    Raised by the resumable execute drivers (``distributed.tc
    ._StripeScheduleDriver.count_plan_resumable`` and
    ``core.executor.CountFuture``) instead of a bare exception: the count's
    reduction is a commutative integer monoid over disjoint pair stripes, so
    the *committed* prefix is exact and only the pairs past the committed
    cursor need re-execution — on the same mesh or (via
    ``distributed.resilient``) a shrunk one.

    Attributes:
        failed_step:     psum step index the failure surfaced at.
        committed_step:  last step whose total + cursor were committed.
        committed_total: exact partial count through ``committed_step``
                         (includes any ``base_total`` carried into the run).
        shard_cursors:   per-shard consumed-pair offsets at the committed
                         step (``StripeSchedule.cursor_after``), or ``None``
                         when the interrupted path tracked no schedule.
        reason:          ``"failure"`` (exception at dispatch/readback) or
                         ``"straggler"`` (StragglerMonitor flag).
        attempt:         the resilient driver's attempt number (0 = first).
    """

    def __init__(
        self,
        message: str,
        *,
        failed_step: int,
        committed_step: int = 0,
        committed_total: int = 0,
        shard_cursors: tuple[int, ...] | None = None,
        reason: str = "failure",
        attempt: int = 0,
    ):
        super().__init__(message)
        self.failed_step = int(failed_step)
        self.committed_step = int(committed_step)
        self.committed_total = int(committed_total)
        self.shard_cursors = (
            tuple(int(c) for c in shard_cursors)
            if shard_cursors is not None
            else None
        )
        self.reason = reason
        self.attempt = int(attempt)

    @property
    def steps_replayed(self) -> int:
        """Steps past the committed cursor a resume re-executes (<= the
        driver's ``checkpoint_every``)."""
        return max(self.failed_step - self.committed_step, 0)


@dataclasses.dataclass
class FailureInjector:
    """Raise SimulatedFailure at configured steps (each at most ``repeats``
    times, default once — the classic transient fault).

    ``fail_at_steps`` arms specific step indices; ``fail_every`` arms every
    positive multiple of a period on top (the serving soak's "one injected
    failure per wave"). ``repeats > 1`` makes an armed step keep firing on
    re-checks — how a *hard* failure that survives bounded retries is
    modeled (the serving layer re-checks the same request id per attempt).
    """

    fail_at_steps: tuple[int, ...] = ()
    fail_every: int = 0
    repeats: int = 1

    def __post_init__(self):
        self._fired: dict[int, int] = {}

    @property
    def failures(self) -> int:
        """Total injected failures so far."""
        return sum(self._fired.values())

    def check(self, step: int):
        armed = step in self.fail_at_steps or (
            self.fail_every > 0 and step > 0 and step % self.fail_every == 0
        )
        if armed and self._fired.get(step, 0) < self.repeats:
            self._fired[step] = self._fired.get(step, 0) + 1
            raise SimulatedFailure(f"injected failure at step {step}")


class StragglerMonitor:
    """EWMA step-time outlier detection.

    flag() returns True when the last step exceeded ``threshold`` x the EWMA
    for ``patience`` consecutive steps — the signature of a persistent
    straggler rather than a transient (GC pause, incast).
    """

    def __init__(self, alpha: float = 0.1, threshold: float = 2.0, patience: int = 3):
        self.alpha = alpha
        self.threshold = threshold
        self.patience = patience
        self.ewma: float | None = None
        self._strikes = 0
        self.history: list[float] = []
        self._t0: float | None = None

    def reset(self):
        """Forget history — e.g. after an elastic remesh, whose new gang has
        a different per-step baseline that must not inherit stale strikes."""
        self.ewma = None
        self._strikes = 0
        self.history = []
        self._t0 = None

    def start_step(self):
        self._t0 = time.perf_counter()

    def end_step(self) -> bool:
        assert self._t0 is not None, "start_step() not called"
        dt = time.perf_counter() - self._t0
        return self.observe(dt)

    def observe(self, dt: float) -> bool:
        """Record a step time; returns True if a straggler is flagged."""
        self.history.append(dt)
        if self.ewma is None:
            self.ewma = dt
            return False
        flagged = dt > self.threshold * self.ewma
        self._strikes = self._strikes + 1 if flagged else 0
        # Slow steps polute the EWMA less (winsorised update).
        self.ewma = (1 - self.alpha) * self.ewma + self.alpha * min(
            dt, self.threshold * self.ewma
        )
        return self._strikes >= self.patience
