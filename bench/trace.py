"""Reduce a JAX profiler trace to device events and the shares read from
them.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes and keeps
three kinds of event, all in nanoseconds from the start of the trace:

- device operations: the ``XLA Ops`` line of every ``/device:TPU:<n>``
  plane (on the CPU backend, which has no device plane, the host events
  that carry an ``hlo_op`` stat, so the reducer can be rehearsed there);
- device modules: the ``XLA Modules`` line of the same planes (on the CPU,
  one interval per module run, the union of its operations);
- host events: every other event on the host plane, which names what the
  host was doing while the device sat idle.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re

_TPU_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_SUFFIX = re.compile(r"(\(\d+\)|\.\d+)$")


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float  # ns from the start of the trace
    end: float
    device: int = 0
    module: str = ""
    detail: str = ""  # the operation's stats (long name, category), joined

    @property
    def text(self) -> str:
        return f"{self.name} {self.detail}"

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class Trace:
    ops: list
    modules: list
    host: list

    @property
    def devices(self) -> list:
        return sorted({e.device for e in self.ops})


def module_name(raw: str) -> str:
    """``jit_worklist_step(12)`` or ``jit_worklist_step.3`` ->
    ``jit_worklist_step``."""
    name = raw.strip()
    while _SUFFIX.search(name):
        name = _SUFFIX.sub("", name)
    return name


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def _stats(event) -> dict:
    try:
        return {k: v for k, v in event.stats}
    except (TypeError, ValueError):
        return {}


def _op(ev, start: float, end: float, dev: int) -> Event:
    st = _stats(ev)
    return Event(ev.name, start, end, dev,
                 module_name(str(st.get("hlo_module", ""))),
                 " ".join(f"{k}={v}" for k, v in st.items()))


def load(path: str) -> Trace:
    """Read one ``.xplane.pb`` (or the newest one under a directory)."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = find_xplane(path)
    data = ProfileData.from_file(path)
    ops, modules, host, cpu_ops = [], [], [], []
    for plane in data.planes:
        tpu = _TPU_PLANE.match(plane.name)
        for line in plane.lines:
            for ev in line.events:
                start = float(ev.start_ns)
                end = start + float(ev.duration_ns)
                if tpu:
                    dev = int(tpu.group(1))
                    if line.name == "XLA Ops":
                        ops.append(_op(ev, start, end, dev))
                    elif line.name == "XLA Modules":
                        modules.append(Event(module_name(ev.name), start, end, dev))
                elif plane.name.startswith("/host:"):
                    if "hlo_op" in _stats(ev):
                        cpu_ops.append(_op(ev, start, end, 0))
                    else:
                        host.append(Event(ev.name, start, end))
    if not ops and cpu_ops:
        ops = cpu_ops
        modules = _modules_from_ops(cpu_ops)
    _attach_modules(ops, modules)
    return Trace(sorted(ops, key=lambda e: e.start),
                 sorted(modules, key=lambda e: e.start),
                 sorted(host, key=lambda e: e.start))


def _modules_from_ops(ops: list) -> list:
    by = collections.defaultdict(list)
    for e in ops:
        by[(e.device, e.module)].append((e.start, e.end))
    return [Event(mod, s, t, dev) for (dev, mod), iv in by.items()
            for s, t in union(iv)]


def _attach_modules(ops: list, modules: list) -> None:
    """Give each operation without an ``hlo_module`` stat the module whose
    run on its device contains it."""
    by_dev = collections.defaultdict(list)
    for m in modules:
        by_dev[m.device].append(m)
    for dev in by_dev:
        by_dev[dev].sort(key=lambda e: e.start)
    for i, op in enumerate(ops):
        if op.module:
            continue
        for m in by_dev.get(op.device, ()):
            if m.start <= op.start and op.end <= m.end:
                ops[i] = dataclasses.replace(op, module=m.name)
                break


def union(intervals) -> list:
    """Merge (start, end) intervals; returns disjoint sorted intervals."""
    out: list = []
    for s, t in sorted(intervals):
        if out and s <= out[-1][1]:
            if t > out[-1][1]:
                out[-1][1] = t
        else:
            out.append([s, t])
    return [(s, t) for s, t in out]


def clip(intervals, lo: float, hi: float) -> list:
    return [(max(s, lo), min(t, hi)) for s, t in intervals if t > lo and s < hi]


def busy_ns(trace: Trace, window_ns: float, device: int | None = None) -> float:
    """Time in which some operation ran on the device, averaged over the
    devices that ran any (or on one device), within [0, window_ns]."""
    devs = [device] if device is not None else trace.devices
    if not devs:
        return 0.0
    total = 0.0
    for d in devs:
        iv = clip(union((e.start, e.end) for e in trace.ops if e.device == d),
                  0.0, window_ns)
        total += sum(t - s for s, t in iv)
    return total / len(devs)


def matching(events, pattern: str, *, field: str = "name") -> list:
    rx = re.compile(pattern)
    return [e for e in events if rx.search(getattr(e, field))]


def time_ns(events, window_ns: float) -> float:
    """Summed durations of the events (clipped to the window) on their
    devices, as the union per device so nested events count once."""
    by = collections.defaultdict(list)
    for e in events:
        by[e.device].append((e.start, e.end))
    return sum(t - s for iv in by.values()
               for s, t in clip(union(iv), 0.0, window_ns))


def top_ops(trace: Trace, window_ns: float, k: int = 10) -> list:
    """The operations that took most device time: [[module:op, seconds]]."""
    tot = collections.Counter()
    for e in trace.ops:
        s, t = max(e.start, 0.0), min(e.end, window_ns)
        if t > s:
            tot[f"{e.module}:{e.name}" if e.module else e.name] += t - s
    return [[name, ns / 1e9] for name, ns in tot.most_common(k)]


def idle_gaps(trace: Trace, window_ns: float, k: int = 10) -> list:
    """The longest idle gaps of the first device, each named by the host
    event that covers most of it (the shortest such event, so a span that
    encloses the whole run does not name every gap)."""
    devs = trace.devices
    busy = clip(union((e.start, e.end) for e in trace.ops
                      if not devs or e.device == devs[0]), 0.0, window_ns)
    gaps, prev = [], 0.0
    for s, t in busy + [(window_ns, window_ns)]:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, t)
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for s, t in gaps[:k]:
        best, best_key = "host idle", None
        for h in trace.host:
            if h.start >= t:
                break
            cover = min(h.end, t) - max(h.start, s)
            if cover <= 0.5 * (t - s):
                continue
            if best_key is None or h.dur < best_key:
                best, best_key = h.name, h.dur
        out.append([best, (t - s) / 1e9])
    return out
