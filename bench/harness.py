"""What every driver shares: the cell's files, the device, the measured
window and its trace, child processes, and the result line.

A driver module under ``bench/drivers/`` exposes ``run(h: Harness) ->
Outcome``. It prepares its inputs, warms up every shape it will use, calls
``h.open_window()``, drives the system for ``h.seconds``, calls
``h.close_window()`` once the work of the window has finished, and then
compares every answer with the reference.
"""
from __future__ import annotations

import collections
import dataclasses
import json
import multiprocessing
import os
import shutil
import tempfile
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


class NoDevice(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Check:
    """One number compared with the reference, beside its limit."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


@dataclasses.dataclass
class Outcome:
    attempted: int
    failed: int
    checks: list
    metrics: dict  # end-to-end metric name -> value, from the host clock
    # For the per-layer readers: graphs counted in the traced window and
    # the valid slice pairs of each.
    graphs: int = 0
    pairs: list = dataclasses.field(default_factory=list)
    notes: dict = dataclasses.field(default_factory=dict)

    @property
    def correct(self) -> bool:
        return all(c.ok for c in self.checks)


def _child_init() -> None:
    """Child processes never take the chip: JAX, should anything import it
    there, runs on the CPU."""
    os.environ["JAX_PLATFORMS"] = "cpu"


class Harness:
    def __init__(self, *, cell: dict, config: dict, traffic: dict, seed: int,
                 seconds: float, trace: bool, t_start: float):
        self.cell = cell
        self.config = config
        self.traffic = traffic
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.t_start = t_start
        self.t_open: float | None = None
        self.t_close: float | None = None
        self.compiles = collections.Counter()
        self._trace_dir: str | None = None
        self._pools: list = []

    # ------------------------------------------------------------ device
    def start_jax(self):
        """Import JAX with the persistent compilation cache in the
        checkout, and check the chips; raises ``NoDevice``."""
        import jax

        if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
            jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
        jax.config.update("jax_enable_compilation_cache", True)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        try:
            devices = jax.devices()
        except RuntimeError as e:
            raise NoDevice(f"no accelerator: {e}") from e
        self.check_devices(devices)
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        self.devices = devices
        return jax

    def check_devices(self, devices) -> None:
        if devices[0].platform != "tpu":
            raise NoDevice(f"needs a TPU, found {devices[0].platform}")
        if len(devices) < int(self.cell["chips"]):
            raise NoDevice(f"cell needs {self.cell['chips']} chips, "
                           f"found {len(devices)}")

    def _event(self, name, **_):
        if name.startswith("/jax/compilation_cache/cache_"):
            self.compiles[("window_" if self._in_window() else "") +
                          name.rsplit("/", 1)[-1]] += 1

    def _duration(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            key = "window_compiles" if self._in_window() else "setup_compiles"
            self.compiles[key] += 1

    def _in_window(self) -> bool:
        return self.t_open is not None and self.t_close is None

    def memory_peak(self) -> int:
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in self.devices[: int(self.cell["chips"])]]
        return int(max(peaks))

    # ------------------------------------------------------------ window
    def open_window(self) -> float:
        if self.trace:
            import jax

            self._trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(self._trace_dir, profiler_options=opts)
        self.t_open = time.perf_counter()
        return self.t_open

    def close_window(self) -> float:
        self.t_close = time.perf_counter()
        if self.trace:
            import jax

            jax.profiler.stop_trace()
        return self.t_close

    @property
    def setup_s(self) -> float:
        return self.t_open - self.t_start

    @property
    def window_s(self) -> float:
        return self.t_close - self.t_open

    def load_trace(self):
        from bench import trace as trace_mod

        try:
            return trace_mod.load(self._trace_dir)
        finally:
            shutil.rmtree(self._trace_dir, ignore_errors=True)

    # ------------------------------------------------------- processes
    def pool(self, processes: int):
        """A pool of spawned processes that stay off the chip."""
        ctx = multiprocessing.get_context("spawn")
        p = ctx.Pool(max(1, int(processes)), initializer=_child_init)
        self._pools.append(p)
        return p

    def close(self) -> None:
        for p in self._pools:
            p.terminate()
            p.join()
        self._pools.clear()


def load_json(path: Path) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)
