"""Run one cell of the benchmark once and print its result line.

    python3 -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration and its traffic are found by name from
``BENCHMARK.json`` at the root of the checkout: the configuration's file,
``bench/traffic/<traffic>.json`` (which names its driver under
``bench/drivers/``), and, in a traced run, ``bench/metrics/<metric>.json``
for each per-layer metric of the cell (which names its reader under
``bench/readers/``). Adding a cell, a configuration or a metric adds files
and entries; no file here changes.

With ``--trace 0`` the result's metrics are the cell's end-to-end metrics;
with ``--trace 1`` they are its per-layer metrics, read from the device
trace of the window, and the line also carries ``breakdown``. The last line
of standard output is the result as one JSON object; the numbers compared
with the reference, each beside its limit, are the last lines of standard
error and the result's last key. Without a TPU, or with fewer chips than
the cell asks for, the run exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

from bench.harness import BENCH, ROOT, Harness, NoDevice, load_json  # noqa: E402


def _cell_files(name: str):
    spec = load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    cell = cells[name]
    config = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    return (spec, cell, load_json(ROOT / config["file"]),
            load_json(BENCH / "traffic" / f"{cell['traffic']}.json"))


def end_to_end(spec: dict, cell: str) -> list:
    """The end-to-end metrics a cell reports."""
    return [m for m in spec["end_to_end"]
            if "workloads" not in m or cell in m["workloads"]]


def per_layer_metrics(spec: dict, cell: str) -> list:
    """The per-layer metrics a cell reports: those whose ``workloads``
    list it."""
    return [m for m in spec["per_layer"] if cell in m["workloads"]]


def per_layer(spec: dict, cell: dict, h: Harness, out, trace) -> dict:
    from bench.readers import ReadContext

    peaks = load_json(BENCH / "peaks.json")["devices"]
    kind = h.devices[0].device_kind
    if kind not in peaks:
        raise KeyError(f"device kind {kind!r} has no entry in bench/peaks.json")
    metrics = {}
    for m in per_layer_metrics(spec, cell["name"]):
        mspec = load_json(BENCH / "metrics" / f"{m['name']}.json")
        reader = importlib.import_module(f"bench.readers.{mspec['reader']}")
        ctx = ReadContext(trace=trace, window_ns=h.window_s * 1e9, outcome=out,
                          peaks=peaks[kind],
                          slice_bits=int(h.config.get("slice_bits", 64)))
        value = reader.read(ctx, **mspec.get("args", {}))
        if ctx.notes:
            out.notes.setdefault("readers", {})[m["name"]] = ctx.notes
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    return metrics


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    spec, cell, config, traffic = _cell_files(args.workload)
    src = ROOT / "src"
    if not (src / "repro").is_dir():
        print(f"bench: no program under {src}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(src))
    return run_cell(spec, cell, config, traffic, seed=args.seed,
                    seconds=args.seconds, trace=bool(args.trace))


def run_cell(spec: dict, cell: dict, config: dict, traffic: dict, *,
             seed: int, seconds: float, trace: bool) -> int:
    """Run the cell once; print the checks and the result line."""
    h = Harness(cell=cell, config=config, traffic=traffic, seed=seed,
                seconds=seconds, trace=trace, t_start=T_START)
    try:
        h.start_jax()
    except NoDevice as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    driver = importlib.import_module(f"bench.drivers.{traffic['driver']}")
    try:
        out = driver.run(h)
    finally:
        h.close()
    device = {
        "platform": h.devices[0].platform,
        "kind": h.devices[0].device_kind,
        "count": len(h.devices),
        "memory_peak_bytes": h.memory_peak(),
    }
    line = {"correct": out.correct, "attempted": out.attempted,
            "failed": out.failed}
    if trace:
        from bench import trace as trace_mod

        tr = h.load_trace()
        window_ns = h.window_s * 1e9
        line["metrics"] = per_layer(spec, cell, h, out, tr)
        device["busy_s"] = trace_mod.busy_ns(tr, window_ns) / 1e9
        device["window_s"] = h.window_s
        line["device"] = device
        line["breakdown"] = {"device_ops": trace_mod.top_ops(tr, window_ns),
                             "idle_gaps": trace_mod.idle_gaps(tr, window_ns)}
    else:
        values = dict(out.metrics, setup_s=h.setup_s)
        line["metrics"] = {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in end_to_end(spec, cell["name"])}
        line["device"] = device
    line["notes"] = dict(out.notes, compiles=dict(h.compiles),
                         window_s=h.window_s)
    line["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                      for c in out.checks}
    for c in out.checks:
        print(f"check {c.name} value={c.value} limit={c.limit}",
              file=sys.stderr, flush=True)
    print(json.dumps(line, default=_plain), flush=True)
    return 0


def _plain(x):
    """NumPy scalars in the result line as plain numbers."""
    return x.item() if hasattr(x, "item") else str(x)


if __name__ == "__main__":
    sys.exit(main())
