#!/usr/bin/env python3
"""The chip benchmark: one cell, one run, one result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json`` ``workloads``) names a configuration and a
traffic mix; the mix names the driver that runs it.  The run refuses any
platform but a TPU with the cell's chip count, and prints nothing on
standard output then.  ``--trace 0`` measures the end-to-end metrics with
the profiler off; ``--trace 1`` traces part of the window and reports the
per-layer metrics, ``busy_s``/``window_s`` and a breakdown.  Each run
compares what its timed path produced with the plain reference, prints
each compared number beside its limit as the last lines of standard
error, and ends standard output with one JSON object.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from bench import core  # noqa: E402


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def layer_metrics(bench: dict, cell_name: str, layer) -> dict:
    """Each per-layer metric of the cell that its reader finds."""
    out = {}
    for m in core.metrics_of(bench, cell_name, trace=True):
        value = None if layer is None else core.metric_reader(m["name"])(layer)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def main(argv=None) -> int:
    args = parse(argv)
    bench = core.benchmark()
    cell = core.cell(bench, args.workload)
    sizes, cfgmod = core.config(cell["config"])
    traffic = core.traffic(cell["traffic"])
    limits = core.load_json(core.BENCH / "limits" / f"{cell['name']}.json")
    core.add_program_path()
    device = core.device_record(cell["chips"])
    peaks = core.peaks(device["kind"])
    cache = core.enable_compile_cache()
    print(f"compile cache: {cache}", file=sys.stderr)
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
    ctx = types.SimpleNamespace(
        t0=T0, seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
        trace_dir=trace_dir, sizes=sizes, cfgmod=cfgmod, traffic=traffic,
        limits=limits, peaks=peaks, compiles=core.CompileCounter(),
        memory_peak=lambda: core.memory_peak_bytes(cell["chips"]))
    try:
        out = core.driver(traffic["driver"]).run(ctx)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)

    checks = {k: core.check(v, lim) for k, (v, lim) in out["checks"].items()}
    checks["compiles_in_window"] = core.check(out["compiles_in_window"], 0)
    device = dict(device, memory_peak_bytes=int(out["memory_peak_bytes"]))
    if args.trace:
        metrics = layer_metrics(bench, cell["name"], out["layer"])
        if out["layer"] is not None:
            red = out["layer"]["reduced"]
            device.update(busy_s=red.busy_s, window_s=red.window_s)
    else:
        metrics = {"setup_s": {"value": out["setup_s"], "unit": "s"}}
        for m in core.metrics_of(bench, cell["name"], trace=False):
            if m["name"] in out["e2e"]:
                metrics[m["name"]] = {"value": float(out["e2e"][m["name"]]),
                                      "unit": m["unit"]}
    print(f"notes: {out.get('notes')}", file=sys.stderr)
    print(f"setup_s {out['setup_s']!r} compiles_in_window "
          f"{out['compiles_in_window']}", file=sys.stderr)
    result = {"correct": all(c["ok"] for c in checks.values()),
              "attempted": int(out["attempted"]),
              "failed": int(out["failed"]),
              "metrics": metrics, "device": device}
    if args.trace and out["layer"] is not None:
        result["breakdown"] = out["layer"]["reduced"].breakdown()
    core.emit(result, checks)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except core.BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        sys.exit(2)
