"""Shared pieces of the chip benchmark: files found by name, the device
check, the peaks table, the in-window compilation count, the percentile
and the result line.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric lives in a file of its own, found by the name that
``BENCHMARK.json`` gives it:

    bench/configs/<config>.json   sizes, source, cut, deployment
    bench/configs/<config>.py     builder of params from the seed, the
                                  program adapter and the plain reference
    bench/traffic/<traffic>.json  parameters read by the driver it names
    bench/metrics/<metric>.py     ``read(ctx)``: the metric or None
"""
from __future__ import annotations

import importlib.util
import json
import math
import pathlib
import statistics
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"


class BenchError(RuntimeError):
    """The benchmark cannot run here (no chip, a file missing)."""


def add_program_path() -> None:
    """Make ``repro`` (the system under test) and ``bench`` importable."""
    for p in (str(ROOT / "src"), str(ROOT)):
        if p not in sys.path:
            sys.path.insert(0, p)


def load_module(path: pathlib.Path, name: str):
    if not path.is_file():
        raise BenchError(f"missing {path.relative_to(ROOT)}")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_json(path: pathlib.Path) -> dict:
    if not path.is_file():
        raise BenchError(f"missing {path.relative_to(ROOT)}")
    return json.loads(path.read_text())


def benchmark() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise BenchError(f"no workload {name!r} in BENCHMARK.json")


def config(name: str) -> tuple[dict, object]:
    """(sizes, module) of configuration ``name``."""
    return (load_json(BENCH / "configs" / f"{name}.json"),
            load_module(BENCH / "configs" / f"{name}.py",
                        f"bench_config_{name.replace('-', '_').replace('.', '_')}"))


def traffic(name: str) -> dict:
    return load_json(BENCH / "traffic" / f"{name}.json")


def metric_reader(name: str):
    mod = load_module(BENCH / "metrics" / f"{name}.py",
                      f"bench_metric_{name.replace('-', '_').replace('.', '_')}")
    return mod.read


def driver(name: str):
    return load_module(BENCH / "drivers" / f"{name}.py",
                       f"bench_driver_{name}")


def metrics_of(bench: dict, cell_name: str, *, trace: bool) -> list[dict]:
    """The cell's end-to-end metrics (``trace=False``) or per-layer ones."""
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group
            if "workloads" not in m or cell_name in m["workloads"]]


# ---------------------------------------------------------------------------
# the device
# ---------------------------------------------------------------------------

def device_record(chips: int) -> dict:
    """Platform, kind and count as JAX reports them; refuses anything but
    ``chips`` or more TPU chips (never falls back to the CPU)."""
    import jax

    devs = jax.devices()
    d = devs[0]
    print(f"device: platform={d.platform} kind={d.device_kind} "
          f"count={len(devs)}", file=sys.stderr)
    if d.platform != "tpu":
        raise BenchError(f"needs a TPU, JAX found platform {d.platform!r} "
                         f"({d.device_kind})")
    if len(devs) < chips:
        raise BenchError(f"needs {chips} TPU chips, JAX found {len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind, "count": chips}


def peaks(kind: str) -> dict:
    table = load_json(BENCH / "peaks.json")["devices"]
    if kind not in table:
        raise BenchError(f"no peaks for device kind {kind!r} in "
                         f"bench/peaks.json")
    return table[kind]


def memory_peak_bytes(chips: int) -> int:
    import jax

    return max(int(d.memory_stats().get("peak_bytes_in_use", 0))
               for d in jax.devices()[:chips])


def enable_compile_cache() -> str:
    """JAX's persistent cache at the program's fixed path inside the
    checkout (or ``JAX_COMPILATION_CACHE_DIR``), every program kept."""
    import jax
    from repro.utils.compile_cache import enable_compile_cache as enable

    where = enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return where


class CompileCounter:
    """Counts traces and backend compilations as JAX reports them, so a
    window can prove that nothing was traced or compiled inside it."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax

        self.counts = {e: 0 for e in self.EVENTS}

        def listen(event, duration, **kw):
            if event in self.counts:
                self.counts[event] += 1

        jax.monitoring.register_event_duration_secs_listener(listen)

    def total(self) -> int:
        return sum(self.counts.values())


# ---------------------------------------------------------------------------
# numbers
# ---------------------------------------------------------------------------

def p95(values) -> float:
    """95th percentile (inclusive interpolation) of all the values."""
    vals = sorted(float(v) for v in values)
    if not vals:
        return math.nan
    if len(vals) == 1:
        return vals[0]
    return statistics.quantiles(vals, n=20, method="inclusive")[18]


def emit(result: dict, checks: dict) -> None:
    """The compared numbers beside their limits as the last lines of
    standard error, then the result as the last line of standard output
    (``checks`` last in it)."""
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r} "
              f"({'ok' if c['ok'] else 'FAIL'})", file=sys.stderr)
    line = dict(result)
    line["checks"] = checks
    sys.stderr.flush()
    print(json.dumps(line), flush=True)


def check(value: float, limit: float) -> dict:
    ok = bool(math.isfinite(value) and value <= limit)
    return {"value": float(value), "limit": float(limit), "ok": ok}
