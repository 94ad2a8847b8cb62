"""The profiler's trace, reduced to what the per-layer metrics read.

``start`` and ``stop`` record a window with ``jax.profiler``; ``events``
turns the ``.xplane.pb`` into a compact record: the device operations
(name, start, duration, and the op's HLO text where the trace gives it)
and the harness's own host spans, in nanoseconds on the trace's clock.
``reduce`` works on that record alone, so it can be checked on a small
trace recorded on the chip (``bench/testdata``):

The device's clock is not the host's: ``events`` moves device times onto
the host's clock by the programs that both sides name (``clock_offset``).

* busy: the union of the device operations' intervals inside the window;
* per-kernel device time, by event name;
* idle gaps, each attributed to the host span that overlaps it most.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

# the harness's host spans (admissions happen inside the engine's
# ``step_wave``, so they fall in ``wave``)
SPANS = ("chunk", "eval", "wave", "arrivals", "retire")
# lines of a device plane that summarise other lines (whole programs,
# steps) rather than list the operations themselves
SUMMARY_LINES = ("XLA Modules", "Steps", "XLA TraceMe", "Framework Ops",
                 "Framework Name Scope", "Source code", "SparseCore")
DETAIL_STATS = ("long_name", "hlo_op", "tf_op", "hlo_module")


def start(directory: str) -> None:
    import jax

    jax.profiler.start_trace(directory)


def stop(directory: str) -> str:
    """Stop tracing; the path of the ``.xplane.pb`` written."""
    import jax

    jax.profiler.stop_trace()
    paths = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise RuntimeError(f"the profiler wrote no trace under {directory}")
    return paths[-1]


def _is_device(plane_name: str) -> bool:
    """An accelerator's own plane (``/device:TPU:0``), not a plane of
    another kind filed under ``/device:`` (``/device:CUSTOM:...``)."""
    return re.fullmatch(r"/device:(TPU|GPU):\d+", plane_name) is not None


def op_name(event_name: str) -> str:
    """A device op's name as the trace gives it, or taken from the HLO text
    the trace gives in its place (``%masked_matmul_dx.1 = bf16[...] ...``)."""
    if event_name.startswith("%"):
        return event_name[1:].split(" = ", 1)[0]
    return event_name


def clock_offset(modules, enqueues) -> int:
    """Nanoseconds to add to device times to put them on the host's clock.

    A program cannot start on the device before the host enqueued it, so
    each program run named on both sides (its ``run_id``, by its first
    enqueue) bounds the offset from below; the largest bound is the offset
    (tight for a run that found the device idle).  0 where no run is named
    on both sides."""
    starts, first = dict(modules), {}
    for r, t in enqueues:          # a run may be enqueued in several parts
        first[r] = min(t, first.get(r, t))
    bounds = [t - starts[r] for r, t in first.items() if r in starts]
    return max(bounds) if bounds else 0


def events(path: str) -> dict:
    """{"device": [[name, start_ns, dur_ns, detail], ...] of the first
    device plane's operations, "host": [[name, start_ns, dur_ns], ...] of
    the harness's spans}, the device's times moved onto the host's clock
    (``clock_offset``)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    dev, host, modules, enqueues = [], [], [], []
    device_planes = sorted((p for p in pd.planes if _is_device(p.name)),
                           key=lambda p: p.name)
    if device_planes:
        plane = device_planes[0]
        lines = [ln for ln in plane.lines if ln.name == "XLA Ops"] or \
                [ln for ln in plane.lines if ln.name not in SUMMARY_LINES]
        for ln in lines:
            for e in ln.events:
                stats = dict(e.stats)
                detail = " ".join([e.name] + [str(stats[k]) for k in
                                              DETAIL_STATS if k in stats])
                dev.append([op_name(e.name), int(e.start_ns),
                            int(e.duration_ns), detail[:400]])
        for ln in plane.lines:
            if ln.name == "XLA Modules":
                modules += [(str(dict(e.stats).get("run_id")), int(e.start_ns))
                            for e in ln.events]
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for ln in plane.lines:
            for e in ln.events:
                if e.name in SPANS:
                    host.append([e.name, int(e.start_ns),
                                 int(e.duration_ns)])
                elif e.name == "DoEnqueueProgram":
                    enqueues.append((str(dict(e.stats).get("run_id")),
                                     int(e.start_ns)))
    shift = clock_offset(modules, enqueues)
    for r in dev:
        r[1] += shift
    dev.sort(key=lambda r: r[1])
    host.sort(key=lambda r: r[1])
    return {"device": dev, "host": host}


# ---------------------------------------------------------------------------
# the reduction
# ---------------------------------------------------------------------------

def union(intervals) -> list:
    """Merged [start, end) intervals, sorted."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def clip(intervals, lo, hi) -> list:
    return [[max(s, lo), min(e, hi)] for s, e in intervals
            if min(e, hi) > max(s, lo)]


def covered(merged, lo, hi) -> int:
    """Nanoseconds of [lo, hi) covered by the merged intervals."""
    return sum(e - s for s, e in clip(merged, lo, hi))


@dataclasses.dataclass
class Reduced:
    window: tuple            # (start_ns, end_ns)
    busy: list               # merged device intervals inside the window
    kernels: dict            # name -> {"count", "seconds"}
    device: list             # the window's device ops [name, start, dur, detail]
    spans: list              # the window's host spans [name, start, dur]
    gaps: list               # [span name, seconds, start_ns], longest first

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    @property
    def busy_s(self) -> float:
        return sum(e - s for s, e in self.busy) * 1e-9

    def ops(self, prefix: str) -> list:
        """The window's device ops named ``prefix`` (exactly, or with a
        ``.N``/``_N`` suffix), or whose HLO text names it."""
        return [r for r in self.device
                if r[0] == prefix or r[0].startswith(prefix + ".")
                or r[0].startswith(prefix + "_")
                or f"name={prefix}" in r[3] or f"\"{prefix}\"" in r[3]]

    def breakdown(self, n: int = 10) -> dict:
        top = sorted(self.kernels.items(), key=lambda kv: -kv[1]["seconds"])
        return {"device_ops": [[k, v["seconds"]] for k, v in top[:n]],
                "idle_gaps": [[g[0], g[1]] for g in self.gaps[:n]]}


def reduce(record: dict, window=None) -> Reduced:
    """Reduce a compact trace record.  ``window`` (ns) defaults to the
    span from the first host span's start to the last one's end."""
    dev, host = record["device"], record["host"]
    if window is None:
        if not host:
            raise ValueError("no host span to bound the window")
        window = (min(h[1] for h in host), max(h[1] + h[2] for h in host))
    lo, hi = window
    ops = [r for r in dev if r[1] < hi and r[1] + r[2] > lo]
    busy = union(clip([[r[1], r[1] + r[2]] for r in ops], lo, hi))
    kernels: dict = {}
    for r in ops:
        k = kernels.setdefault(r[0], {"count": 0, "seconds": 0.0})
        k["count"] += 1
        k["seconds"] += r[2] * 1e-9
    spans = [h for h in host if h[1] < hi and h[1] + h[2] > lo]
    gaps, t = [], lo
    for s, e in busy + [[hi, hi]]:
        if s > t:
            best, most = "none", 0
            for h in spans:
                ov = min(h[1] + h[2], s) - max(h[1], t)
                if ov > most:
                    best, most = h[0], ov
            gaps.append([best, (s - t) * 1e-9, t])
        t = max(t, e)
    gaps.sort(key=lambda g: -g[1])
    return Reduced(window=(lo, hi), busy=busy, kernels=kernels, device=ops,
                   spans=spans, gaps=gaps)
