"""The trace reduction, on a hand-made record and on a small trace
recorded on a TPU v5e (``bench/testdata/trace_v5e.json``: the masked
matmul's forward and backward, the flash-decode kernel and a matmul under
the harness's ``chunk``, ``wave`` and ``eval`` spans, reduced by
``bench.trace.events``)."""
import json

import pytest

from bench import core, trace

RECORDED = core.BENCH / "testdata" / "trace_v5e.json"


def test_hand_made_record():
    rec = {"device": [["a", 10, 20, ""], ["b", 25, 10, ""],
                      ["a", 60, 10, ""], ["c", 95, 20, ""]],
           "host": [["chunk", 0, 50], ["eval", 50, 40], ["wave", 90, 30]]}
    red = trace.reduce(rec, window=(0, 100))
    # busy: [10, 35) and [60, 70) and [95, 100) -> 25 + 10 + 5 ns
    assert red.busy == [[10, 35], [60, 70], [95, 100]]
    assert red.busy_s == pytest.approx(40e-9)
    assert red.window_s == pytest.approx(100e-9)
    assert red.kernels["a"] == {"count": 2, "seconds": pytest.approx(30e-9)}
    assert red.kernels["c"]["count"] == 1
    # idle: [0,10) chunk, [35,60) chunk 15 / eval 10 -> chunk,
    # [70,95) eval 20 / wave 5 -> eval
    gaps = sorted((g[2], g[0], g[1]) for g in red.gaps)
    assert [(s, n) for s, n, _ in gaps] == [(0, "chunk"), (35, "chunk"),
                                           (70, "eval")]
    assert [d for _, _, d in gaps] == pytest.approx([10e-9, 25e-9, 25e-9])
    assert red.breakdown(2)["device_ops"][0][0] == "a"
    assert [g[0] for g in red.breakdown(2)["idle_gaps"]] in (
        ["chunk", "eval"], ["eval", "chunk"])


@pytest.mark.parametrize("event,name", [
    ("%masked_matmul_dx.1 = bf16[256,512]{1,0} custom-call(s32[8] %c)",
     "masked_matmul_dx.1"),
    ("%fusion.12 = bf16[2,512]{1,0} fusion(bf16[2,512] %p), kind=kLoop",
     "fusion.12"),
    ("decode_attention.3", "decode_attention.3"),
])
def test_op_name_from_the_hlo_text(event, name):
    assert trace.op_name(event) == name


@pytest.mark.parametrize("plane,device", [
    ("/device:TPU:0", True), ("/device:CUSTOM:Megascale Trace", False),
    ("/device:CPU:0", False), ("/host:CPU", False)])
def test_device_plane_is_the_accelerators(plane, device):
    assert trace._is_device(plane) is device


def test_clock_offset_is_the_tightest_enqueue_bound():
    # run 24 started on the device 1000 ns after its enqueue would allow,
    # run 25 waited behind it: the offset is set by the idle one
    modules = [("24", 100), ("25", 500)]
    enqueues = [("24", 1300), ("25", 1350), ("26", 9000)]
    assert trace.clock_offset(modules, enqueues) == 1200
    assert trace.clock_offset(modules, []) == 0


def _brute_busy(ops, lo, hi):
    """Busy nanoseconds by walking every boundary: independent of
    ``trace.union``."""
    pts = sorted({lo, hi} | {max(lo, min(hi, p)) for r in ops
                             for p in (r[1], r[1] + r[2])})
    busy = 0
    for a, b in zip(pts, pts[1:]):
        mid = (a + b) / 2
        if any(r[1] <= mid < r[1] + r[2] for r in ops):
            busy += b - a
    return busy


@pytest.mark.skipif(not RECORDED.is_file(), reason="no recorded trace")
def test_recorded_v5e_trace():
    rec = json.loads(RECORDED.read_text())
    spans = rec["host"]
    lo = min(h[1] for h in spans)
    hi = max(h[1] + h[2] for h in spans)
    red = trace.reduce(rec)
    assert red.window == (lo, hi)
    assert red.busy_s * 1e9 == pytest.approx(_brute_busy(rec["device"], lo,
                                                         hi))
    assert 0 < red.busy_s < red.window_s
    idle = sum(g[1] for g in red.gaps)
    assert idle == pytest.approx(red.window_s - red.busy_s)
    # each kernel's time is the sum of its own events in the window
    for name in ("masked_matmul_fwd", "masked_matmul_dx",
                 "masked_matmul_dw", "decode_attention"):
        ev = red.ops(name)
        assert ev, name
        assert sum(r[2] for r in ev) * 1e-9 == pytest.approx(
            sum(k["seconds"] for n, k in red.kernels.items()
                if any(r[0] == n for r in ev)))
    # every gap is attributed to a span that overlaps it, or to none
    for name, seconds, start in red.gaps:
        end = start + seconds * 1e9
        over = [h[0] for h in spans
                if min(h[1] + h[2], end) > max(h[1], start)]
        assert (name in over) if over else name == "none"
