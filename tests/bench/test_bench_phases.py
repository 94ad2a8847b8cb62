"""The round-phase reduction (``bench/phases.py``): on hand-made records,
on a profile taken here on the CPU, and on a chunk trace recorded on a TPU
v5e (``bench/testdata/trace_v5e_phases.json``: two rounds of a tiny masked
LM through the program's ``LocalScanBackend``, written by
``bench/record_phases.py``)."""
import glob
import json

import pytest

from bench import core, phases, trace

RECORDED = core.BENCH / "testdata" / "trace_v5e_phases.json"
RUN = "jit_chunk(1)"


def _record(ops, scopes, runs=((RUN, 0, 1000),)):
    return {"device": [[n, s, d, ""] for n, s, d in ops],
            "host": [["chunk", 0, 1000]],
            "runs": [list(r) for r in runs],
            "scopes": {RUN: scopes}, "program": []}


SCOPES = {
    "while.1": ["while", "jit(chunk)/while"],
    "fusion.1": ["fusion", "jit(chunk)/while/body/closed_call/fl_sample/"
                           "vmap(jit(_shuffle))/sort"],
    "dot.2": ["custom-call", "jit(chunk)/while/body/closed_call/"
                             "fl_client_train/vmap(jvp())/dot_general"],
    "while.3": ["while", "jit(chunk)/while/body/closed_call/"
                         "fl_client_train/vmap(while)"],
    "dot.4": ["fusion", "jit(chunk)/while/body/closed_call/"
                        "transpose(jvp(fl_server_update))/dot_general"],
    "add.5": ["fusion", "jit(chunk)/while/body/closed_call/fl_aggregate/"
                        "reduce_sum"],
    "copy.6": ["copy", None],
    "add.7": ["add", "jit(chunk)/while/body/add"],
    "mul.8": ["fusion", "jit(chunk)/while/body/closed_call/"
                        "fl_server_momentum/mul"],
}


def test_hand_made_record():
    ops = [("while.1", 0, 1000),            # container: left out
           ("fusion.1", 10, 20),
           ("while.3", 40, 300),           # container inside a phase
           ("dot.2", 40, 100), ("dot.2", 120, 100), ("dot.2", 200, 60),
           ("copy.6", 260, 10),            # no op_name: unattributed
           ("dot.4", 300, 200), ("add.5", 500, 30), ("mul.8", 530, 40),
           ("add.7", 570, 5),              # the round loop's own: no phase
           ("dot.4", 990, 50),             # clipped at the run's end
           ("fusion.1", 1100, 50)]         # outside the run
    red = phases.reduce(_record(ops, SCOPES), rounds=2)
    # client train: [40, 260) -> 220 ns over 2 rounds
    assert red.per_round["fl_client_train"] == pytest.approx(110e-9)
    assert red.per_round["fl_sample"] == pytest.approx(10e-9)
    # server update: [300, 500) and [990, 1000) -> 210 ns
    assert red.per_round["fl_server_update"] == pytest.approx(105e-9)
    assert red.per_round["fl_aggregate"] == pytest.approx(15e-9)
    assert red.per_round["fl_server_momentum"] == pytest.approx(20e-9)
    assert red.unattributed_s == pytest.approx(15e-9)
    assert red.busy_s == pytest.approx((20 + 220 + 10 + 210 + 30 + 40 + 5)
                                       * 1e-9)
    assert red.runs_s == pytest.approx(1000e-9)
    assert red.idle_s == pytest.approx(red.runs_s - red.busy_s)
    assert red.leaf_ops == 10
    assert [r[:2] for r in red.stray] == [["copy", None],
                                          ["add", "jit(chunk)/while/body/add"]]
    attributed = sum(red.per_round.values()) * red.rounds
    assert attributed + red.unattributed_s == pytest.approx(red.busy_s)


def test_a_phase_no_op_names_reads_none():
    scopes = {k: v for k, v in SCOPES.items() if k != "mul.8"}
    red = phases.reduce(_record([("dot.2", 0, 10), ("mul.8", 20, 10)],
                                scopes), rounds=1)
    assert red.per_round["fl_server_momentum"] is None
    assert red.per_round["fl_client_train"] == pytest.approx(10e-9)
    assert red.unattributed_s == 0


def test_no_chunk_run_or_no_scopes_reads_nothing():
    ops = [("dot.2", 0, 10)]
    assert phases.reduce(_record(ops, SCOPES, runs=()), rounds=1) is None
    assert phases.reduce(_record(ops, {}), rounds=1) is None
    # the eval program's runs are not the chunk's
    other = _record(ops, SCOPES, runs=(("jit_loss_and_acc(2)", 0, 50),))
    assert phases.reduce(other, rounds=1) is None


@pytest.mark.parametrize("op_name,phase", [
    ("jit(chunk)/while/body/closed_call/fl_client_train/vmap(jvp())/"
     "dot_general", "fl_client_train"),
    ("jit(chunk)/fl_sample/vmap(vmap(jit(_shuffle)))/sort", "fl_sample"),
    ("transpose(jvp(fl_server_update))/dot_general", "fl_server_update"),
    ("vmap(fl_aggregate)/reduce_sum", "fl_aggregate"),
    ("jit(chunk)/while/body/add", None),
    ("jit(chunk)/fl_sample/fl_client_train/add", None),   # two: no phase
    ("jit(chunk)/fl_sampler/add", None),
    (None, None),
])
def test_phase_through_transform_wrappers(op_name, phase):
    assert phases.phase_of(op_name) == phase


def test_phase_names_are_the_programs():
    from repro.core.engine import ROUND_PHASES

    assert phases.PHASES == ROUND_PHASES


# -- the protobuf reader -----------------------------------------------------

def _varint(n):
    out = b""
    while True:
        b, n = n & 0x7F, n >> 7
        out += bytes([b | (0x80 if n else 0)])
        if not n:
            return out


def _msg(num, payload):
    if isinstance(payload, str):
        payload = payload.encode()
    return _varint(num << 3 | 2) + _varint(len(payload)) + payload


def _int(num, value):
    return _varint(num << 3) + _varint(value)


def _instruction(name, opcode, op_name=None):
    meta = _msg(7, _msg(1, "dot") + _msg(2, op_name)) if op_name else b""
    return _msg(1, name) + _msg(2, opcode) + _msg(3, b"\x08\x0b") + meta \
        + _int(35, 9)


def test_hlo_protos_are_read_from_the_metadata_plane():
    comp = (_msg(1, "main") + _msg(2, _instruction("dot.1", "dot", "a/b"))
            + _msg(2, _instruction("p.0", "parameter")) + _int(5, 1))
    fused = _msg(1, "fused") + _msg(2, _instruction("mul.2", "multiply",
                                                    "a/fl_sample/mul"))
    hlo = _msg(1, _msg(1, "jit_chunk") + _msg(3, comp) + _msg(3, fused))
    stat = _int(1, 1) + b"\x11" + bytes(8) + _msg(6, hlo)   # a fixed64 too
    meta = _int(1, 7) + _msg(2, "jit_chunk(7)") + _msg(5, stat)
    plane = (_int(1, 2) + _msg(2, "/host:metadata")
             + _msg(4, _int(1, 7) + _msg(2, meta))
             + _msg(5, _int(1, 1) + _msg(2, _int(1, 1) + _msg(2, "x"))))
    other = _int(1, 1) + _msg(2, "/device:TPU:0") + _msg(4, _int(1, 3))
    space = _msg(1, other) + _msg(1, plane) + _msg(4, "host")
    assert phases.programs(space) == {"jit_chunk(7)": {
        "dot.1": ["dot", "a/b"], "p.0": ["parameter", None],
        "mul.2": ["multiply", "a/fl_sample/mul"]}}


@pytest.fixture(scope="module")
def cpu_profile(tmp_path_factory):
    """A profile of a tiny CNN chunk and its eval under the harness's
    spans, taken here on the CPU."""
    import jax

    from repro.analysis.compile_budget import _fresh_model, make_world
    from repro.core.backend import LocalScanBackend

    data, cfg = make_world("cnn")
    model = _fresh_model("cnn")
    be = LocalScanBackend(model, data, cfg)
    state = be.init_state(model.init(jax.random.key(0)))
    key = jax.random.key(1)
    state, key, _ = be.run_chunk(state, key, 2)
    float(be.evaluate(state)[0])
    d = str(tmp_path_factory.mktemp("profile"))
    trace.start(d)
    with jax.profiler.TraceAnnotation("chunk"):
        state, key, _ = be.run_chunk(state, key, 2)
    with jax.profiler.TraceAnnotation("eval"):
        float(be.evaluate(state)[0])
    return trace.stop(d)


def test_program_spans_sit_inside_the_harness_spans(cpu_profile):
    rec = phases.events(cpu_profile)
    base = trace.events(cpu_profile)
    assert {k: rec[k] for k in base} == base      # the old keys, unchanged
    spans = {h[0]: h for h in rec["host"]}
    prog = {p[0]: p for p in rec["program"]}
    for outer, inner in (("chunk", "fl.run_chunk"), ("eval", "fl.evaluate")):
        o, i = spans[outer], prog[inner]
        assert o[1] <= i[1] and i[1] + i[2] <= o[1] + o[2]


def test_the_profiles_hlo_carries_the_phases(cpu_profile):
    # the metadata plane holds every program the process has loaded
    with open(cpu_profile, "rb") as f:
        progs = phases.programs(f.read())
    named = [{phases.phase_of(op) for _, op in v.values()}
             for k, v in progs.items() if k.startswith("jit_chunk(")]
    assert any(set(phases.PHASES) <= n for n in named)


# -- the chip trace ----------------------------------------------------------

@pytest.fixture(scope="module")
def recorded():
    if not RECORDED.is_file():
        pytest.skip("no recorded chip trace (bench/record_phases.py)")
    return json.loads(RECORDED.read_text())


def test_recorded_chip_trace(recorded):
    red = phases.reduce(recorded, rounds=2)
    assert all(v is not None and v > 0 for v in red.per_round.values())
    attributed = sum(red.per_round.values()) * red.rounds
    assert attributed + red.unattributed_s == pytest.approx(red.busy_s,
                                                            rel=1e-9)
    # the local epochs are most of a round; every op of a phase is a leaf
    assert max(red.per_round, key=red.per_round.get) == "fl_client_train"
    assert 0 <= red.idle_s < red.runs_s
    assert red.busy_s <= red.runs_s
    ops = {r[0] for r in recorded["device"]}
    table = next(iter(recorded["scopes"].values()))
    assert ops <= set(table)


def test_recorded_program_spans(recorded):
    spans = {h[0]: h for h in recorded["host"]}
    for outer, inner in (("chunk", "fl.run_chunk"), ("eval", "fl.evaluate")):
        o = spans[outer]
        (i,) = [p for p in recorded["program"] if p[0] == inner]
        assert o[1] <= i[1] and i[1] + i[2] <= o[1] + o[2]


def test_recorded_trace_is_small(recorded):
    assert RECORDED.stat().st_size <= 300_000
    assert not glob.glob(str(core.BENCH / "testdata" / "*.pb*"))
