"""A federated round's device time, split by the program's round phases.

The program names each phase of a round with a ``jax.named_scope``
(``repro.core.engine.ROUND_PHASES``, copied here as ``PHASES``: the
benchmark imports nothing of the program), so every instruction of the
compiled chunk carries its phase in its ``op_name``.  The TPU's device
plane names each op by its instruction alone; the instructions' metadata
is in the trace's ``/host:metadata`` plane, as each program's serialized
``HloProto``.  ``events`` reads both, beside ``bench.trace.events``'s
record, and ``reduce`` turns them into seconds per round:

* leaf ops only: the HLO ``while``, ``conditional`` and ``call`` ops
  contain other ops and span their bodies;
* an op's phase is the ``fl_*`` component of its ``op_name``, matched
  through transform wrappers (``vmap(jvp(fl_x))``, ``transpose(...)``);
* per phase, the union of its ops' intervals inside the chunk program's
  runs, over the traced rounds; ``None`` for a phase no op names.

The program's own host spans (``fl.run_chunk``, ``fl.evaluate``, ...)
come back under ``program``, on the same clock as the harness's spans.
"""
from __future__ import annotations

import dataclasses
import re

from bench import trace

PHASES = ("fl_sample", "fl_client_train", "fl_aggregate", "fl_server_update",
          "fl_server_momentum")
CONTAINERS = ("while", "conditional", "call")
PHASE_RE = re.compile(r"(?<![\w.])(" + "|".join(PHASES) + r")(?![\w.])")
CHUNK = "jit_chunk"      # the jitted name of the program's scan chunk


# ---------------------------------------------------------------------------
# reading the HLO protos out of the trace (protobuf wire format)
# ---------------------------------------------------------------------------

def _varint(buf, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def fields(buf):
    """(field number, value) of one protobuf message: an int for a varint,
    a memoryview for a length-delimited field, None for fixed widths."""
    buf = memoryview(buf)
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            value, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield num, value


def _first(buf, number: int):
    return next((v for k, v in fields(buf) if k == number), None)


def hlo_ops(hlo_proto) -> dict:
    """{instruction name: [opcode, op_name or None]} of every computation
    of one serialized ``HloProto`` (``hlo_module`` 1; ``computations`` 3;
    ``instructions`` 2; name 1, opcode 2, ``metadata`` 7 -> ``op_name``
    2)."""
    out = {}
    module = _first(hlo_proto, 1)
    for k, comp in fields(module if module is not None else b""):
        if k != 3:
            continue
        for j, ins in fields(comp):
            if j != 2:
                continue
            name = opcode = op = None
            for f, v in fields(ins):
                if f == 1:
                    name = bytes(v).decode()
                elif f == 2:
                    opcode = bytes(v).decode()
                elif f == 7:
                    op = _first(v, 2)
            out[name] = [opcode, bytes(op).decode() if op else None]
    return out


def programs(data: bytes) -> dict:
    """{program name (``jit_chunk(<id>)``): hlo_ops} from an XSpace's
    ``/host:metadata`` plane (``planes`` 1; plane ``name`` 2,
    ``event_metadata`` 4 -> value 2; its ``name`` 2 and ``stats`` 5, whose
    ``bytes_value`` 6 holds the HloProto)."""
    out = {}
    for k, plane in fields(data):
        if k != 1 or bytes(_first(plane, 2) or b"") != b"/host:metadata":
            continue
        for f, entry in fields(plane):
            if f != 4:
                continue
            meta = _first(entry, 2)
            name, protos = None, []
            for g, v in fields(meta):
                if g == 2:
                    name = bytes(v).decode()
                elif g == 5:
                    b = _first(v, 6)
                    if b is not None:
                        protos.append(b)
            if name and protos:
                out[name] = hlo_ops(protos[0])
    return out


# ---------------------------------------------------------------------------
# the record
# ---------------------------------------------------------------------------

def events(path: str) -> dict:
    """``bench.trace.events``'s record, plus ``runs`` ([[program, start_ns,
    dur_ns], ...]: the device's program runs), ``scopes`` ({program: {op:
    [opcode, op_name]}} of the programs that ran) and ``program`` ([[name,
    start_ns, dur_ns], ...]: the program's ``fl.*`` host spans), all on
    the host's clock."""
    from jax.profiler import ProfileData

    rec = trace.events(path)
    with open(path, "rb") as f:
        data = f.read()
    pd = ProfileData.from_serialized_xspace(data)
    runs, enqueues, spans = [], [], []
    planes = sorted((p for p in pd.planes if trace._is_device(p.name)),
                    key=lambda p: p.name)
    for ln in (planes[0].lines if planes else ()):
        if ln.name == "XLA Modules":
            runs += [[e.name, int(e.start_ns), int(e.duration_ns),
                      str(dict(e.stats).get("run_id"))] for e in ln.events]
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for ln in plane.lines:
            for e in ln.events:
                if e.name.startswith("fl."):
                    spans.append([e.name, int(e.start_ns),
                                  int(e.duration_ns)])
                elif e.name == "DoEnqueueProgram":
                    enqueues.append((str(dict(e.stats).get("run_id")),
                                     int(e.start_ns)))
    shift = trace.clock_offset([(r[3], r[1]) for r in runs], enqueues)
    ran = {r[0] for r in runs}
    scopes = {k: v for k, v in programs(data).items() if k in ran}
    return dict(rec, runs=sorted([r[0], r[1] + shift, r[2]] for r in runs),
                scopes=scopes, program=sorted(spans, key=lambda s: s[1]))


def phase_of(op_name: str | None) -> str | None:
    """The round phase an op's ``op_name`` names, or None (none, or more
    than one)."""
    found = set(PHASE_RE.findall(op_name or ""))
    return found.pop() if len(found) == 1 else None


# ---------------------------------------------------------------------------
# the reduction
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Phases:
    rounds: int
    per_round: dict          # phase -> device seconds per round, or None
    runs_s: float            # the chunk program's runs
    busy_s: float            # union of its leaf ops
    unattributed_s: float    # union of its leaf ops that name no phase
    idle_s: float            # runs_s - busy_s
    leaf_ops: int
    stray: list              # [[opcode, op_name, seconds]] of the
                             # unattributed ops, longest first

    def notes(self) -> dict:
        return {"rounds": self.rounds, "runs_s": self.runs_s,
                "leaf_busy_s": self.busy_s,
                "unattributed_s": self.unattributed_s,
                "leaf_idle_s": self.idle_s, "leaf_ops": self.leaf_ops,
                "unattributed_top": self.stray[:5]}


def reduce(record: dict, rounds: int, program: str = CHUNK) -> Phases | None:
    """Seconds per round of each phase inside the runs of ``program`` (the
    chunk), or None when the trace holds no run of it or no scope table
    for it."""
    runs = [r for r in record.get("runs", ())
            if r[0].split("(", 1)[0] == program]
    names = {r[0] for r in runs}
    table: dict = {}
    for k, v in record.get("scopes", {}).items():
        if k in names:
            table.update(v)
    if not runs or not table or rounds <= 0:
        return None
    windows = trace.union([[r[1], r[1] + r[2]] for r in runs])
    by_phase: dict = {p: [] for p in PHASES}
    other, leaves, stray = [], 0, {}
    for name, start, dur, *_ in record["device"]:
        info = table.get(name)
        if info is None or info[0] in CONTAINERS:
            continue
        iv = [[max(start, lo), min(start + dur, hi)] for lo, hi in windows
              if min(start + dur, hi) > max(start, lo)]
        if not iv:
            continue
        leaves += 1
        phase = phase_of(info[1])
        (by_phase[phase] if phase else other).extend(iv)
        if not phase:
            k = (info[0], info[1])
            stray[k] = stray.get(k, 0) + sum(e - s for s, e in iv) * 1e-9

    def seconds(ivs):
        return sum(e - s for s, e in trace.union(ivs)) * 1e-9

    busy = seconds([iv for ivs in by_phase.values() for iv in ivs] + other)
    runs_s = sum(e - s for s, e in windows) * 1e-9
    return Phases(
        rounds=rounds,
        per_round={p: (seconds(v) / rounds if v else None)
                   for p, v in by_phase.items()},
        runs_s=runs_s, busy_s=busy, unattributed_s=seconds(other),
        idle_s=runs_s - busy, leaf_ops=leaves,
        stray=sorted(([*k, v] for k, v in stray.items()),
                     key=lambda r: -r[2]))
