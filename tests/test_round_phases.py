"""The round's phases as named scopes in the compiled scan program, and the
backend's host spans.

Every instruction of a compiled chunk carries its phase (one of
``engine.ROUND_PHASES``) in its ``op_name`` metadata, which is what a
device trace shows for each op; a run under ``jax.profiler`` shows the
backend's entry points as ``fl.*`` host spans.
"""
import dataclasses
import glob
import re

import jax
import pytest

from repro.analysis.compile_budget import _fresh_model, make_world
from repro.core import Eval, FederatedTrainer, Prune, Scan, TrainPlan
from repro.core.backend import LocalScanBackend, MeshBackend
from repro.core.engine import ROUND_PHASES

# instructions that do no work of their own: operands, tuple plumbing and
# control flow (the loops' bodies are walked instead)
PLUMBING = ("parameter", "constant", "tuple", "get-tuple-element", "bitcast",
            "while", "conditional", "call")
CALLED = re.compile(r"\b(?:body|condition|to_apply|branch_computations)="
                    r"(\{[^}]*\}|%?[\w.\-]+)")


def computations(hlo: str) -> tuple[dict, str]:
    """({computation name: [instruction lines]}, entry name) of HLO text."""
    comps, cur, entry = {}, None, None
    for line in hlo.splitlines():
        if line and not line.startswith(" ") and line.rstrip().endswith("{"):
            m = re.match(r"^(ENTRY )?%([\w.\-]+)", line)
            if m:
                cur = m.group(2)
                comps[cur] = []
                entry = cur if m.group(1) else entry
                continue
        if cur and line.startswith("  ") and " = " in line:
            comps[cur].append(line.strip())
    return comps, entry


def instruction(line: str) -> tuple[str, str | None]:
    """(opcode, op_name or None) of one HLO instruction line."""
    rhs = line.split(" = ", 1)[1]
    if rhs.startswith("("):                    # a tuple shape
        depth = 0
        for i, ch in enumerate(rhs):
            depth += {"(": 1, ")": -1}.get(ch, 0)
            if depth == 0:
                break
        rest = rhs[i + 1:].lstrip()
    else:
        rest = rhs.split(" ", 1)[1]
    m = re.search(r'op_name="([^"]*)"', line)
    return rest.split("(", 1)[0], (m.group(1) if m else None)


def phases_of(op_name: str | None) -> set:
    return {p for p in (op_name or "").split("/") if p in ROUND_PHASES}


def loop_control(op_name: str) -> bool:
    """The round scan's own work, directly under its body: the round
    counter, the stacking of per-round metrics, the carry hand-over."""
    return re.fullmatch(r"jit\(chunk\)/while/body(/closed_call)?(/\w+)?",
                        op_name) is not None


def round_body(hlo: str) -> list:
    """(opcode, op_name) of every instruction the round loop runs: the
    entry computation's unscoped ``while`` (the scan over rounds) and each
    computation its body reaches through loops, branches and calls (fused
    computations are represented by their fusion instruction)."""
    comps, entry = computations(hlo)
    loops = [ln for ln in comps[entry] if instruction(ln)[0] == "while"
             and not phases_of(instruction(ln)[1])]
    assert len(loops) == 1, "expected one round loop in the entry"
    todo = [re.search(r"\bbody=%?([\w.\-]+)", loops[0]).group(1)]
    seen, out = set(), []
    while todo:
        c = todo.pop()
        if c in seen:
            continue
        seen.add(c)
        for ln in comps[c]:
            op, name = instruction(ln)
            out.append((op, name))
            if op in ("while", "conditional", "call"):
                for m in CALLED.finditer(ln):
                    todo += [s.strip().lstrip("%")
                             for s in m.group(1).strip("{}").split(",")]
    return out


WORLDS = {
    # FedDUMAP on the CNN, with the health guard (aggregate and server
    # guard code in their phases)
    "cnn_guard": ("cnn", {"guard": "reject_client"}, False),
    # the masked LM on the Pallas kernel (the benchmark's path)
    "lm_kernel": ("lm", {"masked_compute": "kernel"}, True),
    # FedDyn with stragglers: the correction scatter and delta-form FedAvg
    "cnn_feddyn": ("cnn", {"algorithm": "feddyn", "dropout_rate": 0.25},
                   False),
}


def _backend(world, cls=LocalScanBackend):
    kind, kw, masks = WORLDS[world]
    data, cfg = make_world(kind)
    model = _fresh_model(kind)
    be = cls(model, data, dataclasses.replace(cfg, **kw), use_masks=masks)
    return be, be.init_state(model.init(jax.random.key(0)))


def _compiled_hlo(be, state) -> str:
    return be.chunk.lower(state, jax.random.key(1), be.device_data(),
                          length=2).compile().as_text()


@pytest.mark.parametrize("world", sorted(WORLDS))
def test_every_round_op_carries_one_phase(world):
    be, state = _backend(world)
    hlo = _compiled_hlo(be, state)
    names = set(re.findall(r'op_name="([^"]*)"', hlo))
    for phase in ROUND_PHASES:
        assert any(phase in phases_of(n) for n in names), phase
    # instructions the compiler adds (copies of loop-carried buffers, its
    # own rewrites) carry no op_name: no scope can name them
    body = [(op, n) for op, n in round_body(hlo)
            if op not in PLUMBING and n is not None]
    work = [n for _, n in body if not loop_control(n)]
    assert len(work) >= 0.9 * len(body)
    counts = [len(phases_of(n)) for n in work]
    assert max(counts) == 1, "phases nest"
    stray = sorted({n for n, c in zip(work, counts) if c == 0})
    assert not stray, stray[:10]


def test_mesh_chunk_carries_the_phases():
    be, state = _backend("cnn_guard", MeshBackend)
    hlo = _compiled_hlo(be, state)
    names = set(re.findall(r'op_name="([^"]*)"', hlo))
    assert set(ROUND_PHASES) <= set().union(*map(phases_of, names))


def test_phase_names_are_fixed():
    # read by the benchmark (bench/phases.py) and named in PERF.md
    assert ROUND_PHASES == ("fl_sample", "fl_client_train", "fl_aggregate",
                            "fl_server_update", "fl_server_momentum")


def _host_spans(directory) -> list:
    from jax.profiler import ProfileData

    path = glob.glob(f"{directory}/**/*.xplane.pb", recursive=True)[0]
    return [e.name for p in ProfileData.from_file(path).planes
            if p.name.startswith("/host:") for ln in p.lines
            for e in ln.events if e.name.startswith("fl.")]


@pytest.mark.parametrize("backend", ("local", "mesh"))
def test_a_profiled_run_shows_the_backend_spans(backend, tmp_path):
    data, cfg = make_world("cnn")
    tr = FederatedTrainer(_fresh_model("cnn"), data, cfg, backend=backend)
    plan = TrainPlan(Scan(1), Eval(), Prune(mode="mask"), Scan(1),
                     checkpoint_every=1, checkpoint_dir=tmp_path / "ckpt")
    with jax.profiler.trace(str(tmp_path / "trace")):
        tr.run(plan)
    spans = _host_spans(tmp_path / "trace")
    assert spans.count("fl.run_chunk") == 2
    assert spans.count("fl.checkpoint") == 2
    for name in ("fl.evaluate", "fl.prune_decision", "fl.apply_prune"):
        assert spans.count(name) == 1, name
