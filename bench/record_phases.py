#!/usr/bin/env python3
"""Trace the program's scan chunk and split its device time by round phase.

    python3 bench/record_phases.py --out bench/testdata/trace_v5e_phases.json
    python3 bench/record_phases.py --workload olmo-1b-l4.fl-masked --seed <n>

The first form, on a TPU, runs one 2-round chunk of a tiny masked LM (one
layer, the masked FFN on the Pallas kernel) through the program's own
``LocalScanBackend`` (``core.backend.build_chunk``), under the harness's
``chunk`` and ``eval`` spans, and writes the compact record of
``bench.phases.events``: the device ops inside the chunk's run (times
from the first span's start), the program runs, the scope of each op that
ran, and the host spans.  The phase reducer's test reads it.

The second form builds a training cell as ``bench/run.py`` does (the
set-up steps included), traces one window step (a chunk and its eval) and
prints ``bench.phases.reduce``'s reading as one JSON line: device seconds
per round of each phase, the leaf-op busy, unattributed and idle time
inside the chunk's run (with the longest unattributed ops), and the traced
window.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import shutil
import sys
import tempfile

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from bench import core, phases, trace  # noqa: E402

ROUNDS = 2


def tiny_backend():
    """A 1-layer masked LM under FedDUMAP: 2 of 8 clients, 2 local steps,
    tau 2, the masked FFN on the kernel."""
    from repro.configs.base import ModelConfig
    from repro.core import feddumap_config
    from repro.core.backend import LocalScanBackend
    from repro.data.pipeline import build_lm_federated_data
    from repro.data.synthetic import TokenSpec
    from repro.models.lm import LM

    data = build_lm_federated_data(
        num_clients=8, spec=TokenSpec(vocab_size=2048, num_topics=16,
                                      seq_len=129, num_sequences=128))
    cfg = feddumap_config(num_clients=8, clients_per_round=2, local_epochs=1,
                          batch_size=4, server_batch_size=2, lr=3e-3,
                          lr_decay=1.0, masked_compute="kernel")
    model = LM(ModelConfig(name="dense-tiny", family="dense", rope="1d",
                           norm="rmsnorm", act="silu", param_dtype="float32",
                           remat="none", num_layers=1, d_model=256,
                           num_heads=2, num_kv_heads=2, d_ff=1024,
                           vocab_size=2048))
    return model, LocalScanBackend(model, data, cfg, use_masks=True)


def traced(step) -> dict:
    """``phases.events`` of one call of ``step`` under the profiler."""
    d = tempfile.mkdtemp(prefix="phases_trace_")
    try:
        trace.start(d)
        step()
        return phases.events(trace.stop(d))
    finally:
        shutil.rmtree(d, ignore_errors=True)


def compact(rec: dict) -> dict:
    """The chunk run's ops and what reading them needs, times from the
    first host span's start."""
    lo = min(h[1] for h in rec["host"])
    run = next(r for r in rec["runs"] if r[0].startswith(phases.CHUNK + "("))
    a, b = run[1], run[1] + run[2]
    dev = [[r[0], r[1] - lo, r[2], ""] for r in rec["device"]
           if r[1] < b and r[1] + r[2] > a]
    used = {r[0] for r in dev}
    table = rec["scopes"][run[0]]
    return {"device": dev,
            "host": [[h[0], h[1] - lo, h[2]] for h in rec["host"]],
            "runs": [[r[0], r[1] - lo, r[2]] for r in rec["runs"]],
            "scopes": {run[0]: {k: v for k, v in table.items()
                                if k in used}},
            "program": [[s[0], s[1] - lo, s[2]] for s in rec["program"]]}


def record_tiny(out: str) -> int:
    import jax

    model, be = tiny_backend()
    box = {"state": be.init_state(model.init(jax.random.key(0))),
           "key": jax.random.key(1)}

    def step():
        with jax.profiler.TraceAnnotation("chunk"):
            box["state"], box["key"], _ = be.run_chunk(box["state"],
                                                       box["key"], ROUNDS)
        with jax.profiler.TraceAnnotation("eval"):
            float(be.evaluate(box["state"])[0])

    step()                              # compile
    rec = compact(traced(step))
    text = json.dumps(rec, separators=(",", ":")) + "\n"
    pathlib.Path(out).write_text(text)
    red = phases.reduce(rec, ROUNDS)
    print(json.dumps({"bytes": len(text), "device_ops": len(rec["device"]),
                      "per_round": red.per_round, **red.notes()}))
    return 0


def record_cell(workload: str, seed: int) -> int:
    bench = core.benchmark()
    cell = core.cell(bench, workload)
    sizes, cfgmod = core.config(cell["config"])
    t = core.traffic(cell["traffic"])
    mix = core.driver(t["driver"])
    core.device_record(cell["chips"])
    core.enable_compile_cache()
    training = mix.Training(mix.Inputs(sizes, cfgmod, t, seed))
    for _ in range(mix.SETUP_STEPS):
        training.step()
    rec = traced(training.step)
    training.free()
    red = phases.reduce(rec, t["chunk"])
    base = trace.reduce(rec, window=mix._span_window(rec))
    print(json.dumps({
        "workload": workload, "seed": seed,
        "per_round": red.per_round if red else None,
        "notes": red.notes() if red else None,
        "window_s": base.window_s, "busy_s": base.busy_s,
        "program": rec["program"]}))
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--out")
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    core.add_program_path()
    if args.workload:
        return record_cell(args.workload, args.seed)
    if not args.out:
        ap.error("give --out or --workload")
    core.device_record(1)
    return record_tiny(args.out)


if __name__ == "__main__":
    sys.exit(main())
