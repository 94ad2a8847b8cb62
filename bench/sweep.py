#!/usr/bin/env python3
"""The knee of a serving cell: its traffic offered at several fixed rates
to one warmed engine, one window each, in one process on the chip.

    python3 bench/sweep.py --workload <serve cell> --seed <n> --seconds <s> \
        --rates 2,3,4,5

For each rate it prints the requests offered, the backlog (queued plus in
flight) at the window's middle and end, the tails and the tokens per
second.  The knee is the highest rate whose backlog does not grow through
the window; a cell runs at a fixed share of it, written into its traffic
file.  Used once when a serving cell is added, not by the benchmark's
runs.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from bench import core  # noqa: E402


def backlog_at(loop, t: float) -> int:
    best = None
    for when, queued, inflight in loop.backlog:
        if when <= t:
            best = queued + inflight
    return 0 if best is None else best


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    bench = core.benchmark()
    cell = core.cell(bench, args.workload)
    sizes, cfgmod = core.config(cell["config"])
    t = core.traffic(cell["traffic"])
    core.add_program_path()
    device = core.device_record(cell["chips"])
    core.enable_compile_cache()
    drv = core.driver(t["driver"])
    session = drv.Serving(sizes, cfgmod, t, args.seed)
    vocab = cfgmod.dims(sizes)["V"]
    for rate in (float(r) for r in args.rates.split(",")):
        tr = dict(t, rate_per_s=rate)
        arrivals, prompts = drv.schedule(tr, args.seconds,
                                         session.s["traffic"], vocab)
        t0 = time.perf_counter()
        loop = drv.run_window(session.engine, arrivals, prompts,
                              seconds=args.seconds,
                              drain_limit=drv.DRAIN_LIMIT_S)
        out = drv.summarize(loop, session.cfg)
        print(json.dumps({
            "rate_per_s": rate, "device": device["kind"],
            "offered": out["attempted"], "failed": out["failed"],
            "backlog_mid": backlog_at(loop, args.seconds / 2),
            "backlog_end": backlog_at(loop, args.seconds),
            "drain_s": loop.drain_s, "wall_s": time.perf_counter() - t0,
            **out["e2e"], **{k: out["notes"][k] for k in
                             ("ttft_median_ms", "tpot_median_ms",
                              "generator_late_s")}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
