#!/usr/bin/env python3
"""Readings that set a cell's limits: the control and the planted faults.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 [--seconds 15]

For each seed it prints one JSON line with the numbers the cell compares,
read for the control (the plain reference computed in float8, the step
below the configuration's bfloat16, put in the program's place) and for
each fault the driver plants.  A limit lies above the program's readings
(from the benchmark's own runs) and below these.  Run on the chip at the
cell's own size; the benchmark's runs do not run it.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from bench import core  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    args = ap.parse_args(argv)
    cell = core.cell(core.benchmark(), args.workload)
    sizes, cfgmod = core.config(cell["config"])
    t = core.traffic(cell["traffic"])
    core.add_program_path()
    device = core.device_record(cell["chips"])
    core.enable_compile_cache()
    drv = core.driver(t["driver"])
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        out = drv.control(sizes, cfgmod, t, seed, args.seconds)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "device": device["kind"], **out,
                          "wall_s": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
