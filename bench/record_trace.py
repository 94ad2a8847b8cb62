#!/usr/bin/env python3
"""Record the small chip trace that the reducer's test reads.

    python3 bench/record_trace.py --out bench/testdata/trace_v5e.json

On a TPU it runs the program's masked matmul forward and backward, its
flash-decode kernel and a plain matmul at small shapes, each under one of
the harness's host spans (``chunk``, ``eval``, ``wave``) with idle time
between them, traces that, and writes the compact record of
``bench.trace.events`` (only the events inside the spans).  ``--dump``
also writes a listing of the raw trace's planes, lines and a few events
with their stats (and every event that names a program run), to look at how the device names its operations.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import tempfile
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from bench import core, trace  # noqa: E402


def dump(path: str, out: pathlib.Path) -> None:
    from jax.profiler import ProfileData

    lines = []
    for plane in ProfileData.from_file(path).planes:
        lines.append(f"plane {plane.name!r}")
        for ln in plane.lines:
            evs = list(ln.events)
            lines.append(f"  line {ln.name!r}: {len(evs)} events")
            for i, e in enumerate(evs):
                stats = {k: str(v)[:160] for k, v in dict(e.stats).items()}
                if i < 12 or "run_id" in stats:
                    lines.append(f"    {e.name!r} start {e.start_ns} dur "
                                 f"{e.duration_ns} {stats}")
    out.write_text("\n".join(lines) + "\n")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--out", required=True)
    ap.add_argument("--dump")
    args = ap.parse_args(argv)
    core.add_program_path()
    core.device_record(1)
    import jax
    import jax.numpy as jnp
    from repro.kernels.decode_attention import decode_attention
    from repro.kernels.masked_matmul import masked_matmul

    ks = jax.random.split(jax.random.key(0), 6)
    x = jax.random.normal(ks[0], (256, 512), jnp.bfloat16)
    w = jax.random.normal(ks[1], (512, 1024), jnp.bfloat16)
    mask = jnp.array([1, 0, 1, 1, 0, 1, 0, 1], jnp.float32)
    q = jax.random.normal(ks[2], (4, 1, 16, 128), jnp.bfloat16)
    k = jax.random.normal(ks[3], (4, 1024, 16, 128), jnp.bfloat16)
    v = jax.random.normal(ks[4], (4, 1024, 16, 128), jnp.bfloat16)
    lengths = jnp.array([1, 300, 700, 1024], jnp.int32)

    grad = jax.jit(jax.grad(
        lambda x, w: jnp.sum(masked_matmul(x, w, mask).astype(jnp.float32)),
        argnums=(0, 1)))
    fwd = jax.jit(lambda x, w: masked_matmul(x, w, mask) @ w.T)
    dec = jax.jit(lambda q, k, v: decode_attention(q, k, v, lengths))
    calls = (("chunk", lambda: grad(x, w)), ("eval", lambda: fwd(x, w)),
             ("wave", lambda: dec(q, k, v)))
    for _, f in calls:
        jax.block_until_ready(f())
    with tempfile.TemporaryDirectory() as d:
        trace.start(d)
        for name, f in calls:
            time.sleep(0.002)
            with jax.profiler.TraceAnnotation(name):
                jax.block_until_ready(f())
        time.sleep(0.002)
        path = trace.stop(d)
        rec = trace.events(path)
        if args.dump:
            dump(path, pathlib.Path(args.dump))
    lo = min(h[1] for h in rec["host"])
    hi = max(h[1] + h[2] for h in rec["host"])
    rec["device"] = [r for r in rec["device"] if r[1] < hi and r[1] + r[2] > lo]
    pathlib.Path(args.out).write_text(json.dumps(rec) + "\n")
    print(f"{len(rec['device'])} device events, {len(rec['host'])} spans; "
          f"names {sorted({r[0] for r in rec['device']})}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
