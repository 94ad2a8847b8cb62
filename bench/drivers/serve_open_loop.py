"""Serving under open-loop traffic: requests arrive on a schedule drawn
from the seed, whatever the engine's state, and each is timed from its
scheduled arrival.

The schedule: ``rate_per_s`` Poisson arrivals and lognormal prompt
lengths.  Every seed gets the same set of gaps and lengths (quantiles at
evenly spaced probabilities) in its own order, with its own token ids,
so seeds change the order of the work and not its amount.

The engine hands tokens to the host only at the end of a wave, so a
token's time is the end of the wave that produced it.  The harness
reckons which wave that is from its own bookkeeping of the engine's
protocol (first-in first-out admission into free slots at the start of a
wave, one prompt token per step, then one generated token per step), and
checks it against the wave each request actually completes in.
"""
from __future__ import annotations

import dataclasses
import gc
import math
import statistics
import time

import numpy as np

from bench import core, trace as tr

DRAIN_LIMIT_S = 120.0   # how long the drain after the window may last
TRACE_AT = (15.0, 3.0)  # a --trace 1 run traces (from second, for seconds)
SAMPLE_TOKENS = 256     # served tokens compared with the reference, at least


# ---------------------------------------------------------------------------
# the schedule
# ---------------------------------------------------------------------------

def schedule(t: dict, seconds: float, seed: int, vocab: int):
    """(arrival seconds [n], prompts [n]) for a window of ``seconds``."""
    rate = float(t["rate_per_s"])
    n = int(math.ceil(rate * seconds * 1.25)) + 8
    q = (np.arange(n) + 0.5) / n
    p = t["prompt"]
    z = np.array([statistics.NormalDist().inv_cdf(v) for v in q])
    lengths = np.clip(np.round(p["median"] * np.exp(p["sigma"] * z)),
                      p["min"], p["max"]).astype(int)
    gaps = -np.log1p(-q) / rate
    rng = np.random.default_rng(seed)
    lengths = lengths[rng.permutation(n)]
    arrivals = np.cumsum(gaps[rng.permutation(n)])
    prompts = [rng.integers(0, vocab, int(k)).astype(np.int32)
               for k in lengths]
    return arrivals, prompts


# ---------------------------------------------------------------------------
# the window
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Request:
    uid: int
    arrival: float            # scheduled, seconds from the window start
    prompt_len: int
    submitted: float = math.nan
    admit_wave: int = -1
    tokens: np.ndarray | None = None
    status: str = "pending"


@dataclasses.dataclass
class Loop:
    requests: list
    wave_ends: list           # seconds from the window start
    window_s: float
    drain_s: float
    late_s: float             # how late the generator ran, at worst
    mismatched: int           # completions in another wave than reckoned
    traced_waves: tuple = ()  # (first, last + 1) wave indices traced
    backlog: list = dataclasses.field(default_factory=list)
    # (seconds, queued, in flight) at the start of each wave

    def first_wave(self, r: Request, spw: int) -> int:
        return r.admit_wave + (r.prompt_len - 1) // spw

    def token_wave(self, r: Request, k: int, spw: int) -> int:
        return r.admit_wave + (r.prompt_len - 1 + k) // spw


def run_window(engine, arrivals, prompts, *, seconds: float, drain_limit:
               float, trace_at=None, on_trace=None) -> Loop:
    """Offer the schedule for ``seconds``, then drain.  ``trace_at``
    (start, length) in seconds asks ``on_trace("start"|"stop")`` to be
    called at wave boundaries around that part of the window."""
    import jax

    cfg = engine.cfg
    spw, slots = cfg.steps_per_wave, cfg.slots
    reqs: list = []
    by_uid: dict = {}
    fifo: list = []
    inflight = 0
    wave_ends, backlog = [], []
    mismatched, late = 0, 0.0
    traced = [None, None]
    nxt = 0
    t0 = time.perf_counter()
    window_end = None
    while True:
        now = time.perf_counter() - t0
        open_ = now < seconds
        if not open_ and window_end is None:
            window_end = now
        if open_:
            with jax.profiler.TraceAnnotation("arrivals"):
                while nxt < len(arrivals) and arrivals[nxt] <= now:
                    r = Request(len(reqs), float(arrivals[nxt]),
                                int(prompts[nxt].shape[0]))
                    r.submitted = time.perf_counter() - t0
                    late = max(late, r.submitted - r.arrival)
                    reqs.append(r)
                    try:
                        uid = engine.submit(prompts[nxt])
                    except Exception:          # QueueFull and the like
                        uid = None
                    if uid is None:
                        r.status = "rejected"
                    else:
                        by_uid[uid] = r
                        fifo.append(r)
                    nxt += 1
        if not open_ and not fifo and inflight == 0:
            break
        if not open_ and now - window_end > drain_limit:
            break
        if not fifo and inflight == 0:
            wait = (arrivals[nxt] if nxt < len(arrivals) else seconds) - now
            time.sleep(max(0.0, min(wait, seconds - now)))
            continue
        w = len(wave_ends)
        if trace_at and traced[0] is None and now >= trace_at[0]:
            on_trace("start")
            traced[0] = w
        backlog.append((now, len(fifo), inflight))
        admitted = fifo[:slots - inflight]
        del fifo[:len(admitted)]
        for r in admitted:
            r.admit_wave = w
        inflight += len(admitted)
        with jax.profiler.TraceAnnotation("wave"):
            done = engine.step_wave()
        we = time.perf_counter() - t0
        wave_ends.append(we)
        with jax.profiler.TraceAnnotation("retire"):
            for c in done:
                r = by_uid[c.uid]
                r.tokens, r.status = c.tokens, c.status
                inflight -= 1
                want = r.admit_wave + (r.prompt_len - 1
                                       + cfg.max_new_tokens - 1) // spw
                if w != want or len(c.tokens) != cfg.max_new_tokens:
                    mismatched += 1
        if (traced[0] is not None and traced[1] is None
                and we >= trace_at[0] + trace_at[1]):
            on_trace("stop")
            traced[1] = w + 1
    if traced[0] is not None and traced[1] is None:
        on_trace("stop")
        traced[1] = len(wave_ends)
    end = time.perf_counter() - t0
    window_end = seconds if window_end is None else window_end
    return Loop(reqs, wave_ends, seconds, end - window_end,
                late, mismatched,
                tuple(traced) if traced[0] is not None else (), backlog)


def summarize(loop: Loop, cfg) -> dict:
    """attempted, failed and the end-to-end metrics of a window."""
    spw, n_new = cfg.steps_per_wave, cfg.max_new_tokens
    ttft, tpot, tokens = [], [], 0
    failed = 0
    for r in loop.requests:
        if r.status != "ok":
            failed += 1
            continue
        t_first = loop.wave_ends[loop.first_wave(r, spw)]
        t_last = loop.wave_ends[loop.token_wave(r, n_new - 1, spw)]
        ttft.append((t_first - r.arrival) * 1e3)
        if n_new > 1:
            tpot.append((t_last - t_first) / (n_new - 1) * 1e3)
    for r in loop.requests:
        if r.admit_wave < 0:
            continue
        for k in range(n_new):
            w = loop.token_wave(r, k, spw)
            if w < len(loop.wave_ends) and loop.wave_ends[w] <= loop.window_s:
                tokens += 1
    failed += loop.mismatched
    return {"attempted": len(loop.requests), "failed": failed,
            "e2e": {"ttft_p95_ms": core.p95(ttft),
                    "tpot_p95_ms": core.p95(tpot),
                    "serve_tokens_per_s": tokens / loop.window_s},
            "notes": {"requests": len(loop.requests), "waves":
                      len(loop.wave_ends), "drain_s": loop.drain_s,
                      "generator_late_s": loop.late_s,
                      "mismatched": loop.mismatched,
                      "ttft_median_ms": statistics.median(ttft)
                      if ttft else math.nan,
                      "tpot_median_ms": statistics.median(tpot)
                      if tpot else math.nan}}


def slot_contexts(loop: Loop, cfg, wave: int) -> list:
    """For each step of ``wave``, the contexts (positions attended) of the
    slots whose request is still running at that step."""
    spw, n_new = cfg.steps_per_wave, cfg.max_new_tokens
    steps = [[] for _ in range(spw)]
    for r in loop.requests:
        if r.admit_wave < 0 or r.admit_wave > wave:
            continue
        last = r.prompt_len - 1 + n_new - 1       # its last step index
        for j in range(spw):
            g = (wave - r.admit_wave) * spw + j
            if g <= last:
                steps[j].append(g + 1)
    return steps


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

def seeds(seed: int) -> dict:
    ss = np.random.SeedSequence(seed)
    kept, traffic, rest = ss.spawn(3)
    return {"kept": np.random.default_rng(kept),
            "traffic": int(np.random.default_rng(traffic).integers(2 ** 62)),
            "params": int(np.random.default_rng(rest).integers(2 ** 31 - 1)),
            "sample": np.random.default_rng(rest)}


class Serving:
    """The served checkpoint and its engine for one seed, warmed up."""

    def __init__(self, sizes: dict, cfgmod, t: dict, seed: int):
        import jax
        from repro.serving import DecodeEngine, ServeConfig

        self.sizes, self.cfgmod, self.t = sizes, cfgmod, t
        self.s = seeds(seed)
        params = cfgmod.init_params(sizes, jax.random.key(self.s["params"]))
        self.kept = cfgmod.kept_units(sizes, sizes["fedap"]["rate"],
                                      sizes["fedap"]["align"], self.s["kept"])
        sv = cfgmod.servable(sizes, params, self.kept)
        self.cfg = ServeConfig(**t["engine"])
        self.engine = DecodeEngine(sv.model, sv.params, self.cfg,
                                   masks=sv.masks)
        del params, sv
        self.built_at = time.perf_counter()
        self.engine.run([np.arange(t["prompt"]["min"], dtype=np.int32)])

    def free(self) -> None:
        self.engine = None
        gc.collect()


def sample(loop: Loop, rng: np.random.Generator, tokens: int) -> list:
    """Completed requests to compare: the longest, then others drawn from
    the seed until ``tokens`` served tokens are in the sample."""
    ok = [r for r in loop.requests if r.status == "ok"]
    if not ok:
        return []
    ok.sort(key=lambda r: (-(r.prompt_len + len(r.tokens)), r.uid))
    pick, rest = [ok[0]], ok[1:]
    order = rng.permutation(len(rest))
    n = len(ok[0].tokens)
    for i in order:
        if n >= tokens:
            break
        pick.append(rest[i])
        n += len(rest[i].tokens)
    return pick


def served_gaps(cfgmod, params, fmask, prompts: dict, picked: list,
                length: int, *, precision: str = "f32",
                ranked_by: str | None = None) -> list:
    """For each picked request, the widest gap by which a served token's
    reference logit lies below the reference's best at its position.
    ``ranked_by`` (the control) reads, at each position, the gap of the
    token that the ``ranked_by`` precision puts first instead."""
    import jax
    import jax.numpy as jnp

    fwd = jax.jit(lambda p, x, m, prec: cfgmod.logits(p, x, m, prec),
                  static_argnums=(3,))
    gaps = []
    for r in picked:
        seq = np.concatenate([prompts[r.uid], r.tokens[:-1]])
        x = np.zeros((1, length), np.int32)
        x[0, :len(seq)] = seq
        lo = r.prompt_len - 1
        want = fwd(params, jnp.asarray(x), fmask, precision)[0,
                                                            lo:len(seq)]
        if ranked_by is None:
            chosen = jnp.asarray(r.tokens)
        else:
            other = fwd(params, jnp.asarray(x), fmask, ranked_by)[0,
                                                                 lo:len(seq)]
            chosen = jnp.argmax(other, axis=-1)
        best = jnp.max(want, axis=-1)
        got = jnp.take_along_axis(want, chosen[:, None], axis=-1)[:, 0]
        gaps.append(float(jnp.max(best - got)))
    return gaps


def run(ctx) -> dict:
    t = ctx.traffic
    t_driver = time.perf_counter()
    session = Serving(ctx.sizes, ctx.cfgmod, t, ctx.seed)
    t_warm = time.perf_counter()
    dm = ctx.cfgmod.dims(ctx.sizes)
    arrivals, prompts = schedule(t, ctx.seconds, session.s["traffic"],
                                 dm["V"])
    t_end = time.perf_counter()
    setup_s = t_end - ctx.t0
    split = {"imports_and_device": t_driver - ctx.t0,
             "weights_and_engine": session.built_at - t_driver,
             "warm_up": t_warm - session.built_at,
             "schedule": t_end - t_warm}
    compiles = ctx.compiles.total()
    holder = {}

    def on_trace(what):
        if what == "start":
            tr.start(ctx.trace_dir)
        else:
            holder["path"] = tr.stop(ctx.trace_dir)

    loop = run_window(session.engine, arrivals, prompts,
                      seconds=ctx.seconds, drain_limit=DRAIN_LIMIT_S,
                      trace_at=TRACE_AT if ctx.trace else None,
                      on_trace=on_trace)
    in_window = ctx.compiles.total() - compiles
    out = summarize(loop, session.cfg)
    mem = ctx.memory_peak()
    layer = None
    if ctx.trace and "path" in holder:
        rec = tr.events(holder["path"])
        a, b = loop.traced_waves
        red = tr.reduce(rec, window=_wave_window(rec))
        layer = {"reduced": red, "dims": dm, "peaks": ctx.peaks,
                 "ff_kept": int(session.kept["mlp"].shape[1]),
                 "contexts": [slot_contexts(loop, session.cfg, w)
                              for w in range(a, b)]}
    session.free()
    import jax

    params = ctx.cfgmod.init_params(ctx.sizes,
                                    jax.random.key(session.s["params"]))
    fmask = ctx.cfgmod.filter_rows(ctx.sizes, session.kept)
    picked = sample(loop, session.s["sample"], SAMPLE_TOKENS)
    by_uid = {r.uid: prompts[r.uid] for r in picked}
    length = session.cfg.max_prompt + session.cfg.max_new_tokens - 1
    gaps = served_gaps(ctx.cfgmod, params, fmask, by_uid, picked, length)
    worst = max(gaps) if gaps else math.inf
    out.update({"setup_s": setup_s, "compiles_in_window": in_window,
                "memory_peak_bytes": mem, "layer": layer,
                "checks": {"served_logit_gap": (worst,
                                                ctx.limits["served_logit_gap"])}})
    out["notes"].update({"setup_split_s": split,
                         "compared_requests": len(picked),
                         "compared_tokens": int(sum(len(r.tokens)
                                                    for r in picked))})
    return out


def _wave_window(rec: dict):
    host = [h for h in rec["host"] if h[0] == "wave"]
    return (min(h[1] for h in host), max(h[1] + h[2] for h in host))


def control(sizes: dict, cfgmod, t: dict, seed: int, seconds: float) -> dict:
    """On one seed: a short window at the cell's own load, then the
    served-token gap of the program (its lower reading), of the control
    (the token that the reference in float8 puts first, read in the
    float32 reference) and of a planted fault (one served token of the
    longest request altered), over the same sample a run compares."""
    import jax

    session = Serving(sizes, cfgmod, t, seed)
    arrivals, prompts = schedule(t, seconds, session.s["traffic"],
                                 cfgmod.dims(sizes)["V"])
    loop = run_window(session.engine, arrivals, prompts, seconds=seconds,
                      drain_limit=DRAIN_LIMIT_S)
    session.free()
    params = cfgmod.init_params(sizes, jax.random.key(session.s["params"]))
    fmask = cfgmod.filter_rows(sizes, session.kept)
    picked = sample(loop, session.s["sample"], SAMPLE_TOKENS)
    by_uid = {r.uid: prompts[r.uid] for r in picked}
    length = session.cfg.max_prompt + session.cfg.max_new_tokens - 1
    out = {"program": max(served_gaps(cfgmod, params, fmask, by_uid, picked,
                                      length)),
           "control_fp8": max(served_gaps(cfgmod, params, fmask, by_uid,
                                          picked, length, ranked_by="fp8"))}
    bad = dataclasses.replace(picked[0], tokens=picked[0].tokens.copy())
    bad.tokens[len(bad.tokens) // 2] = (bad.tokens[len(bad.tokens) // 2] + 1
                                        ) % cfgmod.dims(sizes)["V"]
    out["token_altered"] = max(served_gaps(cfgmod, params, fmask, by_uid,
                                           [bad], length))
    out["compared_tokens"] = int(sum(len(r.tokens) for r in picked))
    return out
