"""Federated training rounds: the window drives the program's scan chunk
and its eval, as ``PlanExecutor`` does for ``Scan(chunk), Eval()``.

Set-up builds one object (the backend, its compiled chunk and its round
state, with the FedAP masks of the configuration's decision applied),
drives it from the seed through ``SETUP_STEPS`` steps of the window's own
call (the first one compiles), and hands that same object to the window.  The
readings of those steps are what the plain reference is compared with,
once the window has closed and the program's state is freed.
"""
from __future__ import annotations

import gc
import math
import time

import numpy as np

from bench import fl_data, fl_reference, trace as tr

SETUP_STEPS = 2      # window steps of set-up; the reference follows them
TRACE_STEPS = (1, 1)  # a --trace 1 run traces (from step, for steps)


def hyper(sizes: dict, t: dict, data: dict) -> dict:
    """The training mix with the sizes that follow from its data."""
    n_k = int(data["client_x"].shape[1])
    n0 = int(data["server_x"].shape[0])
    return {**t,
            "local_steps": max(1, n_k // t["batch_size"]) * t["local_epochs"],
            "tau": max(1, n0 // t["server_batch_size"]),
            "row_shape": tuple(int(n) for n in data["client_x"].shape[2:]),
            "test_rows": int(data["test_x"].shape[0])}


def seeds(seed: int) -> dict:
    """Independent streams for each part of a run, from one ``--seed``."""
    ss = np.random.SeedSequence(seed)
    data, kept, rest = ss.spawn(3)
    keys = np.random.default_rng(rest).integers(0, 2 ** 31 - 1, 2)
    return {"data": np.random.default_rng(data),
            "kept": np.random.default_rng(kept),
            "params": int(keys[0]), "chain": int(keys[1])}


class Inputs:
    """What one seed makes for a training cell: the federated data, the
    FedAP kept units (where the configuration prunes), the mix's sizes, and the
    seeds of the weights and of the round key chain."""

    def __init__(self, sizes: dict, cfgmod, t: dict, seed: int):
        self.sizes, self.cfgmod, self.t = sizes, cfgmod, t
        s = seeds(seed)
        self.params_seed, self.chain_seed = s["params"], s["chain"]
        self.data = fl_data.federation(t, cfgmod.dims(sizes), s["data"])
        self.kept = self.fmask = None
        if sizes.get("fedap"):
            self.kept = cfgmod.kept_units(sizes, sizes["fedap"]["rate"],
                                          sizes["fedap"]["align"], s["kept"])
            self.fmask = cfgmod.filter_rows(sizes, self.kept)
        self.hp = hyper(sizes, t, self.data)

    def params(self):
        import jax

        return self.cfgmod.init_params(self.sizes,
                                       jax.random.key(self.params_seed))


class Training:
    """The program's training object for one seed (everything the window
    calls), built in set-up."""

    def __init__(self, inputs: Inputs):
        import jax
        from repro.core.backend import LocalScanBackend
        from repro.core.momentum import FedDUMConfig
        from repro.core.rounds import feddumap_config
        from repro.core.server_update import FedDUConfig
        from repro.data.pipeline import FederatedData

        self.inputs = inp = inputs
        t = inp.t
        fl = feddumap_config(
            num_clients=t["clients"],
            clients_per_round=t["clients_per_round"],
            local_epochs=t["local_epochs"], batch_size=t["batch_size"],
            server_batch_size=t["server_batch_size"], lr=t["lr"],
            lr_decay=t["lr_decay"], seed=inp.chain_seed,
            local_momentum=t["local_momentum"],
            server_momentum=t["server_momentum"],
            masked_compute=t["masked_compute"],
            feddu=FedDUConfig(**t["feddu"]), feddum=FedDUMConfig(**t["feddum"]))
        self.model = inp.cfgmod.train_model(inp.sizes)
        got = jax.tree.structure(jax.eval_shape(self.model.init,
                                                jax.random.key(0)))
        params = inp.params()
        if jax.tree.structure(params) != got:
            raise ValueError(f"builder tree {jax.tree.structure(params)} is "
                             f"not the program's {got}")
        fed = FederatedData(**inp.data)
        masked = inp.kept is not None
        self.backend = LocalScanBackend(self.model, fed, fl, use_masks=masked)
        self.state = self.backend.init_state(params)
        del params
        if masked:
            self.state, _ = self.backend.apply_prune(self.state, "mask",
                                                     inp.kept)
        self.key = jax.random.key(inp.chain_seed)

    def start_params(self):
        """The initial parameters, masked where the mix prunes (made again
        from the seed)."""
        inp = self.inputs
        if inp.fmask is None:
            return inp.params()
        return inp.cfgmod.mask_params(inp.params(), inp.fmask)

    def step(self):
        """One window step: a chunk of rounds, then the eval.  Returns the
        eval loss and the chunk's per-round guard rejections."""
        import jax

        with jax.profiler.TraceAnnotation("chunk"):
            self.state, self.key, mets = self.backend.run_chunk(
                self.state, self.key, self.inputs.t["chunk"])
        with jax.profiler.TraceAnnotation("eval"):
            loss, _ = self.backend.evaluate(self.state)
            loss = float(loss)
        return loss, np.asarray(mets["health"])

    def free(self) -> None:
        from repro.core.backend import clear_compiled_cache

        self.state = self.backend = self.model = None
        clear_compiled_cache()
        gc.collect()


def count_failed(loss: float, health) -> int:
    """Rounds of a step that failed: all of them on a non-finite loss,
    else those the guard rejected."""
    if not math.isfinite(loss):
        return len(health)
    return int(np.sum(np.asarray(health) > 0))


def setup_readings(tr_obj: Training) -> dict:
    """Drive the object through ``SETUP_STEPS`` window steps and take the
    readings the reference is compared with."""
    read = {"loss": []}
    for i in range(SETUP_STEPS):
        loss, _ = tr_obj.step()
        read["loss"].append(loss)
        if i == 0:
            read["first_m"] = fl_reference.host(
                fl_reference.leaf_norms(tr_obj.state["server_m"]))
    start = tr_obj.start_params()
    read["change"] = fl_reference.host(
        fl_reference.change_norms(tr_obj.state["params"], start))
    del start
    return read


def reference_readings(inp: Inputs, *, precision: str | None = None,
                       half_batch: bool = False) -> dict:
    """The plain reference's readings over the same steps, from the same
    seed (params, masks, data, key chain), in the precision the
    configuration states (its ``REFERENCE_PRECISION``, else float32).
    ``precision="fp8"`` is the control; ``half_batch`` plants a fault in
    the reference."""
    import jax
    import jax.numpy as jnp

    if precision is None:
        precision = getattr(inp.cfgmod, "REFERENCE_PRECISION", "f32")
    params = inp.params()
    ref = fl_reference.Reference(inp.cfgmod.loss_and_acc,
                                 getattr(inp.cfgmod, "mask_params", None),
                                 inp.fmask, inp.hp,
                                 param_dtype=jax.tree.leaves(params)[0].dtype,
                                 precision=precision, half_batch=half_batch)
    data_dev = {k: jnp.asarray(v) for k, v in inp.data.items()}
    return fl_reference.run_reference(ref, params, data_dev,
                                      jax.random.key(inp.chain_seed),
                                      inp.hp, SETUP_STEPS)


def run(ctx) -> dict:
    """Set-up, the window, the trace if asked, the reference check."""
    t = ctx.traffic
    marks = [time.perf_counter()]
    inputs = Inputs(ctx.sizes, ctx.cfgmod, t, ctx.seed)
    marks.append(time.perf_counter())
    session = Training(inputs)
    marks.append(time.perf_counter())
    got = setup_readings(session)
    marks.append(time.perf_counter())
    setup_s = marks[-1] - ctx.t0
    split = dict(zip(("imports_and_device", "data", "weights_and_state",
                      "setup_steps"),
                     (b - a for a, b in zip([ctx.t0] + marks, marks))))
    compiles = ctx.compiles.total()

    attempted = failed = 0
    traced = None
    steps = 0
    trace_from, trace_steps = TRACE_STEPS
    t_start = time.perf_counter()
    while True:
        tracing = ctx.trace and steps == trace_from
        if tracing:
            tr.start(ctx.trace_dir)
        loss, health = session.step()
        steps += 1
        attempted += len(health)
        failed += count_failed(loss, health)
        if ctx.trace and steps == trace_from + trace_steps:
            path = tr.stop(ctx.trace_dir)
            traced = path
        elapsed = time.perf_counter() - t_start
        if elapsed >= ctx.seconds and (not ctx.trace or traced):
            break
    in_window = ctx.compiles.total() - compiles
    rounds = steps * t["chunk"]
    mem = ctx.memory_peak()
    dm = ctx.cfgmod.dims(ctx.sizes)
    layer = None
    if traced:
        rec = tr.events(traced)
        red = tr.reduce(rec, window=_span_window(rec))
        layer = {"reduced": red, "dims": dm, "hp": inputs.hp,
                 "kept": inputs.kept, "rounds": trace_steps * t["chunk"],
                 "evals": trace_steps, "peaks": ctx.peaks,
                 "flops": ctx.cfgmod.train_flops(ctx.sizes, inputs.hp,
                                                 inputs.kept)}
    session.free()
    want = reference_readings(inputs)
    cmp = fl_reference.compare(got, want)
    lim = ctx.limits
    return {
        "setup_s": setup_s, "compiles_in_window": in_window,
        "attempted": attempted, "failed": failed,
        "e2e": {"round_s": elapsed / rounds},
        "memory_peak_bytes": mem, "layer": layer,
        "checks": {k: (cmp[k], lim[k]) for k in CHECKS},
        "notes": {"setup_split_s": split,
                  "rounds": rounds, "window_s": elapsed, "steps": steps,
                  "where": cmp["where"], "program": got, "reference": want},
    }


def _span_window(rec: dict):
    """The traced window: from the first traced chunk span's start to the
    last eval span's end."""
    host = [h for h in rec["host"] if h[0] in ("chunk", "eval")]
    return (min(h[1] for h in host), max(h[1] + h[2] for h in host))


CHECKS = ("loss_rel_gap", "first_m_gap", "change_gap")


def control(sizes: dict, cfgmod, t: dict, seed: int, seconds: float) -> dict:
    """On one seed, the cell's numbers for the program (its set-up steps,
    as a run takes them), for the control (the reference in float8) and
    for a planted fault (every step's mean over half its batch), each
    against the reference.  A training cell's readings need no
    window: ``seconds`` is unused."""
    inputs = Inputs(sizes, cfgmod, t, seed)
    session = Training(inputs)
    got = setup_readings(session)
    session.free()
    want = reference_readings(inputs)
    out = {"program": fl_reference.compare(got, want)}
    for name, kw in (("control_fp8", {"precision": "fp8"}),
                     ("half_batch", {"half_batch": True})):
        out[name] = fl_reference.compare(reference_readings(inputs, **kw),
                                         want)
    return {k: {c: v[c] for c in CHECKS + ("where",)} for k, v in out.items()}
