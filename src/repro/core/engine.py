"""The unified federated round engine — ONE implementation of the paper's
round (steps 1-5 of Section 3.1), shared by every execution path.

``round_core`` is a pure function of (config, model fns, state, batch) and is
safe under ``jit``, ``lax.scan``, ``vmap`` and ``shard_map``:

  * the simulation driver (`repro.core.rounds.FederatedTrainer`) scans it
    over rounds, with client selection and batch sampling done ON DEVICE
    through `jax.random` keys threaded in the scan carry — no host sync;
  * the pod-scale SPMD path (`repro.launch.steps.make_fl_train_step`) wraps
    it once per mesh program and shard_maps it via `sharding/fl_specs.py`.

Model access is abstracted to two callables over an opaque batch pytree:

  grad_fn(params, batch)          -> grads            (local/server SGD)
  loss_and_acc_fn(params, batch)  -> (loss, acc)      (Formula-7 acc gate)

The Formula-7 accuracy gate is taken from the FIRST server step's own
forward (``value_and_grad`` with aux) rather than a separate evaluation
pass over the full server set — one server-batch forward saved per round
(§Perf iteration B2).  The pure-NumPy oracle in `repro.core.ref_engine`
implements the same semantics naively and is the differential-test target.

Nothing here is sharding-aware by construction: under the MeshBackend the
client dim of ``batch["client"]`` AND the per-step batch dim of
``batch["server"]`` arrive sharding-constrained
(`sharding.fl_specs.fl_sim_batch_specs`), so the local-epoch vmap, the
FedAvg einsum and every one of the (5a) server-SGD steps partition over
the mesh with GSPMD-inserted collectives — the scan below compiles to
per-shard partial gradients + one all-reduce per step, with this source
unchanged (locked against the f64 oracle, first-step acc gate included).

Round state is a dict ``{"params", "server_m", ["global_m"], ["masks"],
["client_state"], "round"}``; ``global_m`` is present only for
``local_momentum == "communicated"`` (FedDA), where the globally-aggregated
momentum buffer is broadcast back to the devices (2x communication — the
baseline FedDUM's restart removes).

``client_state`` (present iff ``cfg.algorithm != "fedavg"``) is the
per-client persistent slot of the heterogeneity-robust client algorithms —
the carry structure is keyed by ``cfg.algorithm`` and FIXED from round 0,
so prune events and chunk caching never re-trace:

  "fedprox"  {"per_client": {}, "shared": {}} — FedProx is stateless (the
             proximal pull ``mu * (theta - theta_global)`` needs only the
             broadcast round-start params), but the slot exists so the
             plumbing (sharding specs, mask scrub, shrink reset) is
             uniform across algorithms;
  "feddyn"   {"per_client": {"h": [N, ...] per param},
              "shared":     {"h": param tree}} — the ALPHA-SCALED FedDyn
             gradient-correction state.  We store h'_k = alpha * h_k (and
             the server average likewise), so the local gradient is
             ``g + alpha (theta - theta_global) - h'_k``, the update is
             ``h'_k <- h'_k - alpha (theta_k^end - theta_global)`` and the
             server correction divides back: ``w_half - h'/alpha`` (a
             static python branch — skipped entirely at alpha == 0, where
             h' is identically zero and the round is bit-exact FedAvg).

The FedAvg reduction supports a straggler/dropout axis: when the batch
carries ``"active"`` ([C] 0/1), dropped clients contribute ZERO weight and
the aggregation runs in DELTA form around the broadcast point
(``base + sum_k w_k (theta_k - base)``) so an all-dropped round is exactly
a no-op; dropped clients' FedDyn state is left untouched (their correction
term is multiplied by ``active``).  Without ``"active"`` the legacy direct
einsum is used, bit-identical to the pre-dropout engine.

``masks`` (present iff ``cfg.use_masks``) is a param-structured 0/1 pytree
that rides in the scan carry: every round the engine multiplies params,
gradients, and momentum buffers by it, so FedAP's static-shape mask mode
(``repro.core.plan.Prune(mode="mask")``) prunes INSIDE a live compiled
scan — no shape change, no re-jit.  Its leaves are FACTORED: each keeps
only the axes along which FedAP can zero its parameter and holds every
other axis at size 1 (the FFN stack's ``wi``/``wg`` [L, 1, d_ff] and
``wo`` [L, d_ff, 1]; a never-pruned leaf ``(1,) * ndim``), so ``x * m``
broadcasts and no round pass reads a parameter-sized mask.  The shapes
come from the model's pruning seam (``pruning.param_masks``,
``pruning_lm.ffn_param_masks``), not from a decision, so they are final
from round 0.  With all-ones masks the round is bit-for-bit the unmasked
round, so the masked engine can be compiled once up front and the prune
event only swaps the carry contents.

``cfg.masked_compute`` selects HOW the masked round computes:

  "params"  (default) the mask is applied to the parameter tree only —
            every matmul still runs at full density (correct, but none of
            FedAP's FLOP savings are realized during training);
  "kernel"  filter-level keep-masks (``pruning.filter_masks``) ride in the
            carry as ``state["filter_masks"]`` alongside the param masks,
            and the model fns are called as ``grad_fn(params, batch,
            filter_masks)`` / ``loss_and_acc_fn(params, batch,
            filter_masks)`` — the model routes masked dense layers through
            the differentiable Pallas ``masked_matmul`` kernel (custom
            VJP), so pruned blocks are skipped on the MXU in BOTH the
            forward and the backward pass.  The param masks still multiply
            params/grads/momentum every round, keeping aggregation and
            momentum semantics identical to "params" mode (differentially
            tested to <= 1e-5 on norm-free models).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.core.momentum import (
    FedDUMConfig,
    server_momentum_step,
    server_pseudo_gradient,
)
from repro.core.server_update import FedDUConfig, feddu_apply, tau_eff


@dataclasses.dataclass(frozen=True)
class FedProxConfig:
    """FedProx's proximal term: local grad = g + mu * (theta - theta_global).
    mu = 0 is bit-identical to FedAvg (the term multiplies to exact zero)."""

    mu: float = 0.01

    def __post_init__(self):
        if self.mu < 0:
            raise ValueError(f"FedProx mu must be >= 0, got {self.mu}")


@dataclasses.dataclass(frozen=True)
class FedDynConfig:
    """FedDyn's dynamic regularizer (alpha-scaled parameterization — see the
    module docstring).  alpha = 0 reduces to FedAvg within float identity:
    the correction state stays exactly zero and the server division is a
    static python branch that never enters the graph."""

    alpha: float = 0.01

    def __post_init__(self):
        if self.alpha < 0:
            raise ValueError(f"FedDyn alpha must be >= 0, got {self.alpha}")


ALGORITHMS = ("fedavg", "fedprox", "feddyn")

GUARD_MODES = ("off", "reject_client", "skip_round")

# The named scopes of one scan iteration, in program order.  Each compiled
# instruction's ``op_name`` metadata carries the scope of the phase whose
# work it does, so a device trace splits a round's time by phase:
#   fl_sample           device-side client selection and batch gather
#                       (``backend.build_chunk``)
#   fl_client_train     step (2): the local epochs of every selected client
#   fl_aggregate        fault injection, health guard, FedAvg, FedDyn
#   fl_server_update    step (5a): the FedDU server scan and its guard
#   fl_server_momentum  step (5b): FedDUM, the new state's masks, the
#                       round-discard select
# The scopes are metadata only: they change no arithmetic.
ROUND_PHASES = ("fl_sample", "fl_client_train", "fl_aggregate",
                "fl_server_update", "fl_server_momentum")


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Algorithm switches of the unified round — covers FedAvg / FedDU /
    FedDUM / FedDA / FedDUMAP (FedAP prunes BETWEEN rounds; see rounds.py),
    plus the heterogeneity-robust client algorithms (FedProx / FedDyn)."""

    lr: float = 0.1                 # eta: local AND server SGD step size
    lr_decay: float = 1.0           # per-round geometric decay (paper 4.1)
    use_server_update: bool = True  # FedDU (Formulas 4-7)
    local_momentum: str = "none"    # none | restart | communicated
    server_momentum: bool = False   # FedDUM server SGDM (Formulas 8/12)
    use_masks: bool = False         # static-shape FedAP: masks in the carry
    masked_compute: str = "params"  # params | kernel (see module docstring)
    algorithm: str = "fedavg"       # fedavg | fedprox | feddyn
    guard: str = "off"              # off | reject_client | skip_round
    faults: tuple = ()              # test-only device-fault injection
    feddu: FedDUConfig = dataclasses.field(default_factory=FedDUConfig)
    feddum: FedDUMConfig = dataclasses.field(default_factory=FedDUMConfig)
    fedprox: FedProxConfig = dataclasses.field(default_factory=FedProxConfig)
    feddyn: FedDynConfig = dataclasses.field(default_factory=FedDynConfig)

    def __post_init__(self):
        if self.local_momentum not in ("none", "restart", "communicated"):
            raise ValueError(f"unknown local_momentum: {self.local_momentum}")
        if self.masked_compute not in ("params", "kernel"):
            raise ValueError(
                f"unknown masked_compute: {self.masked_compute!r} "
                "(expected 'params' or 'kernel')")
        if self.algorithm not in ALGORITHMS:
            raise ValueError(f"unknown algorithm: {self.algorithm!r} "
                             f"(expected one of {ALGORITHMS})")
        if self.guard not in GUARD_MODES:
            raise ValueError(f"unknown guard: {self.guard!r} "
                             f"(expected one of {GUARD_MODES})")
        for f in self.faults:
            if not hasattr(f, "apply_client"):
                raise ValueError(
                    f"EngineConfig.faults takes DEVICE faults (objects with "
                    f"an apply_client hook, e.g. reliability.NaNGrad); got "
                    f"{f!r} — host faults like KillAfterChunk belong to the "
                    f"executor (pass them via FLConfig.faults)")


def init_client_state(params: Any, cfg: EngineConfig,
                      num_clients: int | None) -> dict:
    """The algorithm-keyed ``client_state`` subtree (see module docstring).
    Per-client leaves carry a leading [num_clients] dim — the same dim the
    federated dataset leads with, so ``fl_specs.fl_state_specs`` shards
    them over the mesh client axes exactly like the data."""
    if cfg.algorithm == "fedprox":
        return {"per_client": {}, "shared": {}}
    if num_clients is None:
        raise ValueError(
            "algorithm='feddyn' keeps per-client correction state in the "
            "scan carry: pass num_clients=N (the TOTAL client count) to "
            "init_round_state")
    return {
        "per_client": {"h": jax.tree.map(
            lambda p: jnp.zeros((num_clients,) + p.shape, jnp.float32),
            params)},
        "shared": {"h": jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)},
    }


def init_round_state(params: Any, cfg: EngineConfig,
                     filter_masks: Any = None,
                     num_clients: int | None = None,
                     masks: Any = None) -> dict:
    """{"params", "server_m", ["global_m"], ["masks"], ["filter_masks"],
    ["client_state"], "round"} — the scan carry.  Masks start as all-ones
    (a bit-exact no-op round) so a masked engine compiles once and the
    prune event only swaps carry contents.

    ``masks`` (used iff ``cfg.use_masks``) are the model's all-ones
    factored keep-masks (``backend.param_masks_for(model, params, {})``):
    their shapes are the slot's for the whole run, so a decision's masks
    fit it.  Left out, every leaf is ``(1,) * ndim`` — the slot of a
    model that names no prunable axis.

    ``filter_masks`` (required iff ``cfg.masked_compute == "kernel"``) is
    the per-layer {name: [d] 0/1} dict of ``pruning.filter_masks``; its
    pytree STRUCTURE must already be final (all-ones before the prune
    decision), because the prune event may only swap carry contents, never
    the carry structure, without forcing a re-trace.

    ``num_clients`` (required iff ``cfg.algorithm == "feddyn"``) sizes the
    per-client leaves of ``client_state``.
    """
    zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    state = {"params": params, "server_m": zeros,
             "round": jnp.zeros((), jnp.float32)}
    if cfg.local_momentum == "communicated":
        state["global_m"] = jax.tree.map(jnp.copy, zeros)
    if cfg.algorithm != "fedavg":
        state["client_state"] = init_client_state(params, cfg, num_clients)
    if cfg.use_masks:
        # copy, not asarray: the scan chunk donates the state
        state["masks"] = (
            jax.tree.map(lambda p: jnp.ones((1,) * p.ndim, jnp.float32),
                         params) if masks is None else
            jax.tree.map(lambda m: jnp.array(m, jnp.float32), masks))
        if cfg.masked_compute == "kernel":
            if filter_masks is None:
                raise ValueError(
                    "masked_compute='kernel' needs filter_masks in the scan "
                    "carry: pass pruning.filter_masks(params, spec, {}) "
                    "(all-ones) to init_round_state")
            # copy, not asarray: the scan chunk donates the state, and the
            # caller may retain the same mask arrays (prune artifacts)
            state["filter_masks"] = jax.tree.map(
                lambda m: jnp.array(m, jnp.float32), filter_masks)
    return state


def apply_masks(tree: Any, masks: Any) -> Any:
    """Multiply a param-structured pytree by 0/1 keep-masks (dtype kept);
    factored masks broadcast against their leaves (and a leading client
    dim)."""
    return jax.tree.map(lambda x, m: (x * m).astype(x.dtype), tree, masks)


def build_model_fns(cfg: EngineConfig, loss_fn: Callable,
                    la_fn: Callable) -> tuple[Callable, Callable]:
    """The ONE place the kernel-mode model-fn arity is decided — shared by
    the executor backends (``core.backend.model_fns``) and the pod path
    (``launch.steps.make_fl_train_step``) so the 3-arg kernel signature
    cannot drift between them.

    Callers adapt their model to two mask-aware callables over an opaque
    batch:

      loss_fn(params, batch, filter_masks) -> scalar loss
      la_fn(params, batch, filter_masks)   -> (loss, acc)

    (``filter_masks`` is ``None`` outside kernel mode.)  Returns
    ``(grad_fn, loss_and_acc_fn)`` in the arity ``round_core`` expects:
    3-arg ``(params, batch, filter_masks)`` when ``cfg.masked_compute ==
    "kernel"``, else the plain 2-arg ``(params, batch)`` signature.
    """
    if cfg.use_masks and cfg.masked_compute == "kernel":
        def grad_fn(p, b, fm):
            return jax.grad(lambda q: loss_fn(q, b, fm))(p)

        def loss_and_acc_fn(p, b, fm):
            return la_fn(p, b, fm)
    else:
        def grad_fn(p, b):
            return jax.grad(lambda q: loss_fn(q, b, None))(p)

        def loss_and_acc_fn(p, b):
            return la_fn(p, b, None)
    return grad_fn, loss_and_acc_fn


def local_train(cfg: EngineConfig, grad_fn: Callable, params: Any, m0: Any,
                batches: Any, lr, anchor: Any = None,
                h: Any = None) -> tuple[Any, Any]:
    """E local epochs on ONE client (Formula 11 when momentum is on).

    ``batches`` is a pytree with a leading [steps] axis; scanned, so the
    local loop never unrolls into the HLO.

    ``anchor`` is the broadcast round-start global model (the proximal /
    dynamic-regularizer reference point; required for fedprox/feddyn);
    ``h`` is this client's alpha-scaled FedDyn correction (required for
    feddyn), held FIXED over the local epochs.  Both corrections feed the
    momentum recursion like any other gradient term, so they compose with
    every local-momentum mode unchanged.
    """
    use_m = cfg.local_momentum != "none"
    beta = cfg.feddum.beta_local

    def corrected(g, p):
        if cfg.algorithm == "fedprox":
            mu = cfg.fedprox.mu
            return jax.tree.map(
                lambda gi, pi, ai: (gi + mu * (pi - ai)).astype(gi.dtype),
                g, p, anchor)
        if cfg.algorithm == "feddyn":
            alpha = cfg.feddyn.alpha
            return jax.tree.map(
                lambda gi, pi, ai, hi:
                (gi + alpha * (pi - ai) - hi).astype(gi.dtype),
                g, p, anchor, h)
        return g

    def body(carry, batch):
        p, m = carry
        g = corrected(grad_fn(p, batch), p)
        if use_m:
            m = jax.tree.map(
                lambda mi, gi: beta * mi + (1 - beta) * gi.astype(jnp.float32),
                m, g)
            upd = m
        else:
            upd = g
        p = jax.tree.map(lambda pi, u: (pi - lr * u).astype(pi.dtype), p, upd)
        return (p, m), None

    (params, m), _ = jax.lax.scan(body, (params, m0), batches)
    return params, m


def round_core(cfg: EngineConfig, grad_fn: Callable, loss_and_acc_fn: Callable,
               state: dict, batch: dict, *, client_map: Callable | None = None,
               server_map: Callable | None = None) -> tuple[dict, dict]:
    """One full federated round (paper steps 2-5), pure and scan-safe.

    ``client_map`` maps the per-client local training over the leading
    client axis (default ``jax.vmap``); ``server_map`` wraps the FedDU
    server scan ``(w_half, server_batch) -> (w_end, acc)`` (default: run
    it as is).  The mesh backend's kernel mode passes ``shard_map``
    wrappers here: a Mosaic kernel cannot be partitioned by GSPMD, so each
    device must run it on its own shard.

    batch:
      client    pytree, leading dims [C, steps, ...] (per-client batches)
      sizes     [C] f32 n_k
      server    pytree, leading dim [tau, ...] (server SGD batches)
      d_round   D(Pbar'^t) — non-IID degree of this round's selection
      d_server  D(P0)      — non-IID degree of the server data
      n0        scalar f32 — number of server samples
      sel       [C] int32, OPTIONAL — the selected clients' global indices
                (required for algorithm="feddyn": indexes client_state)
      active    [C] 0/1 f32, OPTIONAL — straggler/dropout mask; when
                present the FedAvg reduction runs in delta form and
                dropped clients contribute zero weight (state untouched)

    ``cfg.guard != "off"`` adds the in-scan health guard: every selected
    client's uploaded update (and, for FedDA, its communicated momentum)
    is finiteness-checked on device; non-finite clients are scrubbed back
    to the broadcast point and get exactly-zero aggregation weight through
    the delta-form reduction.  The FedDU server proposal is guarded the
    same way (a non-finite proposed model / tau_eff / acc falls back to
    the aggregated ``w_half``).  Under ``guard="reject_client"`` the round
    proceeds on the survivors; under ``guard="skip_round"`` ANY rejection
    (client or server) discards the whole round — the carry is restored to
    the round-start state with only the round counter advanced, so a bad
    round is exactly a no-op.  All guard branches are keyed on static
    config, and the carry/metrics structure is identical in every mode, so
    turning guards on compiles ZERO additional programs.

    ``cfg.faults`` (test-only) injects deterministic device faults into
    the uploaded updates BEFORE the guard sees them — a static unroll over
    the frozen fault tuple, so the corruption is part of the traced graph
    and fires identically under jit/scan/mesh.

    Returns (new_state, {"tau_eff", "server_acc", "health"}); ``health``
    is the number of guard rejections this round (active clients scrubbed,
    plus 1 if the server step was rejected) — identically 0.0 when the
    guard is off.
    """
    if cfg.use_masks:
        # Static-shape FedAP: params, gradients and momentum are multiplied
        # by the 0/1 keep-masks riding in the carry, every round.  With the
        # coupled-closure masks built by `pruning.param_masks` this equals
        # training the re-materialized model (norm-free archs) at unchanged
        # shapes — the prune round runs inside the compiled scan.
        masks = state["masks"]
        _m = lambda t: apply_masks(t, masks)
        base_grad_fn, base_la_fn = grad_fn, loss_and_acc_fn
        if cfg.masked_compute == "kernel":
            # Filter-level masks thread into the model fns, which route
            # masked dense layers through the differentiable Pallas
            # masked_matmul kernel — pruned blocks are skipped on the MXU
            # in forward AND backward.  The param masks still scrub
            # grads/params/momentum so aggregation semantics are identical
            # to "params" mode.
            fmasks = state["filter_masks"]
            grad_fn = lambda p, b: _m(base_grad_fn(p, b, fmasks))
            loss_and_acc_fn = lambda p, b: base_la_fn(p, b, fmasks)
        else:
            grad_fn = lambda p, b: _m(base_grad_fn(p, b))
    else:
        _m = lambda t: t

    with jax.named_scope("fl_client_train"):
        params = _m(state["params"])
        lr = cfg.lr * (cfg.lr_decay ** state["round"])

        # (2) local epochs, vmapped over the client dim — clients diverge
        # inside the program; there is NO collective over the client axis.
        if cfg.local_momentum == "communicated":
            m0 = _m(state["global_m"])         # FedDA: broadcast momentum
        else:
            m0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                              params)
        client_map = client_map or jax.vmap
        if cfg.algorithm == "feddyn":
            if "sel" not in batch:
                raise ValueError(
                    "algorithm='feddyn' needs batch['sel'] (the selected "
                    "clients' global indices) to gather per-client state — "
                    "sample_round_batches emits it")
            h_all = state["client_state"]["per_client"]["h"]
            h_sel = _m(jax.tree.map(lambda x: x[batch["sel"]], h_all))
            locals_, local_ms = client_map(
                lambda b, hk: local_train(cfg, grad_fn, params, m0, b, lr,
                                          anchor=params, h=hk))(
                    batch["client"], h_sel)
        elif cfg.algorithm == "fedprox":
            locals_, local_ms = client_map(
                lambda b: local_train(cfg, grad_fn, params, m0, b, lr,
                                      anchor=params))(batch["client"])
        else:
            locals_, local_ms = client_map(
                lambda b: local_train(cfg, grad_fn, params, m0, b,
                                      lr))(batch["client"])

    with jax.named_scope("fl_aggregate"):
        # Deterministic fault injection (test-only): corrupt the uploaded
        # updates BEFORE aggregation / the guard.  A static python unroll
        # over the frozen fault tuple — the faults are part of the traced
        # graph.
        if cfg.faults:  # lint: static-branch (config-keyed)
            sel_ids = batch.get("sel")
            if sel_ids is None:
                sel_ids = jnp.arange(batch["sizes"].shape[0],
                                     dtype=jnp.int32)
            for f in cfg.faults:
                locals_ = f.apply_client(locals_, params, sel_ids,
                                         state["round"])

        # In-scan health guard: all-device finiteness check per client.  A
        # rejected client is scrubbed back to the broadcast point (so
        # NaN/inf never reaches a reduction — 0-weight alone would not
        # neutralize NaN) and contributes zero aggregation weight via the
        # delta-form path.
        sizes = batch["sizes"].astype(jnp.float32)
        active = batch.get("active")
        guard_on = cfg.guard != "off"
        base_act = (active.astype(jnp.float32) if active is not None
                    else jnp.ones_like(sizes))
        if guard_on:
            _cvec = lambda v, leaf: v.reshape(
                v.shape + (1,) * (leaf.ndim - 1))
            client_ok = jnp.ones(sizes.shape, bool)
            checked = [locals_]
            if cfg.local_momentum == "communicated":
                checked.append(local_ms)
            for tree in checked:
                for leaf in jax.tree.leaves(tree):
                    client_ok = client_ok & jnp.all(
                        jnp.isfinite(leaf), axis=tuple(range(1, leaf.ndim)))
            rejected = jnp.sum(base_act * (~client_ok).astype(jnp.float32))
            act = base_act * client_ok.astype(jnp.float32)
            _scrub = lambda trees, base: jax.tree.map(
                lambda l, b: jnp.where(_cvec(client_ok, l), l,
                                       b.astype(l.dtype)), trees, base)
            locals_ = _scrub(locals_, params)
            if cfg.local_momentum == "communicated":
                local_ms = _scrub(local_ms, m0)
        else:
            rejected = jnp.zeros(())
            act = base_act

        # (3-4) upload + FedAvg: ONE weighted reduction over the client
        # axis.  With a dropout mask or an active guard the reduction runs
        # in DELTA form around the broadcast point (an all-dropped round is
        # exactly a no-op); otherwise the legacy direct einsum —
        # bit-identical to the pre-dropout engine.
        if active is not None or guard_on:
            w = sizes * act
            w = w / jnp.maximum(jnp.sum(w), 1e-12)

            def agg_tree(trees, base):
                def one(l, b):
                    d = jnp.einsum("c,c...->...", w, l.astype(jnp.float32)
                                   - b.astype(jnp.float32))
                    return (b.astype(jnp.float32) + d).astype(l.dtype)
                return jax.tree.map(one, trees, base)

            w_half = agg_tree(locals_, params)
            new_global_m = (agg_tree(local_ms, m0)
                            if cfg.local_momentum == "communicated" else None)
        else:
            w = sizes / jnp.sum(sizes)
            agg = lambda l: jnp.einsum(
                "c,c...->...", w, l.astype(jnp.float32)).astype(l.dtype)
            w_half = jax.tree.map(agg, locals_)
            new_global_m = (jax.tree.map(agg, local_ms)
                            if cfg.local_momentum == "communicated" else None)

        # FedDyn: update the per-client correction of the selected ACTIVE
        # clients (scatter), the server average, and pull w_half toward the
        # implicit consensus point — all BEFORE the FedDU server update,
        # which then trains from the corrected model.
        new_client_state = state.get("client_state")
        if cfg.algorithm == "feddyn":
            alpha = cfg.feddyn.alpha
            n_total = jax.tree.leaves(h_all)[0].shape[0]
            bcast = lambda v, leaf: v.reshape(
                v.shape + (1,) * (leaf.ndim - 1))
            drift = jax.tree.map(
                lambda l, p0: l.astype(jnp.float32) - p0.astype(jnp.float32),
                locals_, params)
            h_sel_new = jax.tree.map(
                lambda hk, d: hk - alpha * bcast(act, d) * d, h_sel, drift)
            h_new = jax.tree.map(
                lambda ha, hs: ha.at[batch["sel"]].set(hs.astype(ha.dtype)),
                h_all, h_sel_new)
            h_shared_new = jax.tree.map(
                lambda hs, d: hs - (alpha / n_total)
                * jnp.einsum("c,c...->...", act, d),
                _m(state["client_state"]["shared"]["h"]), drift)
            if alpha > 0:  # lint: static-branch (at alpha == 0, h is identically zero)
                w_half = jax.tree.map(
                    lambda wh, hs: (wh.astype(jnp.float32) - hs / alpha
                                    ).astype(wh.dtype), w_half, h_shared_new)
            new_client_state = {"per_client": {"h": _m(h_new)},
                                "shared": {"h": _m(h_shared_new)}}

    with jax.named_scope("fl_server_update"):
        # (5a) FedDU dynamic server update (Formulas 4-7).  acc comes from
        # the FIRST server step's own forward — no separate evaluation pass.
        if cfg.use_server_update:
            tau = jax.tree.leaves(batch["server"])[0].shape[0]
            la_grad = jax.value_and_grad(loss_and_acc_fn, has_aux=True)

            def sstep(carry, b):
                p, acc0, is_first = carry
                (_, acc), g = la_grad(p, b)
                g = _m(g)
                acc0 = jnp.where(is_first, acc, acc0)
                p = jax.tree.map(
                    lambda pi, gi: (pi - lr * gi).astype(pi.dtype), p, g)
                return (p, acc0, jnp.zeros((), bool)), None

            def server_scan(w, server):
                (w_end, acc, _), _ = jax.lax.scan(
                    sstep, (w, jnp.zeros(()), jnp.ones((), bool)), server)
                return w_end, acc

            w_end, acc = (server_map or (lambda f: f))(server_scan)(
                w_half, batch["server"])
            # Formula 6 via the telescoping identity: mean path gradient.
            g0 = jax.tree.map(
                lambda a, b_: (a.astype(jnp.float32) - b_.astype(jnp.float32))
                / (tau * lr), w_half, w_end)
            t_eff = tau_eff(cfg.feddu, acc=acc, round_idx=state["round"],
                            n0=batch["n0"], n_prime=jnp.sum(batch["sizes"]),
                            d_round=batch["d_round"],
                            d_server=batch["d_server"], tau=tau)
            proposed = feddu_apply(w_half, g0, t_eff, lr)
        else:
            proposed = w_half
            t_eff = jnp.zeros(())
            acc = jnp.zeros(())

        # Server-step guard: a diverged FedDU proposal (non-finite model,
        # tau_eff or gate accuracy) falls back to the plain aggregate
        # w_half.
        if guard_on and cfg.use_server_update:
            server_ok = jnp.isfinite(t_eff) & jnp.isfinite(acc)
            for leaf in jax.tree.leaves(proposed):
                server_ok = server_ok & jnp.all(jnp.isfinite(leaf))
            proposed = jax.tree.map(
                lambda pr, wh: jnp.where(server_ok, pr, wh), proposed, w_half)
            t_eff = jnp.where(server_ok, t_eff, 0.0)
            acc = jnp.where(server_ok, acc, 0.0)
        else:
            server_ok = jnp.ones((), bool)

    with jax.named_scope("fl_server_momentum"):
        # (5b) FedDUM server momentum on the pseudo-gradient (Formulas
        # 8/12).
        if cfg.server_momentum:
            pseudo = server_pseudo_gradient(params, proposed)
            new_params, new_server_m = server_momentum_step(
                params, state["server_m"], pseudo, cfg.feddum)
        else:
            new_params, new_server_m = proposed, state["server_m"]

        new_state = {"params": _m(new_params), "server_m": _m(new_server_m),
                     "round": state["round"] + 1}
        if cfg.local_momentum == "communicated":
            new_state["global_m"] = _m(new_global_m)
        if new_client_state is not None:
            new_state["client_state"] = new_client_state
        if cfg.use_masks:
            new_state["masks"] = masks
            if cfg.masked_compute == "kernel":
                new_state["filter_masks"] = state["filter_masks"]

        # Round discard: with every client rejected there is no information
        # in the round (reject_client), and under skip_round ANY rejection
        # voids it — restore the round-start carry (round counter still
        # advances, so the key chain and lr schedule stay aligned with a
        # fault-free run).
        if guard_on:
            survivors = jnp.sum(act) > 0
            if cfg.guard == "reject_client":
                discard = ~survivors
            else:  # skip_round
                discard = (~survivors) | (rejected > 0) | (~server_ok)
            health = rejected + (~server_ok).astype(jnp.float32)
            for k in ("params", "server_m", "global_m", "client_state"):
                if k in new_state:
                    new_state[k] = jax.tree.map(
                        lambda o, n: jnp.where(discard, o, n),
                        state[k], new_state[k])
            t_eff = jnp.where(discard, 0.0, t_eff)
            acc = jnp.where(discard, 0.0, acc)
        else:
            health = jnp.zeros(())

    return new_state, {"tau_eff": t_eff, "server_acc": acc,
                       "health": health}


# ---------------------------------------------------------------------------
# Device-side sampling — jax.random replaces the host np.random permutations
# ---------------------------------------------------------------------------

def sample_clients(key: jax.Array, num_clients: int, k: int) -> jax.Array:
    """Step (1): D^t — k distinct client indices, drawn on device."""
    return jax.random.choice(key, num_clients, (k,), replace=False)


def epoch_indices(key: jax.Array, n: int, count: int) -> jax.Array:
    """``count`` sample indices drawn as repeated without-replacement
    epochs over ``n`` samples (the paper's epoch semantics), on device."""
    reps = -(-count // n)  # ceil
    perms = jax.vmap(lambda k: jax.random.permutation(k, n))(
        jax.random.split(key, reps))
    return perms.reshape(-1)[:count]


def sample_round_batches(key: jax.Array, data: dict, *, clients_per_round: int,
                         batch_size: int, local_steps: int, server_batch: int,
                         server_tau: int, dropout_rate: float = 0.0) -> dict:
    """Builds one round's ``round_core`` batch entirely on device.

    data (all jnp, see FederatedData.device_arrays):
      client_x [N, n_k, ...], client_y [N, n_k], sizes [N],
      client_dists [N, classes], p_bar [classes], d_server scalar,
      server_x [n0, ...], server_y [n0].

    ``dropout_rate`` > 0 simulates stragglers: each selected client
    independently drops with that probability, emitted as the 0/1
    ``"active"`` mask.  At the default 0.0 the key is split exactly as
    before (3 ways), so existing runs stay bit-identical; dropout configs
    split 4 ways and draw their own deterministic chain.
    """
    from repro.core import niid

    if dropout_rate:
        k_sel, k_cl, k_srv, k_drop = jax.random.split(key, 4)
    else:
        k_sel, k_cl, k_srv = jax.random.split(key, 3)
    num_clients, n_k = data["client_y"].shape[:2]
    n0 = data["server_y"].shape[0]

    sel = sample_clients(k_sel, num_clients, clients_per_round)
    count = local_steps * batch_size
    idx = jax.vmap(lambda k: epoch_indices(k, n_k, count))(
        jax.random.split(k_cl, clients_per_round))              # [C, count]
    cx = jax.vmap(lambda x, i: x[i])(data["client_x"][sel], idx)
    cy = jax.vmap(lambda y, i: y[i])(data["client_y"][sel], idx)
    cx = cx.reshape(clients_per_round, local_steps, batch_size, *cx.shape[2:])
    cy = cy.reshape(clients_per_round, local_steps, batch_size, *cy.shape[2:])

    sidx = epoch_indices(k_srv, n0, server_tau * server_batch)
    sx = data["server_x"][sidx].reshape(
        server_tau, server_batch, *data["server_x"].shape[1:])
    sy = data["server_y"][sidx].reshape(
        server_tau, server_batch, *data["server_y"].shape[1:])

    p_round = niid.round_distribution(data["client_dists"], data["sizes"], sel)
    d_round = niid.non_iid_degree(p_round, data["p_bar"])
    batch = {
        "client": (cx, cy),
        "sizes": data["sizes"][sel],
        "server": (sx, sy),
        "d_round": d_round,
        "d_server": data["d_server"],
        "n0": jnp.asarray(n0, jnp.float32),
        "sel": sel.astype(jnp.int32),
    }
    if dropout_rate:
        batch["active"] = (
            jax.random.uniform(k_drop, (clients_per_round,))
            >= dropout_rate).astype(jnp.float32)
    return batch
