"""Pluggable execution backends behind ONE backend-agnostic plan executor.

A :class:`~repro.core.plan.TrainPlan` describes WHAT happens (Scan / Eval /
Prune / Snapshot / Callback); this module decides WHERE it happens.  The
:class:`PlanExecutor` owns the schedule loop — history and artifact
bookkeeping, the Prune decision/apply split, the legacy Callback contract —
and drives a narrow :class:`ExecutionBackend` protocol:

    init_state(params)                 build the engine round state
    run_chunk(state, key, length)      one compiled scan chunk of rounds
    evaluate(state)                    (loss, acc) on the held-out split
    prune_decision(state, init_params) FedAP Algorithm 3 (the DECISION)
    apply_prune(state, mode, kept)     inject/apply it (mask or shrink)
    snapshot(state)                    a safe copy of the global params
    replace_params(state, params)      the legacy Callback restart contract

Two implementations ship:

  :class:`LocalScanBackend` — the single-host simulation path: session-
      cached jitted scan chunks (`compiled_engine`) with device-side
      `engine.sample_round_batches`; exactly the execution the differential
      tests lock against the f64 oracle.

  :class:`MeshBackend` — the same numerics, client-sharded over a device
      mesh: the federated dataset is placed with the client dimension
      sharded over the mesh's client axes
      (`FederatedData.device_arrays(mesh=...)`), the in-scan sampled round
      batch is sharding-constrained so the per-client local-epoch vmap,
      the FedAvg reduction AND the per-step server batches of the FedDU
      dynamic update partition over the mesh
      (`sharding.fl_specs.fl_sim_batch_specs` — the tau server-SGD steps
      become per-shard partial grads + one all-reduce instead of being
      replicated on every device), evaluation shards the test batch the
      same way (padded rows corrected out exactly), and Prune events run
      POD-SIDE: `fedap.fedap_decision_sharded` gathers the probe/Fisher
      statistics from mesh-sharded participants (ragged probe sets padded
      and masked), a mask decision goes into the live state through
      `masked_round_state` without re-lowering the mesh program, and a shrink
      compacts the state SHARD-LOCALLY (one jitted gather of the kept
      filters — params and momentum never round-trip through the host).

Both backends share the scan-chunk builder below, including the
double-buffered sampling mode (``prefetch=True``): the scan carry holds the
NEXT round's already-gathered batch, so round t+1's client/server gathers
are issued while round t computes — on accelerators the gather latency
hides behind the round's compute.  The key chain and every drawn batch are
IDENTICAL to the non-prefetching chunk (locked bit-exact by
tests/test_plan.py), so prefetching is purely a scheduling change.
"""
from __future__ import annotations

import dataclasses
import functools
import inspect
import time
from typing import Any, Protocol, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import engine
from repro.core.engine import EngineConfig
from repro.core.plan import (
    Callback,
    Eval,
    Prune,
    RunResult,
    Scan,
    Snapshot,
    TrainPlan,
)


# ---------------------------------------------------------------------------
# Shared engine wiring: model fns, sampling kwargs, the scan-chunk builder
# ---------------------------------------------------------------------------

def model_fns(model, eng: EngineConfig):
    """(grad_fn, loss_and_acc_fn) for `engine.round_core` from a simulation
    model (``loss_and_acc(params, x, y[, masks=])``).  The kernel/non-kernel
    arity split lives in ``engine.build_model_fns``; this adapts the
    ``(x, y)`` batch tuples to it.

    Models without the ``masks=`` keyword (e.g. ad-hoc test models) are
    still valid outside kernel mode — the filter masks are only threaded
    through when the model declares the seam."""
    accepts_masks = "masks" in inspect.signature(model.loss_and_acc).parameters
    if eng.use_masks and eng.masked_compute == "kernel" and not accepts_masks:
        raise TypeError(
            f"masked_compute='kernel' needs the model's loss_and_acc to "
            f"accept masks=, but {type(model).__name__}.loss_and_acc does not")

    if accepts_masks:
        def loss_fn(p, b, fm):
            return model.loss_and_acc(p, b[0], b[1], masks=fm)[0]

        def la_fn(p, b, fm):
            return model.loss_and_acc(p, b[0], b[1], masks=fm)
    else:
        def loss_fn(p, b, fm):
            return model.loss_and_acc(p, b[0], b[1])[0]

        def la_fn(p, b, fm):
            return model.loss_and_acc(p, b[0], b[1])

    return engine.build_model_fns(eng, loss_fn, la_fn)


def sim_sample_kw(cfg, data) -> dict:
    """The device-side sampling shape of one simulated round (shared by
    every backend; part of the compiled-program cache key)."""
    n_k = int(data.client_x.shape[1])
    n0 = int(data.server_x.shape[0])
    return dict(
        clients_per_round=cfg.clients_per_round,
        batch_size=cfg.batch_size,
        local_steps=max(1, n_k // cfg.batch_size) * cfg.local_epochs,
        server_batch=cfg.server_batch_size,
        server_tau=max(1, n0 // cfg.server_batch_size) * cfg.server_epochs,
        dropout_rate=float(getattr(cfg, "dropout_rate", 0.0)),
    )


def init_filter_masks(model, params):
    """All-ones per-layer filter masks (``masked_compute="kernel"``): the
    carry structure must be final from round 0 so a prune event only swaps
    contents, never re-traces."""
    return filter_masks_for(model, params, {})


# The Prune apply goes through a small model seam: models that publish
# their own mask/shrink builders (the scanned-stack LM, whose layer params
# are stacked [L, ...] and pruned with per-layer index rows) dispatch
# there; PruneSpec models (the CNN) fall back to the generic spec-driven
# builders in `repro.core.pruning`.  ``kept`` is the decision's host-side
# kept-index map in either case ([d] per layer for spec models, [L, keep]
# rows for scanned stacks).

def param_masks_for(model, params, kept):
    """Param-structured, factored 0/1 masks for the carry
    (``state["masks"]``): each leaf keeps only the axes the model can
    prune (``pruning.param_masks``), so ``kept={}`` gives the all-ones
    masks of the slot's final shapes.  A model with no pruning seam prunes
    nothing: every leaf is ``(1,) * ndim``."""
    if hasattr(model, "param_masks"):
        return model.param_masks(params, kept)
    from repro.core import pruning

    spec = (model.prune_spec(params) if hasattr(model, "prune_spec")
            else pruning.PruneSpec(layers=()))
    return pruning.param_masks(params, spec, kept)


def filter_masks_for(model, params, kept):
    """Filter-level keep-masks for kernel-mode masked compute."""
    if hasattr(model, "filter_masks"):
        return model.filter_masks(params, kept)
    from repro.core import pruning

    return pruning.filter_masks(params, model.prune_spec(params), kept)


def shrink_params_for(model, params, kept):
    """Re-materialize a params-structured tree at the kept indices (also
    applied to momentum buffers, which share the params structure)."""
    if hasattr(model, "shrink_params"):
        return model.shrink_params(params, kept)
    from repro.core import pruning

    return pruning.shrink_params(params, model.prune_spec(params), kept)


def _span(name: str):
    """Decorate a backend entry point with a host span on the profiler's
    clock (``name`` as ``jax.profiler`` shows it)."""
    return functools.partial(jax.profiler.annotate_function, name=name)


def build_chunk(eng: EngineConfig, grad_fn, la_fn, sample_kw: dict, *,
                prefetch: bool = True, constrain=None, client_map=None,
                server_map=None):
    """``chunk(state, key, data_dev, length) -> (state, key, mets)`` — one
    scan over `round_core` with device-side sampling.  ``mets`` is a dict
    of per-round stacked metrics: ``{"tau_eff": [length], "health":
    [length]}`` (``health`` = guard rejection counts; identically zero
    with the guard off — the metric structure never depends on the guard
    mode, so guard configs compile zero extra programs).

    ``constrain`` (MeshBackend) maps the sampled batch through sharding
    constraints so the client axis partitions over the mesh;
    ``client_map``/``server_map`` are passed to ``engine.round_core``.

    ``prefetch=True`` double-buffers the sampling: the prologue draws round
    0's batch, and every scan iteration gathers round t+1's batch BEFORE
    running round t on the batch riding in the carry, so the gather can
    overlap the round's compute.  Key accounting: the non-prefetch chunk
    consumes splits sub_0..sub_{L-1} of the key chain and returns k_L; here
    the prologue consumes sub_0 and iteration t consumes sub_{t+1}, while
    the carry keeps the PREVIOUS chain key so the returned key is the same
    k_L — draws and key chain are bit-identical, only the schedule moves.
    (The final iteration's prefetched batch is discarded: it is the next
    chunk's first draw, recomputed there.)
    """

    def sample(key, data_dev):
        """(next chain key, the round batch drawn with its split)."""
        with jax.named_scope("fl_sample"):
            key, sub = jax.random.split(key)
            batch = engine.sample_round_batches(sub, data_dev, **sample_kw)
            if constrain is not None:
                batch = constrain(batch)
            return key, batch

    def _mets(metrics):
        return {"tau_eff": metrics["tau_eff"], "health": metrics["health"]}

    maps = dict(client_map=client_map, server_map=server_map)

    def serial_chunk(state, key, data_dev, length):
        def body(carry, _):
            st, k = carry
            k, batch = sample(k, data_dev)
            st, metrics = engine.round_core(eng, grad_fn, la_fn, st, batch,
                                             **maps)
            return (st, k), _mets(metrics)

        (state, key), mets = jax.lax.scan(body, (state, key), None,
                                          length=length)
        return state, key, mets

    if not prefetch:
        return serial_chunk

    def chunk(state, key, data_dev, length):
        if length == 1:
            # nothing to overlap with — the prefetch body would pay a
            # second, discarded gather (length is trace-time static, and
            # the draws/key chain are identical either way)
            return serial_chunk(state, key, data_dev, 1)
        k1, batch0 = sample(key, data_dev)

        def body(carry, _):
            st, _, k, batch = carry
            k_next, nb = sample(k, data_dev)    # round t+1, drawn during t
            st, metrics = engine.round_core(eng, grad_fn, la_fn, st, batch,
                                             **maps)
            return (st, k, k_next, nb), _mets(metrics)

        (state, key, _, _), mets = jax.lax.scan(
            body, (state, key, k1, batch0), None, length=length)
        return state, key, mets

    return chunk


def _match_placement(new: Any, ref: Any) -> Any:
    """Place every leaf of ``new`` on its counterpart's NamedSharding in
    ``ref`` — injected host arrays must not silently decay a sharded (or
    mesh-replicated) SPMD state slot to single-device.  Plain single-device
    leaves are left alone: committing them would change the local jit
    cache key and force a needless re-trace."""
    from jax.sharding import NamedSharding

    return jax.tree.map(
        lambda n, r: (jax.device_put(n, r.sharding)
                      if isinstance(getattr(r, "sharding", None),
                                    NamedSharding) else n), new, ref)


def masked_round_state(state: dict, masks: Any, filter_masks: Any = None
                       ) -> dict:
    """Inject FedAP keep-masks into a live masked round state: momentum
    (and the FedProx/FedDyn client_state corrections) restarts, params are
    masked, shapes and shardings — and therefore the compiled (or lowered
    SPMD) program — are untouched.  The one implementation of the
    ``Prune(mode="mask")`` apply, on every backend.  Masks of a
    full parameter shape (an older artifact) are reduced to the slot's
    factored shapes first (``pruning.factor_masks``)."""
    from repro.core.pruning import factor_masks

    masks = factor_masks(masks, state["masks"])
    new = {k: (jax.tree.map(jnp.zeros_like, v)
               if k in ("server_m", "global_m", "client_state") else v)
           for k, v in state.items()}
    new["params"] = _match_placement(
        engine.apply_masks(state["params"], masks), state["params"])
    new["masks"] = _match_placement(
        jax.tree.map(lambda m: jnp.asarray(m, jnp.float32), masks),
        state["masks"])
    if filter_masks is not None:
        # copy, not asarray: the next scan chunk donates the state, and the
        # caller retains the same mask arrays as prune artifacts
        new["filter_masks"] = _match_placement(
            jax.tree.map(lambda m: jnp.array(m, jnp.float32), filter_masks),
            state["filter_masks"])
    return new


# ---------------------------------------------------------------------------
# Session-scoped compiled-engine cache (the LocalScanBackend's programs)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class CompiledEngine:
    """The jitted programs for one (model, engine config, sampling shape,
    prefetch mode).  ``model`` is held as a strong reference so the
    ``id(model)`` cache key stays valid for the lifetime of the entry."""

    model: Any
    eng: EngineConfig
    chunk: Any        # (state, key, data_dev, *, length) -> (state, key, mets)
    round_core: Any   # (state, batch) -> (state, metrics)
    evaluate: Any     # (params, x, y) -> (loss, acc)


_COMPILED_CACHE: dict[tuple, CompiledEngine] = {}
_EVAL_CACHE: dict[int, tuple] = {}


def clear_compiled_cache() -> None:
    _COMPILED_CACHE.clear()
    _EVAL_CACHE.clear()


def compiled_engine(model, eng: EngineConfig, sample_kw: dict, *,
                    prefetch: bool = True) -> CompiledEngine:
    """Session-scoped cache of the jitted scan-chunk / round / eval
    programs.  Trainers over the same model object and equal (engine
    config, sampling shape, prefetch mode) share ONE compiled program set —
    e.g. the integration-test matrix re-running baselines over a
    module-scoped model fixture compiles each distinct configuration once
    per session instead of once per trainer."""
    key = (id(model), eng, tuple(sorted(sample_kw.items())), prefetch)
    ce = _COMPILED_CACHE.get(key)
    if ce is not None:
        return ce

    grad_fn, la_fn = model_fns(model, eng)
    chunk = build_chunk(eng, grad_fn, la_fn, sample_kw, prefetch=prefetch)

    ce = CompiledEngine(
        model=model, eng=eng,
        chunk=jax.jit(chunk, static_argnames=("length",), donate_argnums=(0,)),
        round_core=jax.jit(
            lambda state, batch: engine.round_core(eng, grad_fn, la_fn,
                                                   state, batch)),
        evaluate=eval_program(model))
    _COMPILED_CACHE[key] = ce
    return ce


def eval_program(model):
    """The one jitted ``loss_and_acc`` per model per session (shared by
    every backend instance over that model)."""
    ev = _EVAL_CACHE.get(id(model))
    if ev is None:
        ev = (model, jax.jit(model.loss_and_acc))
        _EVAL_CACHE[id(model)] = ev
    return ev[1]


# ---------------------------------------------------------------------------
# The backend protocol + the shared engine-state plumbing
# ---------------------------------------------------------------------------

@runtime_checkable
class ExecutionBackend(Protocol):
    """What the :class:`PlanExecutor` needs from an execution substrate."""

    eng: EngineConfig

    def init_state(self, params) -> dict: ...
    def restore_state(self, state: dict) -> dict: ...
    def run_chunk(self, state: dict, key, length: int): ...
    def evaluate(self, state: dict): ...
    def prune_decision(self, state: dict, init_params): ...
    def apply_prune(self, state: dict, mode: str, kept, *,
                    compact_existing: bool = False): ...
    def snapshot(self, state: dict): ...
    def snapshot_artifact(self, state: dict, t: int) -> dict: ...
    def replace_params(self, state: dict, params) -> dict: ...


class _EngineBackend:
    """Backend plumbing shared by local and mesh execution: round-state
    construction, the Prune apply (mask inject / shrink re-materialize /
    momentum-preserving compaction), and the legacy Callback restart."""

    model: Any
    eng: EngineConfig

    @property
    def _kernel_masks(self) -> bool:
        return self.eng.use_masks and self.eng.masked_compute == "kernel"

    @property
    def _num_clients(self) -> int:
        """Total client count — sizes the FedDyn per-client state slot."""
        return int(self.data.client_x.shape[0])

    def _place_state(self, state: dict) -> dict:
        """Hook for backends that pin state to explicit shardings."""
        return state

    def _round_state(self, params, filter_masks=None) -> dict:
        """``engine.init_round_state`` with the model's all-ones factored
        keep-masks in the mask slot."""
        masks = (param_masks_for(self.model, params, {})
                 if self.eng.use_masks else None)
        return engine.init_round_state(params, self.eng,
                                       filter_masks=filter_masks,
                                       num_clients=self._num_clients,
                                       masks=masks)

    def init_state(self, params) -> dict:
        fmasks = (init_filter_masks(self.model, params)
                  if self._kernel_masks else None)
        # the scan chunk donates its input state — never the caller's arrays
        state = self._round_state(jax.tree.map(jnp.copy, params), fmasks)
        return self._place_state(state)

    def restore_state(self, state: dict) -> dict:
        """Re-admit a checkpointed (host NumPy) round state: leaves go back
        on device with dtypes preserved, and the mesh backend re-pins them
        to their ``fl_state_specs`` shardings — f32 arrays round-trip
        through npz bit-exactly, which the resume-bit-identity tests
        lock.  Full parameter-shaped keep-masks (a checkpoint written
        before masks were factored) are reduced to the model's factored
        shapes, or refused with a ``CheckpointError`` naming the leaf."""
        if "masks" in state:
            from repro.core.pruning import factor_masks

            like = jax.eval_shape(
                lambda p: param_masks_for(self.model, p, {}),
                state["params"])
            state = dict(state, masks=factor_masks(state["masks"], like))
        return self._place_state(jax.tree.map(jnp.asarray, state))

    def snapshot(self, state: dict):
        # a copy: the next scan chunk donates the round state, which would
        # invalidate retained params
        return jax.tree.map(jnp.copy, state["params"])

    def snapshot_artifact(self, state: dict, t: int) -> dict:
        """A `Snapshot` artifact whose params copy is DEFERRED: the live
        param tree is loaned out and only copied right before the next
        donating chunk launch (``_secure_loans``).  A plan's trailing
        snapshot therefore costs zero copies, and mid-plan snapshots copy
        exactly once, off the per-event path — without ever aliasing a
        donated buffer."""
        art = {"round": t, "params": state["params"]}
        self._loans().append(art)
        return art

    def _loans(self) -> list:
        loans = getattr(self, "_loaned_artifacts", None)
        if loans is None:
            loans = self._loaned_artifacts = []
        return loans

    def _secure_loans(self) -> None:
        """Copy every pending loaned artifact in place.  Called before any
        donating call: the loaned trees may alias the state about to be
        donated (and we deliberately do not track which prune/replace
        events rebuilt the state in between — copying a still-valid loan
        is merely the eager behavior this buffer avoids on the fast
        path)."""
        loans = self._loans()
        for art in loans:
            art["params"] = jax.tree.map(jnp.copy, art["params"])
        loans.clear()

    def replace_params(self, state: dict, params) -> dict:
        """The legacy hook contract: replacement params re-initialize the
        round state (momentum restart) with the round counter preserved; an
        earlier mask-mode prune decision stays in force."""
        round_ = state["round"]
        masks = state.get("masks")
        fmasks = state.get("filter_masks")
        new_state = self._round_state(jax.tree.map(jnp.copy, params),
                                      fmasks)
        new_state["round"] = round_
        if masks is not None:
            new_state["masks"] = masks
            new_state["params"] = engine.apply_masks(new_state["params"],
                                                     masks)
        return self._place_state(new_state)

    @_span("fl.apply_prune")
    def apply_prune(self, state: dict, mode: str, kept, *,
                    compact_existing: bool = False):
        """Apply a FedAP decision.  mask: inject keep-masks into the carry
        (same compiled program keeps running, momentum restarts); shrink:
        re-materialize the smaller model (next chunk re-traces).
        ``compact_existing`` (the mask-now-shrink-later follow-up) compacts
        the CURRENT masked state — params AND momentum buffers — at the
        already-decided kept indices instead of restarting momentum, so
        masked-then-shrunk training continues exactly like
        shrink-from-the-start on normalization-free models."""
        if mode == "mask":
            # the mask builders read only the params' shapes
            masks = param_masks_for(self.model, state["params"], kept)
            fmasks = filter_masks_for(self.model, state["params"], kept)
            new_state = masked_round_state(
                state, masks,
                filter_masks=fmasks if self._kernel_masks else None)
            return self._place_state(new_state), {"filter_masks": fmasks}
        return self._shrink(state, kept, compact_existing=compact_existing)

    def _shrink(self, state: dict, kept, *, compact_existing: bool):
        """``Prune(mode="shrink")``: re-materialize eagerly, then place."""
        params = jax.tree.map(jnp.copy, state["params"])
        round_ = state["round"]
        new_params = shrink_params_for(self.model, params, kept)
        # kernel mode: all-ones filter masks at the SHRUNK shapes — the
        # compacted model has nothing left to skip
        fm = (init_filter_masks(self.model, new_params)
              if self._kernel_masks else None)
        # FedDyn corrections restart as zeros at the SHRUNK shapes: the old
        # h lives in the pre-prune coordinate system and cannot be compacted
        # meaningfully (the correction re-accumulates within a few rounds)
        new_state = self._round_state(new_params, fm)
        if compact_existing:
            new_state["server_m"] = shrink_params_for(
                self.model, jax.tree.map(jnp.copy, state["server_m"]), kept)
            if "global_m" in state:
                new_state["global_m"] = shrink_params_for(
                    self.model, jax.tree.map(jnp.copy, state["global_m"]),
                    kept)
        new_state["round"] = round_
        # the shrink discards the pre-prune params — record them
        return self._place_state(new_state), {"params_before": params}


# ---------------------------------------------------------------------------
# LocalScanBackend — the single-host scan path
# ---------------------------------------------------------------------------

class LocalScanBackend(_EngineBackend):
    """Session-cached jitted scan chunks over the whole federated dataset
    resident on ONE device — the paper's 100-device simulation setting."""

    name = "local"

    def __init__(self, model, data, cfg, *, use_masks: bool = False,
                 data_cache: dict | None = None):
        from repro.core.rounds import engine_config

        self.model, self.data, self.cfg = model, data, cfg
        self.eng = dataclasses.replace(engine_config(cfg),
                                       use_masks=use_masks)
        self.sample_kw = sim_sample_kw(cfg, data)
        # shared per-trainer: both mask-mode backend instances read the
        # SAME device-resident dataset (one transfer, one HBM copy)
        self._data_cache = {} if data_cache is None else data_cache

    def _compiled(self) -> CompiledEngine:
        return compiled_engine(self.model, self.eng, self.sample_kw,
                               prefetch=self.cfg.prefetch_sampling)

    @property
    def chunk(self):
        return self._compiled().chunk

    def device_data(self) -> dict:
        d = self._data_cache.get("local")
        if d is None:
            d = self.data.device_arrays()
            self._data_cache["local"] = d
        return d

    @_span("fl.run_chunk")
    def run_chunk(self, state, key, length):
        self._secure_loans()   # the jitted chunk donates `state`
        return self._compiled().chunk(state, key, self.device_data(),
                                      length=length)

    @_span("fl.evaluate")
    def evaluate(self, state):
        d = self.device_data()
        return self._compiled().evaluate(state["params"], d["test_x"],
                                         d["test_y"])

    @_span("fl.prune_decision")
    def prune_decision(self, state, init_params):
        from repro.core import fedap

        params = jax.tree.map(jnp.copy, state["params"])
        return fedap.fedap_decision(
            self.model, self.data, self.cfg.fedap, params,
            init_params=init_params,
            rng=np.random.default_rng(self.cfg.seed))


# ---------------------------------------------------------------------------
# MeshBackend — the client-sharded SPMD path
# ---------------------------------------------------------------------------

class MeshBackend(_EngineBackend):
    """The same scan-compiled rounds, client-sharded over a device mesh.

    * the federated dataset is placed with the client dimension sharded
      over the mesh client axes (``FederatedData.device_arrays(mesh=)``);
    * the in-scan sampled round batch is sharding-constrained
      (``fl_specs.fl_sim_batch_specs``), so the local-epoch vmap runs
      client-parallel across devices, the FedAvg einsum partitions into
      per-shard partial sums + one all-reduce, and — ``shard_server``
      (default on) — the PER-STEP batch dim of ``batch["server"]`` shards
      over the same axes, so each of the tau FedDU server-update steps
      (the Formula 4-7 scan) is data-parallel instead of redundantly
      replicated on every device; GSPMD inserts the collectives, so
      `round_core` itself is untouched and the numerics stay within float
      tolerance of the local path (locked per round against
      LocalScanBackend AND the f64 oracle by tests/test_mesh_backend.py,
      first-step ``server_acc``/tau_eff gate included);
    * evaluation (``shard_eval``, default on) shards the test split's
      batch dim over the mesh instead of running a replicated full-test
      pass; non-divisible test sizes are padded at placement time with
      copies of row 0 and the eval program subtracts the padded rows'
      contribution exactly (`_eval_program`);
    * engine state follows ``fl_specs.fl_state_specs`` (replicated for the
      simulation models, which publish no model-sharding axes);
    * Prune events run pod-side: ``fedap.fedap_decision_sharded`` computes
      the probe/Fisher statistics on mesh-sharded participants, a mask
      decision is injected by the base ``apply_prune`` — the chunk
      program is NOT re-lowered (mask mode keeps every shape, and the
      carry structure was final from round 0) — and a SHRINK runs as one
      jitted shard-local compaction (``NamedSharding`` outputs, no host
      round-trip of params or momentum; see ``_shrink``).
    """

    name = "mesh"

    def __init__(self, model, data, cfg, *, use_masks: bool = False,
                 mesh=None, data_cache: dict | None = None,
                 shard_server: bool = True, shard_eval: bool = True):
        from repro.core.rounds import engine_config
        from repro.launch.mesh import make_host_mesh
        from repro.sharding.specs import MeshPlan

        self.model, self.data, self.cfg = model, data, cfg
        self.eng = dataclasses.replace(engine_config(cfg),
                                       use_masks=use_masks)
        self.sample_kw = sim_sample_kw(cfg, data)
        self._data_cache = {} if data_cache is None else data_cache
        self.shard_server = shard_server
        self.shard_eval = shard_eval
        self.mesh = mesh if mesh is not None else make_host_mesh(model=1)
        axes = dict(self.mesh.shape)
        if "data" not in axes:
            raise ValueError(
                f"MeshBackend needs a 'data' mesh axis to host FL clients; "
                f"got axes {tuple(axes)}")
        self.plan = MeshPlan(
            mesh=self.mesh, multi_pod="pod" in axes,
            client_axes=(("pod", "data") if "pod" in axes else ("data",)),
            fsdp_axes=(), tp_axes=(("model",) if "model" in axes else ()),
            batch_axes=(), num_clients=axes["data"] * axes.get("pod", 1))
        self._chunk = None
        self._eval = None
        self._shrink_cache: dict = {}

    # -- shardings -----------------------------------------------------------
    def _named(self, spec_tree):
        from jax.sharding import NamedSharding, PartitionSpec as P

        return jax.tree.map(lambda s: NamedSharding(self.mesh, s), spec_tree,
                            is_leaf=lambda x: isinstance(x, P))

    def _place_state(self, state: dict) -> dict:
        from repro.sharding.fl_specs import fl_state_specs

        return jax.device_put(state, self._named(
            fl_state_specs(state, None, self.plan,
                           client_axes=self.plan.client_axes)))

    def device_data(self) -> dict:
        # Mesh hashes by devices + axis names, so equal meshes built
        # independently still share one device-resident dataset copy
        key = ("mesh", self.mesh, self.shard_eval)
        d = self._data_cache.get(key)
        if d is None:
            d = self.data.device_arrays(mesh=self.mesh,
                                        client_axes=self.plan.client_axes,
                                        shard_test=self.shard_eval)
            self._data_cache[key] = d
        return d

    # -- programs ------------------------------------------------------------
    def _programs(self):
        if self._chunk is None:
            from repro.sharding.fl_specs import fl_sim_batch_specs

            grad_fn, la_fn = model_fns(self.model, self.eng)
            shardings = self._named(fl_sim_batch_specs(
                self.cfg.clients_per_round, self.plan,
                server_batch=(self.cfg.server_batch_size
                              if self.shard_server else None),
                with_active=bool(self.sample_kw.get("dropout_rate"))))

            def constrain(batch):
                return jax.lax.with_sharding_constraint(batch, shardings)

            chunk = build_chunk(self.eng, grad_fn, la_fn, self.sample_kw,
                                prefetch=self.cfg.prefetch_sampling,
                                constrain=constrain, **self._kernel_maps())
            self._chunk = jax.jit(chunk, static_argnames=("length",),
                                  donate_argnums=(0,))
        return self._chunk

    def _kernel_maps(self) -> dict:
        """Kernel mode: GSPMD cannot partition a Mosaic kernel, so local
        training runs under a ``shard_map`` over the whole mesh (each
        device vmaps its own clients; replicated when the clients do not
        divide), and the FedDU server scan runs replicated under one too.
        The FedAvg reduction around them stays GSPMD-partitioned."""
        if not self._kernel_masks:
            return {}
        from jax.sharding import PartitionSpec as P

        size = self.plan.axis_size(self.plan.client_axes)
        cspec = (P(self.plan.client_axes)
                 if self.cfg.clients_per_round % size == 0 else P())

        def manual(f, spec):
            return jax.shard_map(f, mesh=self.mesh, in_specs=spec,
                                 out_specs=spec, check_vma=False)

        return {"client_map": lambda f: manual(jax.vmap(f), cspec),
                "server_map": lambda f: manual(f, P())}

    def _eval_program(self):
        """The batch-sharded eval program — built WITHOUT lowering the
        chunk program, so ``evaluate`` on a fresh backend stays cheap.

        The placed test split (``device_data``) is padded with copies of
        row 0 up to a multiple of the mesh client axes and sharded on its
        batch dim; padding keeps the shard genuinely data-parallel for ANY
        test size, and because every padded row IS row 0, its contribution
        is subtracted back out exactly:

            mean_true = (mean_pad * n_pad - k * f(row 0)) / n_true

        one extra single-row forward per Eval, instead of every device
        redundantly re-running the whole test set."""
        if self._eval is None:
            if not self.shard_eval:
                self._eval = eval_program(self.model)
                return self._eval
            la = self.model.loss_and_acc
            n_true = int(self.data.test_x.shape[0])

            def eval_fn(params, x, y):
                loss, acc = la(params, x, y)
                n_pad = x.shape[0]
                if n_pad == n_true:          # static: no padding was needed
                    return loss, acc
                k = float(n_pad - n_true)
                l0, a0 = la(params, x[:1], y[:1])
                return ((loss * n_pad - k * l0) / n_true,
                        (acc * n_pad - k * a0) / n_true)

            self._eval = jax.jit(eval_fn)
        return self._eval

    @property
    def chunk(self):
        return self._programs()

    @_span("fl.run_chunk")
    def run_chunk(self, state, key, length):
        from jax.sharding import NamedSharding, PartitionSpec as P

        self._secure_loans()   # the jitted chunk donates `state`
        # pin the key to the mesh (replicated): a fresh host key is
        # uncommitted while the chunk's output key is mesh-committed, and
        # that sharding difference alone would re-trace the chunk program
        key = jax.device_put(key, NamedSharding(self.mesh, P()))
        return self._programs()(state, key, self.device_data(),
                                length=length)

    @_span("fl.evaluate")
    def evaluate(self, state):
        d = self.device_data()
        return self._eval_program()(state["params"], d["test_x"],
                                    d["test_y"])

    # -- pod-side FedAP ------------------------------------------------------
    @_span("fl.prune_decision")
    def prune_decision(self, state, init_params):
        from repro.core import fedap

        params = jax.tree.map(jnp.copy, state["params"])
        return fedap.fedap_decision_sharded(
            self.model, self.data, self.cfg.fedap, params,
            init_params=init_params,
            rng=np.random.default_rng(self.cfg.seed),
            mesh=self.mesh, client_axes=self.plan.client_axes)

    def _shrink(self, state, kept, *, compact_existing):
        """``Prune(mode="shrink")`` without the host round-trip.

        The base-class shrink re-materializes eagerly (one dispatch per
        sliced tensor) and re-places the result via ``device_put`` — fine
        on one device, but at pod scale it serializes the prune round
        through the host.  Here the WHOLE compaction — gather of the kept
        filters from params (and, with ``compact_existing``, the momentum
        buffers — the ``reuse="prune"`` mask-now-shrink-later path), fresh
        zeros/ones for the restarted slots, the preserved round counter —
        is ONE jitted program whose ``out_shardings`` pin every leaf of
        the new state to its ``fl_state_specs`` NamedSharding: the
        compacted state is born mesh-committed, shard-locally, and the
        next chunk re-traces only because the shapes genuinely changed.
        """
        from repro.sharding.fl_specs import fl_state_specs

        # the shrink discards the pre-prune params — record a device copy
        # (never materialized on the host)
        params_before = jax.tree.map(jnp.copy, state["params"])

        # the jitted compaction is cached per (decision, momentum mode,
        # state structure), so re-applying the same decision — the
        # benchmark's warm timing, or repeated reuse-shrinks — runs the
        # already-compiled program.  Kept-index arrays may be [d] (spec
        # models) or [L, keep] (scanned stacks) — key on shape + raveled
        # values.
        cache_key = (tuple((k, np.asarray(v).shape,
                            tuple(int(i) for i in np.asarray(v).ravel()))
                           for k, v in sorted(kept.items())),
                     bool(compact_existing), tuple(sorted(state)))
        compacted = self._shrink_cache.get(cache_key)
        if compacted is None:
            def compact(st):
                params = shrink_params_for(self.model, st["params"], kept)
                # kernel mode: all-ones filter masks at the SHRUNK shapes —
                # the compacted model has nothing left to skip
                fm = (init_filter_masks(self.model, params)
                      if self._kernel_masks else None)
                new = self._round_state(params, fm)
                if compact_existing:
                    new["server_m"] = shrink_params_for(
                        self.model, st["server_m"], kept)
                    if "global_m" in st:
                        new["global_m"] = shrink_params_for(
                            self.model, st["global_m"], kept)
                new["round"] = st["round"]
                return new

            out_shardings = self._named(fl_state_specs(
                jax.eval_shape(compact, state), None, self.plan,
                client_axes=self.plan.client_axes))
            compacted = jax.jit(compact, out_shardings=out_shardings)
            self._shrink_cache[cache_key] = compacted
        return compacted(state), {"params_before": params_before}


# ---------------------------------------------------------------------------
# The executor — ONE schedule loop over any backend
# ---------------------------------------------------------------------------

class PlanExecutor:
    """Executes a :class:`TrainPlan` against an :class:`ExecutionBackend`.

    All schedule semantics live HERE, once: history rows record the true
    completed-round count ``t`` (Eval AND Callback), artifact keys
    deduplicate with ``#k`` suffixes, ``Prune(reuse=...)`` re-applies an
    earlier event's kept-filter decision instead of re-running Algorithm 3,
    and a Callback returning params restarts the round state through the
    backend (the legacy hook contract).

    Fault tolerance also lives here: a plan with ``checkpoint_dir`` set is
    durably snapshotted at chunk boundaries (round state + key chain +
    plan cursor + history/artifacts, atomic write — see
    :mod:`repro.reliability.checkpoint`), ``run(resume=payload)``
    continues a killed run bit-identically, and host faults
    (``reliability.KillAfterChunk``, threaded via ``faults=``) raise
    :class:`~repro.reliability.faults.SimulatedCrash` at the boundary a
    real preemption would hit — AFTER the checkpoint write.
    """

    def __init__(self, backend: ExecutionBackend, *, trainer=None,
                 faults=()):
        self.backend = backend
        self.trainer = trainer
        self._host_faults = tuple(f for f in faults
                                  if hasattr(f, "chunks"))

    def run(self, plan: TrainPlan, *, params=None, key=None, resume=None):
        """Returns (RunResult, advanced key).  Exactly one of ``params``/
        ``key`` or ``resume`` (a ``reliability.load_checkpoint`` payload)
        selects a fresh or a continued run."""
        backend = self.backend
        ckpt_dir = plan.checkpoint_dir
        if resume is not None:
            if params is not None or key is not None:
                raise ValueError("run(resume=...) restores params and key "
                                 "from the checkpoint — pass neither")
            # Everything the loop below mutates comes back from the
            # snapshot; the scan key chain continues from the EXACT key the
            # interrupted run held at the boundary.
            init_params = jax.tree.map(jnp.asarray, resume["init_params"])
            state = backend.restore_state(resume["state"])
            key = jax.random.wrap_key_data(jnp.asarray(resume["key_data"]))
            history = {k: list(v) for k, v in resume["history"].items()}
            artifacts: dict[str, Any] = dict(resume["artifacts"])
            t = int(resume["t"])
            last_tau = float(resume["last_tau"])
            chunks_done = int(resume["chunks_done"])
            start = int(resume["cursor"])
            t0 = time.time() - float(resume.get("elapsed", 0.0))
        else:
            if params is None or key is None:
                raise ValueError("run() needs params= and key= "
                                 "(or resume=)")
            # Prune events estimate the Lipschitz constant against the
            # params the run started from (the legacy hooks took them
            # explicitly).
            init_params = jax.tree.map(jnp.copy, params)
            state = backend.init_state(params)
            history = {"round": [], "acc": [], "loss": [], "tau_eff": [],
                       "time": [], "health": []}
            artifacts = {}
            t0 = time.time()
            t = 0
            last_tau = 0.0
            chunks_done = 0
            start = 0

        def record(name, value):
            k, i = name, 1
            while k in artifacts:
                k = f"{name}#{i}"
                i += 1
            artifacts[k] = value

        @_span("fl.checkpoint")
        def write_checkpoint(cursor):
            from repro.reliability.checkpoint import (
                plan_spec,
                save_checkpoint,
            )

            backend._secure_loans()   # loaned artifacts may alias state
            save_checkpoint(ckpt_dir, {
                "state": state, "key_data": jax.random.key_data(key),
                "cursor": cursor, "t": t, "chunks_done": chunks_done,
                "last_tau": last_tau, "history": history,
                "artifacts": artifacts, "init_params": init_params,
                "plan": plan_spec(plan),
                "checkpoint_every": plan.checkpoint_every,
                "checkpoint_dir": str(ckpt_dir),
                "backend": backend.name,
                "elapsed": time.time() - t0,
            })

        events = plan.compiled()
        for idx, ev in enumerate(events):
            if idx < start:     # resumed: this event already completed
                continue
            if isinstance(ev, Scan):
                state, key, mets = backend.run_chunk(state, key, ev.rounds)
                t += ev.rounds
                last_tau = float(mets["tau_eff"][-1])
                history["health"].extend(
                    float(h) for h in np.asarray(mets["health"]))
                chunks_done += 1
                if (ckpt_dir is not None
                        and chunks_done % plan.checkpoint_every == 0):
                    write_checkpoint(idx + 1)
                # Host faults fire AFTER the checkpoint write — exactly
                # where a real between-chunks preemption lands.  Counted
                # over the WHOLE run, so a resumed run that restored
                # chunks_done past the fault does not re-die.
                for f in self._host_faults:
                    if f.chunks == chunks_done:
                        from repro.reliability.faults import SimulatedCrash

                        raise SimulatedCrash(
                            f"injected kill after chunk {chunks_done} "
                            f"(round {t})")
            elif isinstance(ev, Eval):
                loss, acc = backend.evaluate(state)
                # the TRUE round count: t rounds have completed when this
                # Eval runs, so a leading Eval() (evaluate-before-training)
                # records round 0, not a fabricated round -1
                history["round"].append(t)
                history["acc"].append(float(acc))
                history["loss"].append(float(loss))
                history["tau_eff"].append(last_tau)
                history["time"].append(time.time() - t0)
            elif isinstance(ev, Snapshot):
                # donation-aware: the copy is deferred until the next
                # donating chunk launch (see _EngineBackend.snapshot_artifact)
                record(ev.name, backend.snapshot_artifact(state, t))
            elif isinstance(ev, Prune):
                state, art = self._prune(ev, state, init_params, artifacts)
                record(ev.name, art)
            elif isinstance(ev, Callback):
                # the true completed-round count (NOT t-1 — mirrors the
                # Eval fix); params are a copy because the next scan chunk
                # donates the round state
                maybe = ev.fn(self.trainer, t, backend.snapshot(state))
                if maybe is not None:   # legacy contract: replace + restart
                    state = backend.replace_params(state, maybe)
            else:  # pragma: no cover — TrainPlan validates event types
                raise TypeError(f"unknown plan event: {ev!r}")

        return (RunResult(params=state["params"], history=history,
                          artifacts=artifacts, state=state), key)

    def _prune(self, ev: Prune, state: dict, init_params,
               artifacts: dict):
        """Decision + apply of one Prune event -> (new state, artifact)."""
        backend = self.backend
        if ev.reuse is not None:
            # the MOST RECENT artifact under that name: record() renames
            # repeated events to "name#k", and a reuse-shrink must compact
            # to the decision currently in force, not the first one
            src = None
            for k, v in artifacts.items():
                if (k.split("#", 1)[0] == ev.reuse
                        and isinstance(v, dict) and "kept" in v):
                    src = v
            if src is None:
                raise ValueError(
                    f"Prune(reuse={ev.reuse!r}) found no earlier prune "
                    f"artifact named {ev.reuse!r} (have: "
                    f"{sorted(artifacts)})")
            kept = src["kept"]
            new_state, extra = backend.apply_prune(state, ev.mode, kept,
                                                   compact_existing=True)
            art = {"mode": ev.mode, "reused": ev.reuse, "kept": kept,
                   # last axis: [d] kept vectors (spec models) and
                   # [L, keep] rows (scanned stacks) both count per layer
                   "kept_counts": {k: int(np.asarray(v).shape[-1])
                                   for k, v in kept.items()},
                   "p_star": src.get("p_star"),
                   "layer_rates": src.get("layer_rates")}
        else:
            decision = backend.prune_decision(state, init_params)
            art = decision.summary()
            art["kept"] = decision.kept
            art["mode"] = ev.mode
            new_state, extra = backend.apply_prune(state, ev.mode,
                                                   decision.kept)
        art.update(extra)
        return new_state, art
