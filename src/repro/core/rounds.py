"""The simulation trainer: a thin facade over pluggable execution backends.

One round (Section 3.1):
  (1) select a random device subset D^t, broadcast w^{t-1};
  (2) each device runs E local epochs (SGD, or restart-SGDM for FedDUM);
  (3) devices upload models;
  (4) server aggregates with FedAvg weights n_k/n';
  (5) server update on shared data with dynamic tau_eff (FedDU), optionally
      through the server-momentum pseudo-gradient path (FedDUM);
  (6) at the predefined round, FedAP prunes the model — as a scheduled
      ``Prune`` event of the declarative :class:`~repro.core.plan.TrainPlan`.

The round itself lives in :mod:`repro.core.engine` (``round_core``).  HOW a
:class:`TrainPlan` over it executes is the job of
:mod:`repro.core.backend`: the backend-agnostic :class:`PlanExecutor`
drives a narrow backend protocol (init_state / run_chunk / evaluate /
prune_decision / apply_prune / snapshot / replace_params), and
:class:`FederatedTrainer` is only the user-facing facade that picks the
substrate::

    FederatedTrainer(model, data, cfg)                     # local scan
    FederatedTrainer(model, data, cfg, backend="mesh")     # client-sharded

  * ``backend="local"`` (:class:`~repro.core.backend.LocalScanBackend`)
    moves the federated dataset to ONE device
    (:meth:`FederatedData.device_arrays`); client selection and batch
    sampling run on device through `jax.random` keys in the scan carry
    (`engine.sample_round_batches`), and every ``Scan`` segment is ONE
    compiled ``jax.lax.scan`` over ``round_core``, with one jitted chunk
    program cached per (model, engine config, sampling shape, prefetch
    mode) in a session-scoped cache (:func:`compiled_engine`);
  * ``backend="mesh"`` (:class:`~repro.core.backend.MeshBackend`) runs the
    SAME chunk client-sharded over a device mesh: the dataset's client
    dimension and the sampled round batch shard over the mesh client axes
    (`sharding/fl_specs.py`), the FedAvg reduction becomes per-shard
    partial sums + one all-reduce, and ``Prune`` events run pod-side
    (`fedap_decision_sharded` + `backend.masked_round_state`, no
    re-lower);
  * both backends double-buffer the in-scan sampling by default
    (``FLConfig.prefetch_sampling``): round t+1's gather is issued while
    round t computes, with a bit-identical key chain and batch sequence;
  * ``Prune(mode="mask")`` injects FedAP keep-masks into the scan carry
    (``EngineConfig.use_masks``) — the prune round and everything after it
    run inside the SAME compiled program; with
    ``FLConfig(masked_compute="kernel")`` filter-level masks also ride in
    the carry and the model fns route masked dense layers through the
    differentiable Pallas ``masked_matmul`` kernel; ``Prune(mode="shrink")``
    re-materializes the smaller model at the segment boundary, and
    ``fedap_plan(..., shrink_round=K)`` chains both (mask now, compact to
    the same decision later — no second FedAP run, no mid-scan re-jit,
    smaller steady-state model);
  * all clients share n_k in the paper's label-shard protocol, so local
    step counts are equal and the engine's client vmap is exact.

Momentum modes (covers the paper's baselines):
  local_momentum = "none"         plain local SGD (FedAvg, FedDU)
                 = "restart"      FedDUM's zero-restart SGDM — no comm cost
                 = "communicated" FedDA-style: global momentum broadcast to
                                  devices and aggregated back (2x comm)
  server_momentum = True          SGDM on the server pseudo-gradient

Every mode is differentially tested against the pure-NumPy oracle in
:mod:`repro.core.ref_engine` (tests/test_engine_diff.py), including the
masked mode; the mesh backend is additionally locked per round against the
local backend AND the oracle (tests/test_mesh_backend.py).

Migrating from the legacy callback API
--------------------------------------
The pre-plan API forced every observer into a per-round host hook, which
collapsed the scan into ``length=1`` chunks::

    hook = make_fedap_hook(model, data, apcfg, init_params=p0)   # OLD
    params, hist = trainer.run(60, eval_every=2, on_round_end=hook)
    kept = hook.result["kept"]

becomes a declarative schedule returning a structured result::

    plan = fedap_plan(60, prune_round=30, mode="mask", eval_every=2)  # NEW
    res = trainer.run(plan)
    params, hist = res.params, res.history
    kept = res.artifacts["prune"]["kept"]

Per-round hooks that must stay (distillation, baseline pruning) migrate to
``TrainPlan.with_callback(60, hook, eval_every=2)`` — the hook signature
``fn(trainer, round_idx, params) -> new params | None`` is unchanged, and
``round_idx`` is the number of COMPLETED rounds when the hook fires (the
first post-round hook of a run sees 1).
"""
from __future__ import annotations

import dataclasses

import jax

from repro.core.backend import (  # noqa: F401  (public re-exports)
    CompiledEngine,
    LocalScanBackend,
    MeshBackend,
    PlanExecutor,
    clear_compiled_cache,
    compiled_engine,
    sim_sample_kw,
)
from repro.core.engine import (
    ALGORITHMS,
    EngineConfig,
    FedDynConfig,
    FedProxConfig,
)
from repro.core.momentum import FedDUMConfig
from repro.core.plan import RunResult, TrainPlan
from repro.core.pruning import FedAPConfig
from repro.core.server_update import FedDUConfig


@dataclasses.dataclass(frozen=True)
class FLConfig:
    num_clients: int = 100
    clients_per_round: int = 10
    local_epochs: int = 5          # E
    batch_size: int = 10           # B
    lr: float = 0.1                # eta (local and server SGD)
    lr_decay: float = 0.99         # per-round learning-rate decay (paper 4.1)
    seed: int = 0
    # Feature switches — FedDUMAP = server update + restart momentum (+FedAP).
    use_server_update: bool = True       # FedDU
    local_momentum: str = "none"         # none | restart | communicated
    server_momentum: bool = False
    # Client-state algorithm: "fedavg" (stateless), "fedprox" (proximal
    # pull toward the round-start model), "feddyn" (per-client gradient
    # correction carried in the scan's client_state slot).
    algorithm: str = "fedavg"
    # Straggler/dropout simulation: each selected client independently drops
    # this round with probability dropout_rate; dropped clients contribute
    # zero aggregation weight and their client state is untouched.
    dropout_rate: float = 0.0
    # Masked-mode compute path: "params" zeroes the parameter tree only
    # (full-density matmuls); "kernel" threads filter masks into the model
    # so masked dense layers run the differentiable Pallas masked_matmul
    # (FedAP's FLOP savings realized during training).
    masked_compute: str = "params"
    # Double-buffered in-scan sampling: round t+1's client/server gather is
    # issued while round t computes (bit-identical batches and key chain —
    # purely a scheduling change; False restores the serial draw).
    prefetch_sampling: bool = True
    # In-scan health guard (see engine.round_core): "reject_client" zero-
    # weights non-finite client uploads; "skip_round" additionally discards
    # any round with a rejection.  Guards never change the compiled program
    # count (locked by the compile-budget sentinel).
    guard: str = "off"
    # Deterministic fault injection (tests/benchmarks only): a tuple /
    # reliability.FaultPlan of fault events.  Device faults (NaNGrad,
    # CorruptUpdate) are threaded into the engine config; host faults
    # (KillAfterChunk) fire in the PlanExecutor schedule loop.
    faults: tuple = ()
    # Server data usage per round: tau = server_epochs * floor(n0 / B_server).
    server_epochs: int = 1
    server_batch_size: int = 32
    feddu: FedDUConfig = dataclasses.field(default_factory=FedDUConfig)
    feddum: FedDUMConfig = dataclasses.field(default_factory=FedDUMConfig)
    fedap: FedAPConfig = dataclasses.field(default_factory=FedAPConfig)
    fedprox: FedProxConfig = dataclasses.field(default_factory=FedProxConfig)
    feddyn: FedDynConfig = dataclasses.field(default_factory=FedDynConfig)

    def __post_init__(self):
        # Mirror EngineConfig.__post_init__: a bad switch must fail HERE,
        # at construction, with a clear message — not at jit time.
        if self.local_momentum not in ("none", "restart", "communicated"):
            raise ValueError(
                f"unknown local_momentum: {self.local_momentum!r} "
                "(expected 'none', 'restart' or 'communicated')")
        if self.masked_compute not in ("params", "kernel"):
            raise ValueError(
                f"unknown masked_compute: {self.masked_compute!r} "
                "(expected 'params' or 'kernel')")
        if not 1 <= self.clients_per_round <= self.num_clients:
            raise ValueError(
                f"clients_per_round must be in [1, num_clients="
                f"{self.num_clients}], got {self.clients_per_round}")
        for name in ("local_epochs", "batch_size", "server_epochs",
                     "server_batch_size"):
            if getattr(self, name) < 1:
                raise ValueError(f"{name} must be >= 1, got "
                                 f"{getattr(self, name)}")
        if self.lr <= 0:
            raise ValueError(f"lr must be > 0, got {self.lr}")
        if self.lr_decay <= 0:
            raise ValueError(f"lr_decay must be > 0, got {self.lr_decay}")
        if self.algorithm not in ALGORITHMS:
            raise ValueError(f"unknown algorithm: {self.algorithm!r} "
                             f"(expected one of {ALGORITHMS})")
        if not 0.0 <= self.dropout_rate < 1.0:
            raise ValueError(f"dropout_rate must be in [0, 1), got "
                             f"{self.dropout_rate}")
        if self.guard not in ("off", "reject_client", "skip_round"):
            raise ValueError(
                f"unknown guard: {self.guard!r} (expected 'off', "
                f"'reject_client' or 'skip_round')")
        for f in self.faults:
            if not (hasattr(f, "apply_client") or hasattr(f, "chunks")):
                raise ValueError(
                    f"FLConfig.faults entries must be reliability fault "
                    f"events (NaNGrad / CorruptUpdate / KillAfterChunk), "
                    f"got {f!r}")


def feddumap_config(**kw) -> FLConfig:
    """The full method: FedDU + FedDUM (+FedAP via a plan Prune event)."""
    kw.setdefault("use_server_update", True)
    kw.setdefault("local_momentum", "restart")
    kw.setdefault("server_momentum", True)
    return FLConfig(**kw)


def engine_config(cfg: FLConfig) -> EngineConfig:
    """The FLConfig -> EngineConfig wiring (the round's only one)."""
    return EngineConfig(
        lr=cfg.lr, lr_decay=cfg.lr_decay,
        use_server_update=cfg.use_server_update,
        local_momentum=cfg.local_momentum,
        server_momentum=cfg.server_momentum,
        masked_compute=cfg.masked_compute,
        algorithm=cfg.algorithm,
        guard=cfg.guard,
        faults=tuple(f for f in cfg.faults if hasattr(f, "apply_client")),
        feddu=cfg.feddu, feddum=cfg.feddum,
        fedprox=cfg.fedprox, feddyn=cfg.feddyn)


_BACKENDS = {"local": LocalScanBackend, "mesh": MeshBackend}


class FederatedTrainer:
    """Simulation-grade FL trainer — a facade that binds (model, data,
    config) to an execution backend and hands TrainPlans to the
    :class:`~repro.core.backend.PlanExecutor`.

    model: an object exposing
        init(rng) -> params
        loss_and_acc(params, x, y) -> (scalar loss, scalar acc)
        prune_spec(params) / feature_maps(params, x)   (only for Prune events)
    data: repro.data.pipeline.FederatedData
    backend: "local" (single-host scan) | "mesh" (client-sharded over a
        device mesh; ``mesh=`` overrides the default host mesh, and
        ``backend_opts`` forwards extra backend constructor switches —
        e.g. ``{"shard_server": False}`` / ``{"shard_eval": False}`` to
        fall back to the replicated server scan / evaluation, which the
        BENCH_mesh_server_eval benchmark uses as its baseline)
    """

    def __init__(self, model, data, cfg: FLConfig, *,
                 backend: str = "local", mesh=None,
                 backend_opts: dict | None = None):
        if backend not in _BACKENDS:
            raise ValueError(f"unknown backend: {backend!r} "
                             f"(expected one of {sorted(_BACKENDS)})")
        self.model, self.data, self.cfg = model, data, cfg
        self.backend_name = backend
        self._mesh = mesh
        self._backend_opts = dict(backend_opts or {})
        # fail HERE with a clear message, not as a TypeError (or a silent
        # override) deep inside the first run()'s backend construction
        if backend != "mesh" and self._backend_opts:
            raise ValueError(
                f"backend_opts={sorted(self._backend_opts)} are "
                f"mesh-backend switches; pass backend=\"mesh\" "
                f"(got backend={backend!r})")
        reserved = {"mesh", "use_masks", "data_cache"} & set(
            self._backend_opts)
        if reserved:
            raise ValueError(
                f"backend_opts may not override trainer-managed backend "
                f"arguments {sorted(reserved)}; use the mesh= trainer "
                f"parameter / plan-driven masking instead")
        self._key = jax.random.key(cfg.seed)
        self.engine_config = engine_config(cfg)
        self._sample_kw = sim_sample_kw(cfg, data)
        self._backends: dict = {}
        # both mask-mode backend instances share ONE device-resident copy
        # of the federated dataset
        self._data_cache: dict = {}

    # -- backend plumbing ----------------------------------------------------
    def backend(self, *, use_masks: bool = False):
        """The (cached) execution backend for this trainer; one instance
        per mask mode so the jitted programs persist across runs."""
        if use_masks not in self._backends:
            kw = dict(self._backend_opts)
            if self.backend_name == "mesh":
                if self._mesh is None:
                    # resolve the default host mesh ONCE: both mask-mode
                    # backend instances must agree on the mesh (and share
                    # the device-resident dataset keyed on it)
                    from repro.launch.mesh import make_host_mesh
                    self._mesh = make_host_mesh(model=1)
                kw["mesh"] = self._mesh
            self._backends[use_masks] = _BACKENDS[self.backend_name](
                self.model, self.data, self.cfg, use_masks=use_masks,
                data_cache=self._data_cache, **kw)
        return self._backends[use_masks]

    def _compiled(self, *, use_masks: bool = False) -> CompiledEngine:
        """The session-cached local jitted programs (differential tests and
        benchmarks drive the engine through these directly)."""
        eng = dataclasses.replace(self.engine_config, use_masks=use_masks)
        return compiled_engine(self.model, eng, self._sample_kw,
                               prefetch=self.cfg.prefetch_sampling)

    def round_step(self, state, batch):
        """One round at explicit batches — the engine exactly as the pod
        path runs it; used by the differential/parity tests."""
        return self._compiled().round_core(state, batch)

    def _device_data(self) -> dict:
        return self.backend().device_data()

    # -- public API ----------------------------------------------------------
    def run(self, plan: TrainPlan | int, *, eval_every: int = 1,
            params=None) -> RunResult:
        """Execute a :class:`TrainPlan` (an ``int`` builds the standard
        train+eval plan for that many rounds).  Returns a RunResult."""
        if isinstance(plan, int):
            plan = TrainPlan.standard(plan, eval_every=eval_every)
        params0 = (self.model.init(jax.random.key(self.cfg.seed))
                   if params is None else params)
        executor = PlanExecutor(self.backend(use_masks=plan.uses_masks),
                                trainer=self, faults=self.cfg.faults)
        result, self._key = executor.run(plan, params=params0, key=self._key)
        return result

    def resume(self, checkpoint_dir, *, plan: TrainPlan | None = None
               ) -> RunResult:
        """Continue a killed run from its chunk-boundary checkpoints,
        bit-identically to the uninterrupted run (round state, scan key
        chain, plan cursor and history are all restored from the snapshot).

        ``plan=None`` rebuilds the schedule from the checkpoint's stored
        plan spec (checkpointing re-enabled into the same directory).
        Plans containing :class:`~repro.core.plan.Callback` events cannot
        be reconstructed from disk — pass the original plan object, which
        is validated against the stored spec.
        """
        from repro.reliability.checkpoint import (
            load_checkpoint,
            plan_from_spec,
            plan_spec,
        )
        from repro.core.plan import CheckpointError

        payload = load_checkpoint(checkpoint_dir)
        if payload.get("backend") != self.backend_name:
            raise CheckpointError(
                f"checkpoint was written by the {payload.get('backend')!r} "
                f"backend but this trainer runs {self.backend_name!r} — "
                f"resume on the same backend (bit-identity is per-backend)")
        if plan is None:
            plan = plan_from_spec(
                payload["plan"],
                checkpoint_every=payload.get("checkpoint_every"),
                checkpoint_dir=payload.get("checkpoint_dir",
                                           checkpoint_dir))
        elif plan_spec(plan) != list(payload["plan"]):
            raise CheckpointError(
                "the plan passed to resume() does not match the plan the "
                "checkpoint was written under — resuming would replay a "
                "different schedule")
        executor = PlanExecutor(self.backend(use_masks=plan.uses_masks),
                                trainer=self, faults=self.cfg.faults)
        result, self._key = executor.run(plan, resume=payload)
        return result
