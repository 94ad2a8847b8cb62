"""Declarative training plans — the schedule language of the FL trainer.

A :class:`TrainPlan` is a typed sequence of segments and events:

  Scan(n)      n federated rounds inside ONE compiled ``lax.scan`` chunk
  Eval()       score the global model on the held-out test split
  Prune(mode)  FedAP (Algorithm 3) as a first-class event:
                 mode="mask"    static-shape: keep-masks are injected into
                                the scan carry; training keeps running in
                                the SAME compiled program (no re-jit)
                 mode="shrink"  re-materialize the genuinely smaller model
                                at the segment boundary (forces a re-trace)
  Snapshot()   record a copy of the current global params as an artifact
  Callback(fn) host escape hatch at a segment boundary (distillation,
               baseline pruning hooks, ...); fn(trainer, round_idx, params)
               may return new params, which restart the round state exactly
               like the legacy ``on_round_end`` protocol did

The plan replaces the old ``FederatedTrainer.run(n, on_round_end=...)``
callback API, whose per-round hook forced the scan into ``length=1``
chunks and made FedAP — the paper's cheap efficiency win — the most
expensive thing in the system.  The executor
(`repro.core.backend.PlanExecutor`, driving a local-scan or mesh backend)
compiles a plan into the minimal set of jitted scan chunks: consecutive
``Scan`` segments merge, and chunk programs are cached per (engine config,
chunk length), so a plan with ten ``Scan(5)`` segments compiles exactly
one program.

Execution returns a structured :class:`RunResult` (history + per-event
artifacts) instead of closure-mutated ``hook.result`` dicts.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Iterable, Union


class CheckpointError(ValueError):
    """A checkpoint directory is partial, corrupted, or mismatched.

    Subclasses :class:`ValueError` so legacy ``except ValueError`` callers
    keep working; raised by :func:`load_artifact` and by the run-checkpoint
    store in :mod:`repro.reliability.checkpoint` instead of raw
    ``KeyError`` / ``FileNotFoundError`` / ``zipfile.BadZipFile`` crashes.
    """


@dataclasses.dataclass(frozen=True)
class Scan:
    """``rounds`` federated rounds in one compiled scan chunk."""

    rounds: int

    def __post_init__(self):
        if self.rounds < 1:
            raise ValueError(f"Scan.rounds must be >= 1, got {self.rounds}")


@dataclasses.dataclass(frozen=True)
class Eval:
    """Evaluate the global model on the test split; appends to history.

    ``history["round"]`` records the number of completed rounds at the
    Eval, so a leading ``Eval()`` (evaluate-before-training) logs round 0
    and a trailing one logs ``plan.total_rounds``."""

    name: str = "eval"


@dataclasses.dataclass(frozen=True)
class Prune:
    """FedAP (Algorithm 3) at this point of the schedule.

    mode="mask":   static shapes — keep-masks enter the scan carry and the
                   engine applies them every round (`EngineConfig.use_masks`);
                   the surrounding Scan segments stay one compiled program.
                   With ``FLConfig(masked_compute="kernel")`` filter-level
                   masks ride along too and masked dense layers run the
                   differentiable Pallas ``masked_matmul`` kernel — pruned
                   blocks are skipped on the MXU during training, not just
                   zeroed in the parameter tree.
    mode="shrink": re-materialize the pruned model (true FLOP shrink on
                   device); the next Scan segment re-traces at the new
                   shapes, exactly like the legacy hook path.
    Both modes restart the server momentum (the paper's prune round resets
    optimizer state), so they produce identical training trajectories on
    normalization-free models.

    ``reuse`` (mode="shrink" only) names an EARLIER Prune event's artifact
    whose kept-filter decision this event compacts to — no second FedAP
    run, and the momentum buffers are compacted rather than restarted, so
    the event is a pure re-materialization of the masked training state.
    This is the mask-now-shrink-later pattern (``fedap_plan(...,
    shrink_round=K)``): the prune round stays inside the compiled scan
    (mask), and the next segment boundary compacts to the genuinely
    smaller — and faster per round — model.
    """

    mode: str = "mask"
    name: str = "prune"
    reuse: str | None = None

    def __post_init__(self):
        if self.mode not in ("mask", "shrink"):
            raise ValueError(f"Prune.mode must be 'mask' or 'shrink', "
                             f"got {self.mode!r}")
        if self.reuse is not None and self.mode != "shrink":
            raise ValueError(
                "Prune.reuse compacts to an earlier event's decision and "
                f"needs mode='shrink', got mode={self.mode!r}")


@dataclasses.dataclass(frozen=True)
class Snapshot:
    """Copy the current global params into ``RunResult.artifacts[name]``."""

    name: str = "snapshot"


@dataclasses.dataclass(frozen=True)
class Callback:
    """Host callback at a segment boundary — the migration target for the
    legacy ``on_round_end`` hooks (distillation, baseline pruning, ...).

    ``fn(trainer, round_idx, params)`` receives a COPY of the params (the
    next scan chunk donates the round state) and may return replacement
    params; a non-None return re-initializes the round state (momentum
    restart) with the round counter preserved — the legacy hook contract.
    """

    fn: Callable
    name: str = "callback"


Event = Union[Scan, Eval, Prune, Snapshot, Callback]


class TrainPlan:
    """An ordered schedule of :data:`Event` items.

    ``TrainPlan(Scan(30), Eval(), Prune(mode="mask"), Scan(30), Eval())``

    Iterables flatten, so builders can splice sub-schedules in place.

    ``checkpoint_dir`` makes the executor durably snapshot the run (round
    state + key chain + plan cursor + history/artifacts) at chunk
    boundaries — every ``checkpoint_every`` completed Scan chunks (default
    1 = every chunk).  A killed run then continues bit-identically via
    ``FederatedTrainer.resume(checkpoint_dir)``.  Checkpointing is an
    execution setting, not part of the schedule: it does not participate
    in plan equality.
    """

    def __init__(self, *events: Event | Iterable[Event],
                 checkpoint_every: int | None = None,
                 checkpoint_dir=None):
        flat: list[Event] = []
        for e in events:
            if isinstance(e, (Scan, Eval, Prune, Snapshot, Callback)):
                flat.append(e)
            else:
                flat.extend(e)
        for e in flat:
            if not isinstance(e, (Scan, Eval, Prune, Snapshot, Callback)):
                raise TypeError(f"not a TrainPlan event: {e!r}")
        self.events: tuple[Event, ...] = tuple(flat)
        if checkpoint_every is not None and checkpoint_dir is None:
            raise ValueError("checkpoint_every without checkpoint_dir: "
                             "there is nowhere to write the snapshots")
        if checkpoint_every is None and checkpoint_dir is not None:
            checkpoint_every = 1
        if checkpoint_every is not None and checkpoint_every < 1:
            raise ValueError(f"checkpoint_every must be >= 1, "
                             f"got {checkpoint_every}")
        self.checkpoint_every = checkpoint_every
        self.checkpoint_dir = checkpoint_dir

    def with_checkpointing(self, directory, *, every: int = 1) -> "TrainPlan":
        """A copy of this plan that checkpoints into ``directory`` every
        ``every`` completed Scan chunks."""
        return TrainPlan(self.events, checkpoint_every=every,
                         checkpoint_dir=directory)

    def __repr__(self):
        return f"TrainPlan({', '.join(map(repr, self.events))})"

    def __eq__(self, other):
        return isinstance(other, TrainPlan) and self.events == other.events

    @property
    def total_rounds(self) -> int:
        return sum(e.rounds for e in self.events if isinstance(e, Scan))

    @property
    def uses_masks(self) -> bool:
        """True iff the plan schedules a mask-mode prune — the executor then
        builds the engine with ``use_masks=True`` from round 0 (all-ones
        masks are a bit-exact no-op), so the prune event never re-jits."""
        return any(isinstance(e, Prune) and e.mode == "mask"
                   for e in self.events)

    def compiled(self) -> tuple[Event, ...]:
        """The minimal executable form: consecutive Scan segments merged.

        The executor jit-caches one chunk program per (engine config, chunk
        length); merging means a plan's distinct chunk lengths — not its
        event count — determine how many programs compile.
        """
        out: list[Event] = []
        for e in self.events:
            if isinstance(e, Scan) and out and isinstance(out[-1], Scan):
                out[-1] = Scan(out[-1].rounds + e.rounds)
            else:
                out.append(e)
        return tuple(out)

    def chunk_lengths(self) -> tuple[int, ...]:
        """Distinct Scan lengths after merging — the number of scan programs
        the executor will compile."""
        return tuple(sorted({e.rounds for e in self.compiled()
                             if isinstance(e, Scan)}))

    # -- builders ------------------------------------------------------------
    @classmethod
    def standard(cls, num_rounds: int, *, eval_every: int = 1) -> "TrainPlan":
        """``num_rounds`` of training with an Eval every ``eval_every``
        rounds — the plan equivalent of the legacy ``run(n, eval_every=k)``."""
        if eval_every < 1:
            raise ValueError(f"eval_every must be >= 1, got {eval_every}")
        events: list[Event] = []
        t = 0
        while t < num_rounds:
            n = min(eval_every - (t % eval_every), num_rounds - t)
            events.append(Scan(n))
            t += n
            if t % eval_every == 0 or t == num_rounds:
                events.append(Eval())
        return cls(events)

    @classmethod
    def with_callback(cls, num_rounds: int, fn: Callable, *,
                      every: int = 1, eval_every: int = 1,
                      name: str = "callback") -> "TrainPlan":
        """Training with ``fn`` invoked every ``every`` rounds — the
        migration path for legacy ``on_round_end`` hooks (the hook's own
        round gating keeps working: it still receives ``round_idx``).
        ``eval_every=0`` schedules no Eval events at all."""
        events: list[Event] = []
        t = 0
        while t < num_rounds:
            stops = [t + every - (t % every)]
            if eval_every:
                stops.append(t + eval_every - (t % eval_every))
            stop = min(min(stops), num_rounds)
            events.append(Scan(stop - t))
            t = stop
            if eval_every and (t % eval_every == 0 or t == num_rounds):
                events.append(Eval())
            if t % every == 0 or t == num_rounds:
                events.append(Callback(fn, name=name))
        return cls(events)


def fedap_plan(num_rounds: int, *, prune_round: int, mode: str = "mask",
               eval_every: int = 1,
               shrink_round: int | None = None) -> TrainPlan:
    """The paper's FedDUMAP schedule: train, FedAP once at ``prune_round``,
    keep training.  ``mode="mask"`` keeps every round inside the compiled
    scan; ``mode="shrink"`` re-materializes (legacy-hook behaviour).

    ``shrink_round=K`` (mask mode only) schedules the mask-now-shrink-later
    pattern: the FedAP decision at ``prune_round`` is applied as masks (no
    mid-scan re-jit), and at round ``K`` a follow-up
    ``Prune(mode="shrink", reuse="prune")`` compacts the state to the SAME
    kept filters — momentum included, no second FedAP run — so the
    steady-state rounds after ``K`` train the genuinely smaller model.
    On normalization-free models the result is exactly
    shrink-from-``prune_round`` training (locked by tests/test_plan.py).
    """
    if not 0 < prune_round <= num_rounds:
        raise ValueError(f"prune_round must be in (0, {num_rounds}], "
                         f"got {prune_round}")
    if eval_every < 1:
        raise ValueError(f"eval_every must be >= 1, got {eval_every}")
    if shrink_round is not None:
        if mode != "mask":
            raise ValueError("shrink_round schedules a follow-up compaction "
                             "of a MASK prune; use mode='mask' (got "
                             f"mode={mode!r})")
        if not prune_round < shrink_round <= num_rounds:
            raise ValueError(
                f"shrink_round must be in (prune_round={prune_round}, "
                f"{num_rounds}], got {shrink_round}")
    events: list[Event] = []
    t = 0
    while t < num_rounds:
        stops = [t + eval_every - (t % eval_every), num_rounds]
        if t < prune_round:
            stops.append(prune_round)
        if shrink_round is not None and t < shrink_round:
            stops.append(shrink_round)
        stop = min(stops)
        events.append(Scan(stop - t))
        t = stop
        if t % eval_every == 0 or t == num_rounds:
            events.append(Eval())
        if t == prune_round:
            events.append(Prune(mode=mode))
        if shrink_round is not None and t == shrink_round:
            events.append(Prune(mode="shrink", reuse="prune", name="shrink"))
    return TrainPlan(events)


@dataclasses.dataclass
class RunResult:
    """What a plan execution returns.

    params     final global params (masked-to-zero coordinates included in
               mask mode — ``artifacts["prune"]["kept"]`` compacts them)
    history    {"round", "acc", "loss", "tau_eff", "time"} from Eval events
               ("round" = completed rounds at the Eval; a leading Eval
               logs 0, and its "tau_eff" is 0.0 — no round has run yet)
    artifacts  per-event outputs keyed by event name (deduplicated with
               ``#k`` suffixes): Prune -> {"p_star", "layer_rates", "kept",
               "filter_masks"|"params_before"}, Snapshot -> {"round",
               "params"}, Callback -> whatever the callback returned
    state      the final engine round state (params/momentum/masks/round)
    """

    params: Any
    history: dict[str, list]
    artifacts: dict[str, Any]
    state: dict

    def save(self, path, *, model_config=None, params=None) -> None:
        """Persist the run as a serving-consumable checkpoint directory:
        ``arrays.npz`` (params + prune kept-filters/masks, keys are
        '/'-joined pytree paths) + ``meta.json`` (prune mode / p_star /
        layer_rates / kept_counts, eval history, and — when given — the
        :class:`repro.configs.base.ModelConfig` so the loader can rebuild
        the model without out-of-band knowledge).

        The LAST Prune event's artifact (if any) is exported; ``params``
        overrides the final params (e.g. to save a mid-run ``Snapshot``
        artifact's copy instead).  Load back with :func:`load_artifact`.

        Both files are written atomically (temp file + ``os.replace``), so
        a crash mid-save never leaves a half-written ``arrays.npz`` or
        ``meta.json`` for the loader to trip over — at worst one of the
        two is stale, which :func:`load_artifact` reports by name.
        """
        import json
        import os
        import pathlib

        import numpy as np

        out = pathlib.Path(path)
        out.mkdir(parents=True, exist_ok=True)
        prune_name, prune_art = None, None
        for name, art in self.artifacts.items():
            if isinstance(art, dict) and "kept" in art:
                prune_name, prune_art = name, art

        arrays = _flatten_arrays({"params": params if params is not None
                                  else self.params})
        meta: dict = {
            "format": "repro-checkpoint-v1",
            "history": _json_safe(self.history),
            "model_config": (model_config.to_dict()
                             if model_config is not None else None),
            "prune": None,
        }
        if prune_art is not None:
            kept = prune_art.get("kept") or {}
            arrays.update(_flatten_arrays({"kept": dict(kept)}))
            fmasks = prune_art.get("filter_masks")
            if fmasks:
                arrays.update(_flatten_arrays({"masks": dict(fmasks)}))
            meta["prune"] = _json_safe({
                "event": prune_name,
                "mode": prune_art.get("mode"),
                "p_star": prune_art.get("p_star"),
                "layer_rates": prune_art.get("layer_rates"),
                "kept_counts": prune_art.get(
                    "kept_counts",
                    {k: int(np.asarray(v).shape[-1]) for k, v in kept.items()}),
            })
        # npz holds neither empty subtrees (a param-free layer, e.g. a
        # non-parametric norm) nor dtypes beyond numpy's own (bfloat16
        # comes back as raw 2-byte voids): the loader restores both by name
        meta["empty"] = sorted(k for k, v in arrays.items() if v is None)
        arrays = {k: v for k, v in arrays.items() if v is not None}
        meta["dtypes"] = {k: str(np.asarray(v).dtype)
                          for k, v in arrays.items()}
        tmp = out / f".arrays.npz.tmp-{os.getpid()}"
        with open(tmp, "wb") as f:
            np.savez(f, **{k: np.asarray(v) for k, v in arrays.items()})
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, out / "arrays.npz")
        tmp = out / f".meta.json.tmp-{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(meta, f, indent=2)
            f.write("\n")
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, out / "meta.json")


def _flatten_arrays(tree, prefix: str = "") -> dict:
    """Nested dicts of arrays -> flat {'a/b/c': leaf}.  Keys must be
    '/'-free strings (true for every model param tree in this repo).  An
    empty dict below the root flattens to a None leaf."""
    flat: dict = {}
    if isinstance(tree, dict):
        if not tree and prefix:
            return {prefix[:-1]: None}
        for k, v in tree.items():
            k = str(k)
            if "/" in k:
                raise ValueError(f"checkpoint keys may not contain '/': {k!r}")
            flat.update(_flatten_arrays(v, f"{prefix}{k}/"))
        return flat
    flat[prefix[:-1]] = tree
    return flat


def _unflatten_arrays(flat: dict) -> dict:
    tree: dict = {}
    for key, leaf in flat.items():
        node = tree
        parts = key.split("/")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = leaf
    return tree


def _json_safe(x):
    """numpy scalars/arrays -> python, recursively (checkpoint metadata)."""
    import numpy as np

    if isinstance(x, dict):
        return {str(k): _json_safe(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return [_json_safe(v) for v in x]
    if isinstance(x, (np.generic,)):
        return x.item()
    if hasattr(x, "tolist") and hasattr(x, "ndim"):     # np/jnp arrays
        return np.asarray(x).tolist()
    return x


def load_artifact(path) -> dict:
    """Load a :meth:`RunResult.save` checkpoint directory.

    Returns ``{"params", "kept", "filter_masks", "mode", "model_config",
    "history", "meta"}`` — ``kept``/``filter_masks`` are None for a dense
    (never-pruned) run, ``model_config`` is a rebuilt
    :class:`~repro.configs.base.ModelConfig` or None if the save didn't
    record one.  ``repro.serving`` consumes this to decode the checkpoint
    dense, masked (block-skipping kernel at dense shapes) or shrunk
    (compacted shapes).

    Partial directories (a crash between the two file writes, a copy that
    dropped a file) and corrupted/mismatched saves raise
    :class:`CheckpointError` naming what is wrong, instead of a raw
    ``FileNotFoundError`` / ``zipfile.BadZipFile`` / ``KeyError``.
    """
    import json
    import pathlib
    import zipfile

    import jax.numpy as jnp
    import numpy as np

    p = pathlib.Path(path)
    if not (p / "meta.json").exists():
        raise CheckpointError(
            f"{p}: not a checkpoint directory (missing meta.json)")
    try:
        with open(p / "meta.json") as f:
            meta = json.load(f)
    except (OSError, json.JSONDecodeError) as e:
        raise CheckpointError(f"{p}: unreadable meta.json ({e})") from e
    if meta.get("format") != "repro-checkpoint-v1":
        raise CheckpointError(f"{p}: not a repro checkpoint "
                              f"(format={meta.get('format')!r})")
    if not (p / "arrays.npz").exists():
        raise CheckpointError(
            f"{p}: partial checkpoint (meta.json present but arrays.npz "
            f"missing — interrupted or incomplete save)")
    dtypes = meta.get("dtypes", {})
    try:
        with np.load(p / "arrays.npz") as z:
            flat = {k: (z[k].view(jnp.dtype(dtypes[k]))
                        if k in dtypes and z[k].dtype.kind == "V" else z[k])
                    for k in z.files}
        tree = _unflatten_arrays(
            {**flat, **{k: {} for k in meta.get("empty", [])}})
    except (zipfile.BadZipFile, OSError, ValueError) as e:
        raise CheckpointError(f"{p}: corrupted arrays.npz ({e})") from e
    from repro.configs.base import ModelConfig

    prune = meta.get("prune") or {}
    return {
        "params": tree.get("params", {}),
        "kept": tree.get("kept"),
        "filter_masks": tree.get("masks"),
        "mode": prune.get("mode"),
        "model_config": (ModelConfig.from_dict(meta["model_config"])
                         if meta.get("model_config") else None),
        "history": meta.get("history", {}),
        "meta": meta,
    }
