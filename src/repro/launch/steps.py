"""Pod-scale FL steps: the paper's round as ONE SPMD program.

``fl_train_step`` wraps the SAME unified round implementation as the
simulation driver — :func:`repro.core.engine.round_core` — so the two
paths cannot diverge (tests/test_engine_diff.py locks the parity):

    local E steps        — per-client restart-SGDM (FedDUM Formula 11);
                           NO collective over the client axis: clients
                           diverge inside the step.
    aggregate            — weighted mean over the client dim (one weight
                           all-reduce over the client axis; this IS the
                           paper's "upload models + FedAvg" step 3-4).
    FedDU server update  — tau server SGD steps on the shared batch,
                           normalized (Formula 6), scaled by tau_eff
                           (Formula 7); data-parallel over the whole mesh.
    FedDUM server SGDM   — pseudo-gradient momentum (Formulas 8/12).

This module only contributes the pod-specific pieces: the batch-dict model
adapter (``loss_and_accuracy`` fuses the Formula-7 acc gate into the first
server gradient step — §Perf iteration B2), the FLRunConfig->EngineConfig
wiring, and the (arch x shape) batch construction that
`sharding/fl_specs.py` partitions over the mesh.

State between rounds is just {global params, server momentum, [masks],
round} — FL clients are stateless (the momentum restart is what makes
this one program possible with zero extra communication).  With
``use_masks`` the FedAP keep-masks ride in that state in factored form
(only the axes FedAP prunes, every other axis at size 1; sharded like the
params on those axes), so the prune round needs no re-lower of the mesh
program (``with_masks`` injects a decision mid-run).

This module is the POD-SCALE entry point — big-model (arch x shape) FL
over `sharding/fl_specs.py` partition specs.  The simulation-scale
client-sharded driver lives in :mod:`repro.core.backend`
(``MeshBackend``), which reuses the pieces here: its Prune events compute
the FedAP decision from mesh-sharded participants
(``fedap.fedap_decision_sharded`` — ragged probe sets padded and masked)
and inject MASK decisions through :func:`with_masks`, whose canonical
state transform is ``backend.masked_round_state`` (shared with the local
executor so the two paths cannot diverge); SHRINK decisions compact the
sharded state in one jitted shard-local gather
(``MeshBackend._sharded_shrink``) — the pod analogue of the same
no-host-round-trip rule this module follows for the round itself.  Its
server-update and eval batches shard over the mesh exactly as
``fl_batch_partition_specs`` shards the server batch dim here.

Serve steps (``prefill_step`` / ``decode_step``) run the aggregated global
model — plain distributed inference.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import InputShape, ModelConfig
from repro.core.engine import (
    EngineConfig,
    FedDynConfig,
    FedProxConfig,
    build_model_fns,
    init_round_state,
    round_core,
)
from repro.core.momentum import FedDUMConfig
from repro.core.server_update import FedDUConfig
from repro.models.api import build_model, decode_cache_len, input_specs
from repro.sharding.specs import MeshPlan


@dataclasses.dataclass(frozen=True)
class FLRunConfig:
    lr: float = 1e-3              # eta' (local) and eta (server SGD)
    beta_local: float = 0.9       # FedDUM Formula 11
    beta_server: float = 0.9      # FedDUM Formula 8
    eta_server: float = 1.0
    local_steps: int = 1          # local iterations per round (E*n_k/B)
    server_tau: int = 1           # server iterations per round
    server_batch: int = 32
    feddu: FedDUConfig = dataclasses.field(default_factory=FedDUConfig)
    use_server_update: bool = True
    use_momentum: bool = True
    # Static-shape FedAP: factored keep-masks ride in the SPMD round state
    # (sharded like the params on their prunable axes — see
    # sharding/fl_specs.py), so the pod program prunes without a shape
    # change or re-lower.
    use_masks: bool = False
    # "kernel" additionally threads filter-level masks (replicated, tiny)
    # into the model fns so masked dense layers run the differentiable
    # Pallas masked_matmul; requires a masks-aware model
    # (model.loss/apply accept masks=).  "params" masks the tree only.
    masked_compute: str = "params"
    # Client-state algorithm (fedavg | fedprox | feddyn) — the pod round
    # state grows the same client_state slot as the simulation path
    # (fl_specs.fl_state_specs shards its per-client leaves).
    algorithm: str = "fedavg"
    # In-scan health guard (engine.round_core): non-finite client uploads
    # get zero aggregation weight ("reject_client") or void the whole
    # round ("skip_round"); adds zero programs to the pod step.
    guard: str = "off"
    fedprox: FedProxConfig = dataclasses.field(default_factory=FedProxConfig)
    feddyn: FedDynConfig = dataclasses.field(default_factory=FedDynConfig)


def token_accuracy(model, params, batch) -> jnp.ndarray:
    logits, _ = model.apply(params, batch)
    ok = (jnp.argmax(logits, -1) == batch["labels"]).astype(jnp.float32)
    mask = batch.get("loss_mask")
    if mask is not None:
        return jnp.sum(ok * mask) / jnp.clip(jnp.sum(mask), 1.0, None)
    return jnp.mean(ok)


def loss_and_accuracy(model, params, batch, masks=None):
    """Single-forward loss + token accuracy (the Formula-7 acc gate fused
    into the first server gradient step — §Perf iteration B2: the separate
    accuracy forward cost one extra server-batch pass per round).

    ``masks`` (masked_compute="kernel" only) is forwarded to a masks-aware
    ``model.apply``; None keeps the plain call so existing pod models need
    no signature change."""
    if masks is None:
        logits, aux = model.apply(params, batch)
    else:
        logits, aux = model.apply(params, batch, masks=masks)
    labels = batch["labels"]
    mask = batch.get("loss_mask")
    logp = jax.nn.log_softmax(logits.astype(jnp.float32))
    nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    ok = (jnp.argmax(logits, -1) == labels).astype(jnp.float32)
    if mask is not None:
        denom = jnp.clip(jnp.sum(mask), 1.0, None)
        loss = jnp.sum(nll * mask) / denom + aux
        acc = jnp.sum(ok * mask) / denom
    else:
        loss = jnp.sum(nll) / nll.size + aux
        acc = jnp.mean(ok)
    return loss, acc


def engine_config(run: FLRunConfig) -> EngineConfig:
    """The FLRunConfig -> EngineConfig wiring (locked against the simulation
    driver's FLConfig wiring by tests/test_engine_diff.py)."""
    return EngineConfig(
        lr=run.lr, lr_decay=1.0,
        use_server_update=run.use_server_update,
        local_momentum="restart" if run.use_momentum else "none",
        server_momentum=run.use_momentum,
        use_masks=run.use_masks,
        masked_compute=run.masked_compute,
        algorithm=run.algorithm,
        guard=run.guard,
        fedprox=run.fedprox,
        feddyn=run.feddyn,
        feddu=run.feddu,
        feddum=FedDUMConfig(beta_server=run.beta_server,
                            beta_local=run.beta_local,
                            eta_server=run.eta_server))


def make_fl_train_step(cfg: ModelConfig, run: FLRunConfig, num_clients: int,
                       *, model: Any = None):
    """Returns (init_state_fn(rng), train_step(state, batch) -> state_out).

    The round itself is `repro.core.engine.round_core`; this wires the
    batch-dict model adapter into it.  ``model`` overrides ``build_model``
    for tests (anything exposing init / loss / apply over batch dicts).

    batch:
      client: batch pytree with leading [C, steps, ...] dims
      server: batch pytree with leading [tau, ...] dim
      sizes:  [C] f32 n_k
      d_round, d_server: scalars (non-IID degrees, Formula 2)
      n0: scalar f32
    """
    model = build_model(cfg) if model is None else model
    eng = engine_config(run)

    # The kernel-mode arity decision (does round_core hand the carry's
    # filter masks to the model fns?) lives in ONE place —
    # engine.build_model_fns, shared with core.backend.model_fns — so the
    # pod and executor signatures cannot drift.  This module contributes
    # only the batch-dict adapters.
    def loss_fn(p, b, fm):
        if fm is None:
            return model.loss(p, b)
        return model.loss(p, b, masks=fm)

    def la_base(p, b, fm):
        return loss_and_accuracy(model, p, b, masks=fm)

    grad_fn, la_fn = build_model_fns(eng, loss_fn, la_base)

    def init_state(rng, filter_masks=None):
        params = model.init(rng)
        masks = None
        if eng.use_masks:
            from repro.core.backend import param_masks_for

            masks = param_masks_for(model, params, {})
        return init_round_state(params, eng, filter_masks=filter_masks,
                                num_clients=num_clients, masks=masks)

    def train_step(state, batch):
        new_state, metrics = round_core(eng, grad_fn, la_fn, state, batch)
        return new_state, metrics["tau_eff"]

    return init_state, train_step


def with_masks(state: dict, masks: Any, filter_masks: Any = None) -> dict:
    """Inject FedAP keep-masks into a running masked round state — the pod
    analogue of the simulation executor's ``Prune(mode="mask")`` event:
    momentum restarts, params are masked, shapes (and the lowered mesh
    program) are untouched.  ``masks`` are the model's factored masks
    (``backend.param_masks_for``); a full parameter-shaped mask is reduced
    to the slot's factored shape if it is constant along the slot's size-1
    axes, and refused with a ``CheckpointError`` naming the leaf if not.
    ``filter_masks`` swaps the kernel-mode filter masks too (required when
    the state carries a ``filter_masks`` slot — its pytree structure must
    stay identical)."""
    from repro.core.backend import masked_round_state

    if "masks" not in state:
        raise ValueError("state has no mask slot — build the step with "
                         "FLRunConfig(use_masks=True)")
    if "filter_masks" in state and filter_masks is None:
        raise ValueError(
            "state carries a filter_masks slot (masked_compute='kernel') — "
            "pass filter_masks=pruning.filter_masks(...) so the kernel path "
            "prunes the same filters the param masks zero")
    if filter_masks is not None and "filter_masks" not in state:
        raise ValueError(
            "filter_masks given but the state has no filter_masks slot — "
            "build the step with FLRunConfig(masked_compute='kernel')")
    return masked_round_state(state, masks, filter_masks=filter_masks)


def make_prefill_step(cfg: ModelConfig):
    model = build_model(cfg)

    def prefill_step(params, batch):
        logits, _ = model.apply(params, batch)
        # return only the last position (serving returns next-token logits)
        return logits[:, -1, :]

    return model, prefill_step


def make_decode_step(cfg: ModelConfig):
    model = build_model(cfg)

    def decode_step(params, cache, batch):
        return model.decode_step(params, cache, batch)

    return model, decode_step


# ---------------------------------------------------------------------------
# FL batch construction for (arch x shape)
# ---------------------------------------------------------------------------

def fl_batch_specs(cfg: ModelConfig, shape: InputShape, num_clients: int,
                   run: FLRunConfig, *, abstract: bool = True, seed: int = 0):
    """The train-shape batch: the global batch is split over C clients;
    the server batch rides along (tau leading dim)."""
    import numpy as np

    c = num_clients
    b_c = max(1, shape.global_batch // c)
    base = input_specs(cfg, shape, abstract=abstract, seed=seed)

    def expand(leaf, lead):
        if abstract:
            return jax.ShapeDtypeStruct(lead + leaf.shape, leaf.dtype)
        reps = 1
        for d in lead:
            reps *= d
        return jnp.broadcast_to(leaf, lead + leaf.shape)

    def reshard_client(leaf):
        # [B, ...] -> [C, steps, b_c, ...]
        shp = (c, run.local_steps, b_c) + leaf.shape[1:]
        if abstract:
            return jax.ShapeDtypeStruct(shp, leaf.dtype)
        sliced = leaf[: c * b_c]
        tiled = jnp.reshape(sliced, (c, 1, b_c) + leaf.shape[1:])
        return jnp.broadcast_to(tiled, shp)

    def reshard_positions(leaf):
        # [P, B, S] -> [C, steps, P, b_c, S]
        shp = (c, run.local_steps, leaf.shape[0], b_c) + leaf.shape[2:]
        if abstract:
            return jax.ShapeDtypeStruct(shp, leaf.dtype)
        sliced = leaf[:, : c * b_c]
        tiled = jnp.transpose(
            jnp.reshape(sliced, (leaf.shape[0], c, b_c) + leaf.shape[2:]),
            (1, 0, 2) + tuple(range(3, leaf.ndim + 1)))[:, None]
        return jnp.broadcast_to(tiled, shp)

    client = {}
    for k, v in base.items():
        client[k] = reshard_positions(v) if k == "positions" else reshard_client(v)

    server_base = input_specs(cfg, dataclasses.replace(
        shape, global_batch=run.server_batch), abstract=abstract, seed=seed + 1)
    server = {k: expand(v, (run.server_tau,)) for k, v in server_base.items()}

    scalar = (lambda v: jax.ShapeDtypeStruct((), jnp.float32)) if abstract else \
        (lambda v: jnp.asarray(v, jnp.float32))
    sizes = (jax.ShapeDtypeStruct((c,), jnp.float32) if abstract
             else jnp.ones((c,), jnp.float32))
    batch = {
        "client": client,
        "server": server,
        "sizes": sizes,
        "d_round": scalar(0.3),
        "d_server": scalar(0.01),
        "n0": scalar(2048.0),
    }
    if run.algorithm == "feddyn":
        # selected-client ids indexing the client_state's per-client slot;
        # the pod shape exercises full participation (client k <- slot k)
        batch["sel"] = (jax.ShapeDtypeStruct((c,), jnp.int32) if abstract
                        else jnp.arange(c, dtype=jnp.int32))
    return batch
