"""PartitionSpecs for the mesh backend's round state and round batch."""
from __future__ import annotations

from typing import Any

import jax
from jax.sharding import PartitionSpec as P

from repro.sharding.specs import MeshPlan, param_specs


def _axis(axes: tuple):
    if not axes:
        return None
    return axes if len(axes) > 1 else axes[0]


def fl_state_specs(state_shapes: Any, model_axes: Any, plan: MeshPlan, *,
                   client_axes: tuple = ()) -> Any:
    """Engine round state = {params, server_m, [global_m], [masks],
    [filter_masks], [client_state], round}: every momentum buffer mirrors
    the params' model sharding (TP/FSDP, replicated over client axes); the
    round counter is replicated.  The FedAP keep-masks of the static-shape
    masked mode (``EngineConfig.use_masks``) are FACTORED — each leaf holds
    at size 1 every axis FedAP never prunes (``pruning.param_masks``) — so
    each takes its parameter's spec with ``None`` on every size-1 axis: a
    broadcast axis is never sharded, and a pruned axis shards as its
    parameter does.  The kernel-mode ``filter_masks`` slot (per-layer
    [d_l] vectors, a few KB) is fully replicated: every shard needs the
    whole block mask to decide which MXU blocks to skip.  Key-generic so the
    communicated-momentum (FedDA) state and the mask slots shard without
    special-casing.

    The ``client_state`` slot (FedProx/FedDyn) splits in two: leaves under
    ``per_client`` carry a LEADING num-clients dim and shard over
    ``client_axes`` exactly like the federated dataset (replicated when
    the dim does not divide the axis size — the production-safe fallback
    used throughout this module); leaves under ``shared`` are
    param-structured and follow the model placement.

    ``model_axes=None`` (the MeshBackend's simulation models, which publish
    no logical-axis tree) replicates every param-structured slot: on the
    simulation path the CLIENT axis of the batch is what shards over the
    mesh, and the global model rides replicated."""
    ca = _axis(client_axes)
    csize = plan.axis_size(client_axes) if client_axes else 1

    def per_client_spec(leaf):
        dim = leaf.shape[0] if len(leaf.shape) else 0
        if client_axes and dim % csize == 0:
            return P(ca)
        return P()

    def shared_spec(v):
        if model_axes is None:
            return jax.tree.map(lambda _: P(), v)
        return param_specs(v, model_axes, plan)

    def one(k, v):
        if k == "round":
            return P()
        if k == "client_state":
            return {"per_client": jax.tree.map(per_client_spec,
                                               v["per_client"]),
                    "shared": shared_spec(v["shared"])}
        if k == "filter_masks" or model_axes is None:
            return jax.tree.map(lambda _: P(), v)
        if k == "masks":
            return jax.tree.map(
                lambda m, spec: P(*(None if d == 1 else a for d, a in
                                    zip(m.shape, tuple(spec)
                                        + (None,) * len(m.shape)))),
                v, param_specs(state_shapes["params"], model_axes, plan))
        return param_specs(v, model_axes, plan)

    return {k: one(k, v) for k, v in state_shapes.items()}


def client_dim_sharding(mesh, client_axes: tuple, leading_dim: int):
    """NamedSharding for an array whose LEADING dim is the FL-client axis:
    sharded over ``client_axes`` when the dim divides the axis size,
    replicated otherwise (the production-safe fallback used throughout
    this module).  One implementation for every client-leading placement —
    the federated dataset (``FederatedData.device_arrays``) and the FedAP
    probe stack (``fedap_decision_sharded``) must never disagree."""
    from jax.sharding import NamedSharding

    size = 1
    for a in client_axes:
        size *= mesh.shape[a]
    if client_axes and leading_dim % size == 0:
        return NamedSharding(mesh, P(_axis(client_axes)))
    return NamedSharding(mesh, P())


def fl_sim_batch_specs(clients_per_round: int, plan: MeshPlan, *,
                       server_batch: int | None = None,
                       with_active: bool = False) -> dict:
    """PartitionSpecs for the SIMULATION path's round batch — the pytree
    built on device by ``engine.sample_round_batches``:

      client  (x [C, steps, b, ...], y [C, steps, b]) — C over the client
              axes (the per-client local-epoch vmap partitions over the
              mesh; the FedAvg einsum becomes per-shard partial sums + one
              all-reduce, inserted by GSPMD);
      sizes   [C] — alongside the client dim;
      server  (x [tau, b, ...], y [tau, b]) — with ``server_batch`` given,
              the PER-STEP batch dim b shards over the client axes, so each
              of the tau FedDU server-update steps (the Formula 4-7 scan in
              ``engine.round_core``) computes per-shard partial gradients +
              one GSPMD all-reduce instead of replicating the whole server
              step on every device; ``server_batch=None`` (or a
              non-divisible b) keeps it replicated;
      the non-IID scalars — replicated.

    A non-divisible ``clients_per_round`` falls back to replication, the
    production-safe default everywhere else in this module."""
    ca = _axis(plan.client_axes)
    size = plan.axis_size(plan.client_axes) if plan.client_axes else 1
    ok = bool(plan.client_axes) and clients_per_round % size == 0
    cspec = P(ca) if ok else P()
    sok = bool(plan.client_axes) and server_batch is not None \
        and server_batch % size == 0
    sspec = P(None, ca) if sok else P()
    specs = {
        "client": (cspec, cspec),
        "sizes": cspec,
        "server": (sspec, sspec),
        "d_round": P(),
        "d_server": P(),
        "n0": P(),
        # "sel" ([C] int32 selected-client ids) stays replicated: it indexes
        # the client_state's per-client leaves, whose gather/scatter GSPMD
        # resolves against their own (possibly client-sharded) placement.
        "sel": P(),
    }
    if with_active:
        # dropout indicator [C], alongside the client dim like "sizes"
        specs["active"] = cspec
    return specs
