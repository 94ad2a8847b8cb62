"""Logical-axis -> mesh PartitionSpec rules.

The mesh is (data=16, model=16) single-pod or (pod=2, data=16, model=16)
multi-pod.  FL semantics determine the *client*
axis (DESIGN.md Section 4):

  * data-client archs (<= ~10B): clients live on 'data' (x 'pod' when
    multi-pod) — parameters carry a leading client dim sharded over those
    axes; TP shards head/ffn dims over 'model'.
  * pod-client archs (cross-silo giants): clients live on 'pod'; inside a
    silo parameters are FSDP-sharded over 'data' and TP-sharded over
    'model'.

Every mapping is divisibility-checked against the actual dim size; a
non-divisible dim falls back to replication (e.g. whisper's 12 heads on a
16-way model axis) — the production-safe default.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs.base import ModelConfig

# logical axis -> candidate mesh-axis role
_TP_AXES = {"vocab", "heads", "kv_heads", "mlp", "expert_mlp", "experts", "ssm_inner"}
_FSDP_AXES = {"embed"}


@dataclasses.dataclass(frozen=True)
class MeshPlan:
    mesh: Mesh
    multi_pod: bool
    client_axes: tuple          # mesh axes hosting FL clients
    fsdp_axes: tuple            # mesh axes for parameter FSDP
    tp_axes: tuple              # mesh axes for tensor parallelism
    batch_axes: tuple           # mesh axes sharding the within-client batch
    num_clients: int

    def axis_size(self, names: tuple) -> int:
        s = 1
        for n in names:
            s *= self.mesh.shape[n]
        return s


def make_plan(mesh: Mesh, cfg: ModelConfig) -> MeshPlan:
    multi_pod = "pod" in mesh.shape
    if cfg.fl_client_axis == "data":
        client_axes = ("pod", "data") if multi_pod else ("data",)
        fsdp_axes = ()
        batch_axes = ()
    elif cfg.fl_client_axis == "pod":
        client_axes = ("pod",) if multi_pod else ()
        fsdp_axes = ("data",) if cfg.fsdp else ()
        batch_axes = ("data",)
    else:
        client_axes = ()
        fsdp_axes = ("data",) if cfg.fsdp else ()
        batch_axes = ("data",) if not multi_pod else ("pod", "data")
    num_clients = 1
    for a in client_axes:
        num_clients *= mesh.shape[a]
    return MeshPlan(mesh=mesh, multi_pod=multi_pod, client_axes=client_axes,
                    fsdp_axes=fsdp_axes, tp_axes=("model",),
                    batch_axes=batch_axes, num_clients=num_clients)


# §Perf C1 note: jit input shardings must divide evenly, so non-divisible
# head counts are handled by WEIGHT-LEVEL padding at init (REPRO_PAD_HEADS
# in repro.models.layers.init_attention), not by relaxing this check.


def _divisible(dim: int, plan: MeshPlan, axes: tuple) -> bool:
    return dim % plan.axis_size(axes) == 0 if axes else True


def _shardable(name: str, dim: int, plan: MeshPlan, axes: tuple) -> bool:
    return _divisible(dim, plan, axes)


def _spec_for(shape: tuple, logical: tuple, plan: MeshPlan,
              *, client_leading: bool) -> P:
    """PartitionSpec for one tensor given its logical axis names."""
    parts: list = []
    used: set = set()
    offset = 0
    if client_leading:
        ca = tuple(a for a in plan.client_axes)
        if ca and _divisible(shape[0], plan, ca):
            parts.append(ca if len(ca) > 1 else ca[0])
            used.update(ca)
        else:
            parts.append(None)
        offset = 1
    for i, name in enumerate(logical):
        dim = shape[offset + i]
        target: Optional[tuple] = None
        if name in _TP_AXES:
            target = plan.tp_axes
        elif name in _FSDP_AXES and plan.fsdp_axes:
            target = plan.fsdp_axes
        if target and not used.intersection(target) and _shardable(name, dim, plan, target):
            parts.append(target if len(target) > 1 else target[0])
            used.update(target)
        else:
            parts.append(None)
    return P(*parts)


def param_specs(shapes: Any, axes: Any, plan: MeshPlan,
                *, client_leading: bool = False) -> Any:
    """PartitionSpec tree matching the param tree.

    shapes: pytree of ShapeDtypeStruct (or arrays); axes: logical-axis tree.
    client_leading: params carry a leading FL-client dim (the federated
    training state).
    """
    # axes-tree leaves are plain tuples (pytree nodes), so flatten the two
    # trees separately with parallel leaf orders and zip.
    s_leaves, s_def = jax.tree.flatten(shapes)
    a_leaves = jax.tree.leaves(axes, is_leaf=lambda x: isinstance(x, tuple))
    if len(s_leaves) != len(a_leaves):
        raise ValueError(f"param/axes tree mismatch: {len(s_leaves)} vs {len(a_leaves)}")
    specs = [_spec_for(s.shape, ax, plan, client_leading=client_leading)
             for s, ax in zip(s_leaves, a_leaves)]
    return jax.tree.unflatten(s_def, specs)
