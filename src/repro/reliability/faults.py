"""Deterministic fault injection — every recovery claim gets a repro.

A :class:`FaultPlan` is an ordered, hashable tuple of fault events that
threads through TEST-ONLY hooks at three levels of the stack:

* **device faults** (:class:`NaNGrad`, :class:`CorruptUpdate`) rewrite a
  matching client's uploaded model update *inside* ``round_core`` — the
  fault is part of the traced graph (a static unroll over the fault
  tuple), so it fires deterministically at the configured (client, round)
  under jit, scan, and the mesh backend alike, and the in-scan health
  guard (``EngineConfig.guard``) is exercised by exactly the corruption
  the test asked for.  Each fault mirrors itself in NumPy float64
  (``ref_apply_client``) so the oracle in :mod:`repro.core.ref_engine`
  sees the same corrupted uploads;
* **host faults** (:class:`KillAfterChunk`) fire in the
  :class:`~repro.core.backend.PlanExecutor` schedule loop, raising
  :class:`SimulatedCrash` AFTER the chunk-boundary checkpoint write — the
  resume-bit-identity tests kill a run exactly where a real preemption
  would land;
* **serving faults** (:class:`NaNLogits`) poison one decode slot's
  logits inside the wave program, driving the engine's non-finite-logit
  slot retirement.

Faults are frozen dataclasses (hashable), so a device-fault tuple can
ride in the frozen :class:`~repro.core.engine.EngineConfig` that keys the
session compile cache.
"""
from __future__ import annotations

import dataclasses


class SimulatedCrash(RuntimeError):
    """Raised by the executor when a :class:`KillAfterChunk` fault fires.

    The crash is injected AFTER the chunk's checkpoint write (exactly like
    a preemption between chunks), so ``FederatedTrainer.resume`` can
    continue the run from the snapshot on disk."""


class FaultPlan(tuple):
    """An ordered, hashable collection of fault events.

    ``FaultPlan(NaNGrad(client=3, round=5), KillAfterChunk(2))`` — pass it
    (or a plain tuple) as ``FLConfig(faults=...)``; the trainer routes
    device faults into the engine config and host faults into the
    executor."""

    def __new__(cls, *faults):
        return super().__new__(cls, faults)

    @property
    def device(self) -> tuple:
        return tuple(f for f in self if hasattr(f, "apply_client"))

    @property
    def host(self) -> tuple:
        return tuple(f for f in self if hasattr(f, "chunks"))


def _bcast(v, leaf):
    """Broadcast a [C] vector over a [C, ...] leaf."""
    return v.reshape(v.shape + (1,) * (leaf.ndim - 1))


@dataclasses.dataclass(frozen=True)
class NaNGrad:
    """Client ``client``'s uploaded update becomes all-NaN at global round
    ``round`` (matched against the scan carry's round counter and the
    sampled ``batch["sel"]`` indices — the client must be selected that
    round for the fault to land)."""

    client: int
    round: int

    def apply_client(self, locals_, params, sel, round_):
        import jax
        import jax.numpy as jnp

        hit = (sel == self.client) & (round_ == float(self.round))
        return jax.tree.map(
            lambda l: jnp.where(_bcast(hit, l), jnp.float32(jnp.nan),
                                l).astype(l.dtype), locals_)

    def ref_apply_client(self, locals_, params, sel, round_):
        import jax
        import numpy as np

        out = []
        for c, tree in enumerate(locals_):
            if int(sel[c]) == self.client and round_ == float(self.round):
                tree = jax.tree.map(lambda l: np.full_like(l, np.nan), tree)
            out.append(tree)
        return out


@dataclasses.dataclass(frozen=True)
class CorruptUpdate:
    """Scale a client's update delta around the broadcast round-start
    model: ``theta_k <- theta_global + scale * (theta_k - theta_global)``.
    ``client=None`` / ``round=None`` match every client / every round.
    Large scales (e.g. 1e6) model a diverged or byzantine upload that is
    still finite in f32 — the guard catches it only once it overflows
    downstream, which is exactly the scenario worth testing."""

    scale: float = 1e6
    client: int | None = None
    round: int | None = None

    def _hit(self, sel, round_, ones):
        hit = ones
        if self.client is not None:
            hit = hit & (sel == self.client)
        if self.round is not None:
            hit = hit & (round_ == float(self.round))
        return hit

    def apply_client(self, locals_, params, sel, round_):
        import jax
        import jax.numpy as jnp

        hit = self._hit(sel, round_, jnp.ones(sel.shape, bool))
        return jax.tree.map(
            lambda l, p: jnp.where(
                _bcast(hit, l),
                p.astype(jnp.float32) + self.scale
                * (l.astype(jnp.float32) - p.astype(jnp.float32)),
                l.astype(jnp.float32)).astype(l.dtype),
            locals_, params)

    def ref_apply_client(self, locals_, params, sel, round_):
        import jax
        import numpy as np

        np_hit = self._hit(np.asarray(sel), round_,
                           np.ones(np.shape(sel), bool))
        out = []
        for c, tree in enumerate(locals_):
            if np_hit[c]:
                tree = jax.tree.map(lambda l, p: p + self.scale * (l - p),
                                    tree, params)
            out.append(tree)
        return out


@dataclasses.dataclass(frozen=True)
class KillAfterChunk:
    """Host fault: the executor raises :class:`SimulatedCrash` once
    ``chunks`` Scan chunks have completed (counted over the WHOLE run, so
    a resumed run that restored ``chunks_done > chunks`` does not re-die).
    The chunk-boundary checkpoint (if configured) is written first."""

    chunks: int

    def __post_init__(self):
        if self.chunks < 1:
            raise ValueError(f"chunks must be >= 1, got {self.chunks}")


@dataclasses.dataclass(frozen=True)
class NaNLogits:
    """Serving fault: slot ``slot``'s logits become NaN on the decode step
    where its emitted-token count equals ``n_out`` (fires at most once per
    occupancy — after retirement the error bit is cleared on admit)."""

    slot: int
    n_out: int = 0

    def apply_logits(self, logits, state):
        import jax.numpy as jnp

        hit = ((state["slot"] == self.slot)
               & (state["n_out"] == self.n_out) & state["active"])
        return jnp.where(hit[:, None, None], jnp.float32(jnp.nan),
                         logits.astype(jnp.float32)).astype(logits.dtype)


def device_faults(faults) -> tuple:
    """The subset of ``faults`` that runs inside ``round_core``."""
    return tuple(f for f in (faults or ()) if hasattr(f, "apply_client"))


def host_faults(faults) -> tuple:
    """The subset of ``faults`` the executor schedule loop handles."""
    return tuple(f for f in (faults or ()) if hasattr(f, "chunks"))
