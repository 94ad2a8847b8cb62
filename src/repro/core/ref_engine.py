"""Pure-NumPy reference oracle for the unified round engine.

A deliberately naive, loop-based float64 implementation of the paper's
Formulas 4-12 — no jit, no scan, no vmap, no clever telescoping — used as
the differential-test target for :func:`repro.core.engine.round_core`
(tests/test_engine_diff.py).  Every algorithm mode the engine supports is
mirrored here:

  * local SGD / restart-SGDM (Formula 11) / communicated-momentum (FedDA);
  * FedAvg aggregation with n_k/n' weights (steps 3-4);
  * FedDU dynamic server update (Formulas 4-7) with g0_bar computed
    LITERALLY as the average of the per-step gradients along the server
    SGD path (Formula 6) — the engine uses the telescoping identity
    (w_start - w_end)/(tau*eta), which is exact for plain SGD, so any
    disagreement beyond float tolerance is a bug;
  * FedDUM server momentum on the pseudo-gradient (Formulas 8/12, with
    the descent-consistent sign — see repro.core.momentum);
  * the static-shape masked mode (``cfg.use_masks``): params, gradients
    and momentum buffers are multiplied by the 0/1 keep-masks in
    ``state["masks"]`` every round, exactly where the engine does (NumPy
    broadcasting: the masks may be factored, size 1 on unpruned axes);
  * the client-state algorithms: FedProx's proximal pull
    ``g + mu * (theta - anchor)`` inside each local step, and FedDyn's
    per-client correction in the engine's alpha-scaled parameterization
    (``h`` stores ``alpha * h_paper``): local gradient
    ``g + alpha * (theta - anchor) - h_k``, per-client update
    ``h_k <- h_k - alpha * act_k * (theta_k^end - anchor)``, shared
    ``h <- h - (alpha / N) * sum_k act_k * drift_k``, and server
    correction ``w_half <- w_half - h / alpha`` (skipped entirely when
    ``alpha == 0``, where ``h`` is identically zero);
  * straggler/dropout: when ``batch["active"]`` is present, aggregation
    runs in delta form ``base + sum w_k (local_k - base)`` with
    ``w = sizes * active / max(sum, 1e-12)``, so an all-dropped round is
    exactly a no-op and dropped clients' state is untouched.

The Formula-7 accuracy gate matches the engine's fused semantics: the
accuracy of w^{t-1/2} evaluated on the FIRST server batch.

`jax.tree` is used ONLY for pytree structure traversal; every number is
produced by NumPy in float64.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax  # tree structure only — no jnp math in this module
import numpy as np

from repro.core.engine import EngineConfig


def tree_f64(tree: Any) -> Any:
    return jax.tree.map(lambda x: np.asarray(x, np.float64), tree)


def _zeros_like(tree: Any) -> Any:
    return jax.tree.map(lambda x: np.zeros_like(np.asarray(x, np.float64)), tree)


def _index(tree: Any, *idx) -> Any:
    sl = tuple(idx)
    return jax.tree.map(lambda x: np.asarray(x, np.float64)[sl]
                        if np.issubdtype(np.asarray(x).dtype, np.floating)
                        else np.asarray(x)[sl], tree)


def ref_tau_eff(feddu, *, acc: float, round_idx: float, n0: float,
                n_prime: float, d_round: float, d_server: float,
                tau: int) -> float:
    """Formula 7, scalar float64."""
    if feddu.static_tau_eff is not None:
        return float(feddu.static_tau_eff)
    if feddu.f_prime_kind == "1-acc":
        gate = 1.0 - acc
    elif feddu.f_prime_kind == "inv":
        gate = 1.0 / (acc + feddu.eps)
    else:
        raise ValueError(feddu.f_prime_kind)
    num = n0 * d_round
    den = num + n_prime * d_server + feddu.eps
    return gate * (num / den) * feddu.C * (feddu.decay ** round_idx) * tau


def ref_local_train(cfg: EngineConfig, grad_fn: Callable, params: Any,
                    m0: Any, batches: list, lr: float,
                    anchor: Any = None, h: Any = None):
    """E local epochs on one client — Formula 11 when momentum is on.

    ``anchor`` is the round-start global model for the FedProx/FedDyn
    correction terms; ``h`` is this client's (alpha-scaled) FedDyn
    correction.  Both are ignored under plain FedAvg.
    """
    use_m = cfg.local_momentum != "none"
    beta = cfg.feddum.beta_local
    p, m = params, m0
    for b in batches:
        g = grad_fn(p, b)
        if cfg.algorithm == "fedprox":
            mu = cfg.fedprox.mu
            g = jax.tree.map(lambda gi, pi, ai: gi + mu * (pi - ai),
                             g, p, anchor)
        elif cfg.algorithm == "feddyn":
            alpha = cfg.feddyn.alpha
            g = jax.tree.map(lambda gi, pi, ai, hi: gi + alpha * (pi - ai) - hi,
                             g, p, anchor, h)
        if use_m:
            m = jax.tree.map(lambda mi, gi: beta * mi + (1 - beta) * gi, m, g)
            upd = m
        else:
            upd = g
        p = jax.tree.map(lambda pi, u: pi - lr * u, p, upd)
    return p, m


def ref_round(cfg: EngineConfig, grad_fn: Callable, loss_and_acc_fn: Callable,
              state: dict, batch: dict) -> tuple[dict, dict]:
    """One federated round, naive float64 — mirrors ``engine.round_core``.

    grad_fn(params, batch) and loss_and_acc_fn(params, batch) must be pure
    NumPy (see :class:`SoftmaxRegression` for the differential-test model);
    ``batch`` has the same layout as the engine's round batch, with NumPy
    leaves.
    """
    if cfg.use_masks:
        masks = tree_f64(state["masks"])
        _m = lambda t: jax.tree.map(lambda x, mk: x * mk, t, masks)
        base_grad_fn = grad_fn
        grad_fn = lambda p, b: _m(base_grad_fn(p, b))
    else:
        _m = lambda t: t

    params = _m(tree_f64(state["params"]))
    lr = cfg.lr * (cfg.lr_decay ** float(state["round"]))
    sizes = np.asarray(batch["sizes"], np.float64)
    num_clients = sizes.shape[0]
    steps = len(jax.tree.leaves(batch["client"])[0][0])

    # (2) local epochs on every selected client
    if cfg.local_momentum == "communicated":
        m0 = _m(tree_f64(state["global_m"]))
    else:
        m0 = _zeros_like(params)
    anchor = params if cfg.algorithm in ("fedprox", "feddyn") else None
    if cfg.algorithm == "feddyn":
        if "sel" not in batch:
            raise ValueError("feddyn needs batch['sel'] to index client state")
        sel = np.asarray(batch["sel"], np.int64)
        h_all = tree_f64(state["client_state"]["per_client"]["h"])
        h_sels = [_m(jax.tree.map(lambda x: x[sel[c]], h_all))
                  for c in range(num_clients)]
    else:
        h_sels = [None] * num_clients
    locals_, local_ms = [], []
    for c in range(num_clients):
        bs = [_index(batch["client"], c, s) for s in range(steps)]
        p, m = ref_local_train(cfg, grad_fn, params, m0, bs, lr,
                               anchor=anchor, h=h_sels[c])
        locals_.append(p)
        local_ms.append(m)

    # Fault injection + in-scan health guard, mirroring the engine: faults
    # corrupt the uploaded updates first, then non-finite clients are
    # scrubbed back to the broadcast point and zero-weighted.
    active = batch.get("active")
    guard_on = cfg.guard != "off"
    if cfg.faults:
        sel_ids = np.asarray(batch.get("sel", np.arange(num_clients)))
        for f in cfg.faults:
            locals_ = f.ref_apply_client(locals_, params, sel_ids,
                                         float(state["round"]))
    base_act = (np.asarray(active, np.float64) if active is not None
                else np.ones_like(sizes))
    if guard_on:
        client_ok = np.ones(num_clients, bool)
        for c in range(num_clients):
            checked = [locals_[c]]
            if cfg.local_momentum == "communicated":
                checked.append(local_ms[c])
            for tree in checked:
                for leaf in jax.tree.leaves(tree):
                    client_ok[c] &= bool(np.isfinite(leaf).all())
        rejected = float((base_act * (~client_ok)).sum())
        act = base_act * client_ok
        locals_ = [locals_[c] if client_ok[c] else
                   jax.tree.map(np.copy, params)
                   for c in range(num_clients)]
        if cfg.local_momentum == "communicated":
            local_ms = [local_ms[c] if client_ok[c] else
                        jax.tree.map(np.copy, m0)
                        for c in range(num_clients)]
    else:
        rejected = 0.0
        act = base_act

    # (3-4) FedAvg aggregation with n_k/n' weights; when the batch carries
    # an "active" vector (straggler/dropout) or the guard is on, run in
    # delta form so dropped clients contribute exactly zero and an
    # all-dropped round is a no-op.
    if active is not None or guard_on:
        w = sizes * act
        w = w / max(w.sum(), 1e-12)

        def weighted_mean(trees, base):
            return jax.tree.map(
                lambda b_, *leaves: b_ + sum(wi * (li - b_)
                                             for wi, li in zip(w, leaves)),
                base, *trees)

        w_half = weighted_mean(locals_, params)
        new_global_m = (weighted_mean(local_ms, m0)
                        if cfg.local_momentum == "communicated" else None)
    else:
        w = sizes / sizes.sum()

        def weighted_mean(trees):
            return jax.tree.map(
                lambda *leaves: sum(wi * li for wi, li in zip(w, leaves)),
                *trees)

        w_half = weighted_mean(locals_)
        new_global_m = (weighted_mean(local_ms)
                        if cfg.local_momentum == "communicated" else None)

    # (4b) FedDyn correction updates + server-side correction of w_half
    new_client_state = state.get("client_state")
    if cfg.algorithm == "feddyn":
        alpha = cfg.feddyn.alpha
        n_total = jax.tree.leaves(h_all)[0].shape[0]
        drifts = [jax.tree.map(lambda l, p0: l - p0, locals_[c], params)
                  for c in range(num_clients)]

        def scatter(ha, *rows):
            out = ha.copy()
            for c in range(num_clients):
                out[sel[c]] = rows[c]
            return out

        h_sel_new = [jax.tree.map(lambda hk, d, a=act[c]: hk - alpha * a * d,
                                  h_sels[c], drifts[c])
                     for c in range(num_clients)]
        h_new = jax.tree.map(scatter, h_all, *h_sel_new)
        h_shared = _m(tree_f64(state["client_state"]["shared"]["h"]))
        h_shared_new = jax.tree.map(
            lambda hs, *ds: hs - (alpha / n_total) * sum(
                a * d for a, d in zip(act, ds)),
            h_shared, *drifts)
        if alpha > 0:  # static branch: at alpha == 0, h is identically zero
            w_half = jax.tree.map(lambda wh, hs: wh - hs / alpha,
                                  w_half, h_shared_new)
        new_client_state = {"per_client": {"h": _m(h_new)},
                            "shared": {"h": _m(h_shared_new)}}

    # (5a) FedDU: tau server SGD steps; g0_bar is the literal Formula-6
    # average of the per-step gradients; acc gate from the first forward.
    if cfg.use_server_update:
        tau = len(jax.tree.leaves(batch["server"])[0])
        p = w_half
        grads = []
        acc = 0.0
        for i in range(tau):
            b = _index(batch["server"], i)
            _, a = loss_and_acc_fn(p, b)
            if i == 0:
                acc = float(a)
            g = grad_fn(p, b)
            grads.append(g)
            p = jax.tree.map(lambda pi, gi: pi - lr * gi, p, g)
        g0 = jax.tree.map(lambda *gs: sum(gs) / tau, *grads)
        t_eff = ref_tau_eff(cfg.feddu, acc=acc, round_idx=float(state["round"]),
                            n0=float(batch["n0"]), n_prime=float(sizes.sum()),
                            d_round=float(batch["d_round"]),
                            d_server=float(batch["d_server"]), tau=tau)
        proposed = jax.tree.map(lambda pi, gi: pi - t_eff * lr * gi, w_half, g0)
    else:
        proposed = w_half
        t_eff, acc = 0.0, 0.0

    # Server-step guard mirror: a non-finite proposal falls back to w_half.
    server_ok = True
    if guard_on and cfg.use_server_update:
        server_ok = bool(np.isfinite(t_eff) and np.isfinite(acc)
                         and all(np.isfinite(l).all()
                                 for l in jax.tree.leaves(proposed)))
        if not server_ok:
            proposed = w_half
            t_eff, acc = 0.0, 0.0

    # (5b) FedDUM server momentum on the pseudo-gradient (Formulas 8/12)
    if cfg.server_momentum:
        pseudo = jax.tree.map(lambda a, b_: a - b_, params, proposed)
        bs_ = cfg.feddum.beta_server
        m = jax.tree.map(lambda mi, g: bs_ * mi + (1 - bs_) * g,
                         tree_f64(state["server_m"]), pseudo)
        new_params = jax.tree.map(
            lambda pi, mi: pi - cfg.feddum.eta_server * mi, params, m)
    else:
        m = tree_f64(state["server_m"])
        new_params = proposed

    new_state = {"params": _m(new_params), "server_m": _m(m),
                 "round": float(state["round"]) + 1.0}
    if cfg.local_momentum == "communicated":
        new_state["global_m"] = _m(new_global_m)
    if cfg.use_masks:
        new_state["masks"] = masks
    if new_client_state is not None:
        new_state["client_state"] = new_client_state

    # Round-discard mirror: restore the round-start carry (round counter
    # advances) when the guard voids the round.
    if guard_on:
        survivors = float(np.sum(act)) > 0
        if cfg.guard == "reject_client":
            discard = not survivors
        else:  # skip_round
            discard = (not survivors) or rejected > 0 or not server_ok
        health = rejected + (0.0 if server_ok else 1.0)
        if discard:
            for k in ("params", "server_m", "global_m", "client_state"):
                if k in new_state:
                    new_state[k] = tree_f64(state[k])
            t_eff, acc = 0.0, 0.0
    else:
        health = 0.0
    return new_state, {"tau_eff": t_eff, "server_acc": acc,
                       "health": health}


def ref_init_state(params: Any, cfg: EngineConfig, masks: Any = None,
                   num_clients: int | None = None) -> dict:
    state = {"params": tree_f64(params), "server_m": _zeros_like(params),
             "round": 0.0}
    if cfg.local_momentum == "communicated":
        state["global_m"] = _zeros_like(params)
    if cfg.use_masks:
        state["masks"] = (tree_f64(masks) if masks is not None else
                          jax.tree.map(lambda x: np.ones(
                              (1,) * np.ndim(x), np.float64), params))
    if cfg.algorithm == "fedprox":
        state["client_state"] = {"per_client": {}, "shared": {}}
    elif cfg.algorithm == "feddyn":
        if num_clients is None:
            raise ValueError("feddyn needs num_clients for its per-client h")
        state["client_state"] = {
            "per_client": {"h": jax.tree.map(
                lambda x: np.zeros((num_clients,) + np.shape(x), np.float64),
                params)},
            "shared": {"h": _zeros_like(params)},
        }
    return state


# ---------------------------------------------------------------------------
# Differential-test model: softmax regression with a CLOSED-FORM NumPy
# gradient (no autodiff anywhere on the oracle side)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class SoftmaxRegression:
    """Linear softmax classifier, d features -> c classes.

    NumPy methods feed the oracle; the jnp-free closed-form gradient also
    cross-checks `jax.grad` on the engine side.  ``loss_and_acc`` (the
    PaperModel-style (params, x, y) interface) is provided by the test via
    jnp so the engine path stays pure-JAX.
    """

    dim: int = 6
    num_classes: int = 4

    def init(self, seed: int = 0) -> dict:
        rng = np.random.default_rng(seed)
        return {
            "w": (0.5 * rng.standard_normal((self.dim, self.num_classes))
                  ).astype(np.float32),
            "b": np.zeros((self.num_classes,), np.float32),
        }

    @staticmethod
    def _logits(params, x):
        return x @ params["w"] + params["b"]

    def np_loss_and_acc(self, params, batch):
        x, y = np.asarray(batch[0]), np.asarray(batch[1])
        z = self._logits(params, x)
        z = z - z.max(axis=1, keepdims=True)
        logp = z - np.log(np.exp(z).sum(axis=1, keepdims=True))
        loss = -logp[np.arange(len(y)), y].mean()
        acc = (z.argmax(axis=1) == y).mean()
        return loss, acc

    def np_grad(self, params, batch):
        x, y = np.asarray(batch[0]), np.asarray(batch[1])
        z = self._logits(params, x)
        z = z - z.max(axis=1, keepdims=True)
        p = np.exp(z)
        p /= p.sum(axis=1, keepdims=True)
        p[np.arange(len(y)), y] -= 1.0
        p /= len(y)
        return {"w": x.T @ p, "b": p.sum(axis=0)}
