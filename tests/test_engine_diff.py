"""Differential tests: the scan-compiled engine vs. the pure-NumPy oracle.

The core lock is ONE table-driven parity fixture
(``test_parity_table_local_mesh_oracle``): every (client algorithm,
momentum mode, use_masks) combination — FedAvg / FedProx / FedDyn crossed
with the FedDU / FedDUM / FedDA wirings, plus dropout rows — runs for
several rounds through THREE legs

  * `repro.core.engine.round_core` under `jax.lax.scan` + `jit` (exactly
    how the plan executor's backends execute it),
  * the SAME scan with the round state placed on a host mesh through
    ``fl_specs.fl_state_specs`` NamedShardings (the MeshBackend's state
    placement, client_state per-client leaves included), and
  * `repro.core.ref_engine.ref_round` — naive float64 NumPy loops,

on identical explicit batches, and every leg must agree with the oracle to
<= 1e-5 PER ROUND through one shared assertion helper — a new engine mode
gets locked by adding one table row.

Limit tests pin the client algorithms'
exact reductions: FedProx mu=0 is BIT-identical to FedAvg, FedDyn alpha=0
matches FedAvg <= 1e-6.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import engine, ref_engine
from repro.core.engine import EngineConfig
from repro.core.ref_engine import SoftmaxRegression
from repro.models.cnn import softmax_xent_acc

DIM, CLASSES = 6, 4
CLIENTS, STEPS, BATCH = 3, 2, 5
TAU, SBATCH = 3, 5
ROUNDS = 3


@pytest.fixture(scope="module")
def world():
    model = SoftmaxRegression(dim=DIM, num_classes=CLASSES)
    rng = np.random.default_rng(42)
    params = model.init(seed=7)

    def batches(lead):
        x = rng.standard_normal(lead + (DIM,)).astype(np.float32)
        y = rng.integers(0, CLASSES, lead).astype(np.int32)
        return x, y

    rounds = []
    for _ in range(ROUNDS):
        cx, cy = batches((CLIENTS, STEPS, BATCH))
        sx, sy = batches((TAU, SBATCH))
        rounds.append({
            "client": (cx, cy),
            "sizes": np.asarray([40.0, 25.0, 35.0], np.float32),
            "server": (sx, sy),
            "d_round": np.float32(0.3),
            "d_server": np.float32(0.02),
            "n0": np.float32(500.0),
        })
    return model, params, rounds


def jnp_loss_and_acc(params, b):
    logits = b[0] @ params["w"] + params["b"]
    return softmax_xent_acc(logits, b[1])


def jnp_grad(params, b):
    return jax.grad(lambda p: jnp_loss_and_acc(p, b)[0])(params)


MODES = {
    "fedavg": dict(use_server_update=False, local_momentum="none",
                   server_momentum=False),
    "feddu": dict(use_server_update=True, local_momentum="none",
                  server_momentum=False),
    "server_momentum": dict(use_server_update=True, local_momentum="none",
                            server_momentum=True),
    "device_momentum": dict(use_server_update=True, local_momentum="restart",
                            server_momentum=False),
    "feddum": dict(use_server_update=True, local_momentum="restart",
                   server_momentum=True),
    "fedda": dict(use_server_update=True, local_momentum="communicated",
                  server_momentum=True),
}

# ---------------------------------------------------------------------------
# THE parity table: every (client algorithm, momentum mode, use_masks)
# combination + dropout rows, each run local-scan vs mesh-placed-scan vs
# f64 oracle through ONE assertion helper.  A new engine mode gets locked
# by adding one row here.
# ---------------------------------------------------------------------------

N_TOTAL = 6          # total clients (sizes the FedDyn per-client state)
SELS = np.asarray([[4, 1, 3], [0, 2, 5], [5, 0, 2]], np.int32)
# dropout rows: round 1 drops EVERY client — the aggregation must be an
# exact no-op (delta form), with client state untouched
ACTIVES = np.asarray([[1, 0, 1], [0, 0, 0], [1, 1, 1]], np.float32)

ALGOS = {
    "fedavg": {},
    "fedprox": dict(algorithm="fedprox",
                    fedprox=engine.FedProxConfig(mu=0.05)),
    "feddyn": dict(algorithm="feddyn",
                   feddyn=engine.FedDynConfig(alpha=0.05)),
}

PARITY_TABLE = [
    (algo, mode, use_masks, False)
    for algo in ALGOS
    for mode in MODES
    for use_masks in (False, True)
] + [
    ("fedavg", "feddum", False, True),
    ("fedprox", "feddum", False, True),
    ("feddyn", "feddum", False, True),
]


def _row_id(row):
    algo, mode, use_masks, dropout = row
    return (f"{algo}-{mode}" + ("-masked" if use_masks else "")
            + ("-dropout" if dropout else ""))


def _parity_masks():
    rng = np.random.default_rng(3)
    return {"w": (rng.random((DIM, CLASSES)) > 0.4).astype(np.float32),
            "b": (rng.random((CLASSES,)) > 0.4).astype(np.float32)}


def _parity_rounds(rounds, dropout):
    out = []
    for r, b in enumerate(rounds):
        b = dict(b)
        b["sel"] = SELS[r]
        if dropout:
            b["active"] = ACTIVES[r]
        out.append(b)
    return out


def _engine_history(cfg, state0, rounds, *, mesh=False):
    """Run the scan-compiled engine and return per-round
    (params, server_m, tau_eff) histories.  ``mesh=True`` places the round
    state through ``fl_state_specs`` NamedShardings on a host mesh first —
    the MeshBackend's state placement, client_state included."""
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs),
                           *[jax.tree.map(jnp.asarray, b) for b in rounds])
    state0 = jax.tree.map(jnp.asarray, state0)
    if mesh:
        from jax.sharding import NamedSharding, PartitionSpec as P

        from repro.launch.mesh import make_host_mesh
        from repro.sharding.fl_specs import fl_state_specs
        from repro.sharding.specs import MeshPlan

        m = make_host_mesh(model=1)
        plan = MeshPlan(mesh=m, multi_pod=False, client_axes=("data",),
                        fsdp_axes=(), tp_axes=(), batch_axes=(),
                        num_clients=m.shape["data"])
        specs = fl_state_specs(state0, None, plan,
                               client_axes=plan.client_axes)
        state0 = jax.device_put(state0, jax.tree.map(
            lambda s: NamedSharding(m, s), specs,
            is_leaf=lambda x: isinstance(x, P)))
        stacked = jax.device_put(stacked, NamedSharding(m, P()))

    @jax.jit
    def run(state, batches):
        def body(st, b):
            st, metrics = engine.round_core(cfg, jnp_grad, jnp_loss_and_acc,
                                            st, b)
            return st, (metrics["tau_eff"], st["params"], st["server_m"])
        return jax.lax.scan(body, state, batches)

    _, (taus, phist, mhist) = run(state0, stacked)
    return phist, mhist, taus


def _assert_leg_matches_oracle(leg, phist, mhist, taus, ref_params, ref_ms,
                               ref_taus, row_id, masks=None):
    """THE shared assertion: each leg agrees with the f64 oracle <= 1e-5
    on params, server momentum and tau_eff — PER ROUND."""
    for r in range(ROUNDS):
        for leaf, ref_leaf in zip(jax.tree.leaves(phist),
                                  jax.tree.leaves(ref_params[r])):
            np.testing.assert_allclose(
                np.asarray(leaf[r]), ref_leaf, atol=1e-5,
                err_msg=f"[{row_id}] {leg} params diverged at round {r}")
        for leaf, ref_leaf in zip(jax.tree.leaves(mhist),
                                  jax.tree.leaves(ref_ms[r])):
            np.testing.assert_allclose(
                np.asarray(leaf[r]), ref_leaf, atol=1e-5,
                err_msg=f"[{row_id}] {leg} server_m diverged at round {r}")
    np.testing.assert_allclose(np.asarray(taus), np.asarray(ref_taus),
                               atol=1e-5,
                               err_msg=f"[{row_id}] {leg} tau_eff")
    if masks is not None:
        # pruned coordinates stay exactly zero on every leg
        for leaf, m in zip(jax.tree.leaves(phist), jax.tree.leaves(masks)):
            np.testing.assert_array_equal(np.asarray(leaf[-1])[m == 0], 0.0)


@pytest.mark.parametrize("algo,mode,use_masks,dropout", PARITY_TABLE,
                         ids=[_row_id(r) for r in PARITY_TABLE])
def test_parity_table_local_mesh_oracle(world, algo, mode, use_masks,
                                        dropout):
    model, params, rounds = world
    cfg = EngineConfig(lr=0.08, lr_decay=0.97, use_masks=use_masks,
                       **ALGOS[algo], **MODES[mode])
    rounds = _parity_rounds(rounds, dropout)
    masks = _parity_masks() if use_masks else None

    state0 = engine.init_round_state(jax.tree.map(jnp.asarray, params), cfg,
                                     num_clients=N_TOTAL)
    if masks is not None:
        state0["masks"] = jax.tree.map(jnp.asarray, masks)

    # oracle leg: naive float64 NumPy loops, per-round history
    ref = ref_engine.ref_init_state(params, cfg, masks=masks,
                                    num_clients=N_TOTAL)
    ref_params, ref_ms, ref_taus = [], [], []
    for b in rounds:
        ref, met = ref_engine.ref_round(cfg, model.np_grad,
                                        model.np_loss_and_acc, ref, b)
        ref_params.append(ref["params"])
        ref_ms.append(ref["server_m"])
        ref_taus.append(met["tau_eff"])

    row_id = _row_id((algo, mode, use_masks, dropout))
    for leg, on_mesh in (("local", False), ("mesh", True)):
        phist, mhist, taus = _engine_history(cfg, state0, rounds,
                                             mesh=on_mesh)
        _assert_leg_matches_oracle(leg, phist, mhist, taus, ref_params,
                                   ref_ms, ref_taus, row_id, masks=masks)


def _scan_engine(cfg, state0, rounds):
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *rounds)

    @jax.jit
    def run(state, batches):
        def body(st, b):
            st, metrics = engine.round_core(cfg, jnp_grad, jnp_loss_and_acc,
                                            st, b)
            return st, metrics["tau_eff"]
        return jax.lax.scan(body, state, batches)

    return run(state0, stacked)


def test_fedprox_mu0_bit_identical_to_fedavg(world):
    """mu = 0 multiplies the proximal term to EXACT zero: the FedProx
    engine must be bit-identical to FedAvg on the same batches."""
    _, params, rounds = world
    base = dict(lr=0.08, lr_decay=0.97, **MODES["feddum"])
    cfg_avg = EngineConfig(**base)
    cfg_px = EngineConfig(algorithm="fedprox",
                          fedprox=engine.FedProxConfig(mu=0.0), **base)
    rounds = _parity_rounds(rounds, False)
    s_avg, t_avg = _scan_engine(
        cfg_avg, engine.init_round_state(jax.tree.map(jnp.asarray, params),
                                         cfg_avg), rounds)
    s_px, t_px = _scan_engine(
        cfg_px, engine.init_round_state(jax.tree.map(jnp.asarray, params),
                                        cfg_px, num_clients=N_TOTAL), rounds)
    for a, b in zip(jax.tree.leaves(s_avg["params"]),
                    jax.tree.leaves(s_px["params"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(t_avg), np.asarray(t_px))


def test_feddyn_alpha0_reduces_to_fedavg(world):
    """alpha = 0: the correction state stays exactly zero and the server
    division never enters the graph — FedDyn must match FedAvg <= 1e-6."""
    _, params, rounds = world
    base = dict(lr=0.08, lr_decay=0.97, **MODES["feddum"])
    cfg_avg = EngineConfig(**base)
    cfg_dy = EngineConfig(algorithm="feddyn",
                          feddyn=engine.FedDynConfig(alpha=0.0), **base)
    rounds = _parity_rounds(rounds, False)
    s_avg, _ = _scan_engine(
        cfg_avg, engine.init_round_state(jax.tree.map(jnp.asarray, params),
                                         cfg_avg), rounds)
    s_dy, _ = _scan_engine(
        cfg_dy, engine.init_round_state(jax.tree.map(jnp.asarray, params),
                                        cfg_dy, num_clients=N_TOTAL), rounds)
    for a, b in zip(jax.tree.leaves(s_avg["params"]),
                    jax.tree.leaves(s_dy["params"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)
    # the correction state itself never moved off zero
    for leaf in jax.tree.leaves(s_dy["client_state"]):
        np.testing.assert_array_equal(np.asarray(leaf), 0.0)


@pytest.mark.parametrize("mode", list(MODES))
def test_kernel_masked_compute_matches_params_and_oracle(world, mode):
    """masked_compute="kernel" (filter masks threaded into the model fns)
    must match the param-masked engine AND the f64 oracle to <= 1e-5 in
    every momentum mode.  For the toy softmax model the filter mask is an
    output-class column mask, whose feature-level application
    ((x @ w + b) * m) is algebraically the param-level one
    (x @ (w * m) + b * m) — the same coupled-closure identity the CNN's
    feature-map masking relies on."""
    model, params, rounds = world
    colmask = np.asarray([1.0, 0.0, 1.0, 1.0], np.float32)
    masks = {"w": np.broadcast_to(colmask, (DIM, CLASSES)).copy(),
             "b": colmask.copy()}
    base = dict(lr=0.08, lr_decay=0.97, use_masks=True, **MODES[mode])
    cfg_k = EngineConfig(masked_compute="kernel", **base)
    cfg_p = EngineConfig(masked_compute="params", **base)

    def la_kernel(p, b, fm):
        return softmax_xent_acc((b[0] @ p["w"] + p["b"]) * fm["out"], b[1])

    def grad_kernel(p, b, fm):
        return jax.grad(lambda q: la_kernel(q, b, fm)[0])(p)

    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *rounds)
    state_k = engine.init_round_state(
        jax.tree.map(jnp.asarray, params), cfg_k,
        filter_masks={"out": jnp.ones((CLASSES,))})
    state_k["masks"] = jax.tree.map(jnp.asarray, masks)
    state_k["filter_masks"] = {"out": jnp.asarray(colmask)}

    @jax.jit
    def run_k(state, batches):
        def body(st, b):
            st, metrics = engine.round_core(cfg_k, grad_kernel, la_kernel,
                                            st, b)
            return st, metrics["tau_eff"]
        return jax.lax.scan(body, state, batches)

    state_k, taus_k = run_k(state_k, stacked)

    # params-mode engine on the same masks
    state_p = engine.init_round_state(jax.tree.map(jnp.asarray, params),
                                      cfg_p)
    state_p["masks"] = jax.tree.map(jnp.asarray, masks)
    state_p, taus_p = _scan_engine(cfg_p, state_p, rounds)

    # f64 oracle (params-mode mask semantics — the ground truth for both)
    ref_state = ref_engine.ref_init_state(params, cfg_p, masks=masks)
    for b in rounds:
        ref_state, _ = ref_engine.ref_round(
            cfg_p, model.np_grad, model.np_loss_and_acc, ref_state, b)

    for lk, lp, lr_ in zip(jax.tree.leaves(state_k["params"]),
                           jax.tree.leaves(state_p["params"]),
                           jax.tree.leaves(ref_state["params"])):
        np.testing.assert_allclose(np.asarray(lk), np.asarray(lp), atol=1e-5,
                                   err_msg=f"kernel != params in mode={mode}")
        np.testing.assert_allclose(np.asarray(lk), lr_, atol=1e-5,
                                   err_msg=f"kernel != oracle in mode={mode}")
    np.testing.assert_allclose(np.asarray(taus_k), np.asarray(taus_p),
                               atol=1e-5)
    # pruned coordinates stay exactly zero through the kernel path
    for leaf, m in zip(jax.tree.leaves(state_k["params"]),
                       jax.tree.leaves(masks)):
        np.testing.assert_array_equal(np.asarray(leaf)[m == 0], 0.0)


def test_init_round_state_kernel_mode_requires_filter_masks(world):
    model, params, _ = world
    cfg = EngineConfig(use_masks=True, masked_compute="kernel")
    with pytest.raises(ValueError, match="filter_masks"):
        engine.init_round_state(jax.tree.map(jnp.asarray, params), cfg)
    with pytest.raises(ValueError, match="masked_compute"):
        EngineConfig(masked_compute="dense")


def test_all_ones_masks_equal_unmasked_engine(world):
    """use_masks with all-ones masks must be a numerical no-op, so a masked
    engine can be compiled up front and pruned mid-scan without a re-jit."""
    model, params, rounds = world
    base = dict(lr=0.08, lr_decay=0.97, use_server_update=True,
                local_momentum="restart", server_momentum=True)
    cfg_m = EngineConfig(use_masks=True, **base)
    cfg_u = EngineConfig(use_masks=False, **base)

    state_m, taus_m = _scan_engine(
        cfg_m, engine.init_round_state(jax.tree.map(jnp.asarray, params),
                                       cfg_m), rounds)
    state_u, taus_u = _scan_engine(
        cfg_u, engine.init_round_state(jax.tree.map(jnp.asarray, params),
                                       cfg_u), rounds)
    for a, b in zip(jax.tree.leaves(state_m["params"]),
                    jax.tree.leaves(state_u["params"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(np.asarray(taus_m), np.asarray(taus_u))


def test_closed_form_gradient_matches_jax_grad(world):
    """The oracle's hand-written softmax CE gradient vs. jax.grad."""
    model, params, rounds = world
    b = jax.tree.map(lambda x: x[0, 0], rounds[0]["client"])
    g_np = model.np_grad(params, b)
    g_jax = jnp_grad(jax.tree.map(jnp.asarray, params), b)
    for k in g_np:
        np.testing.assert_allclose(np.asarray(g_jax[k]), g_np[k], atol=1e-6)
