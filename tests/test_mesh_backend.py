"""MeshBackend parity + sharded-state round-trip tests.

The client-sharded execution backend must be NUMERICALLY the local scan
backend: per-round parity local == mesh == f64 oracle (<= 1e-5), a full
TrainPlan (Scan/Eval/Prune(mode="mask")/Snapshot/Callback) with the FedAP
decision computed POD-SIDE and applied mid-run without re-lowering the
chunk program, and `backend.masked_round_state` round-tripping a
genuinely sharded SPMD round state with shardings and the compiled program
intact.

Multi-device intent: run under
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the CI
``mesh-backend`` job does) so the mesh is a real 8-way client axis.  The
tests adapt to the available device count, so under plain tier-1 (one
device) they still execute the mesh code path on a 1-way mesh.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

from repro.core import (
    FedAPConfig,
    FederatedTrainer,
    Callback,
    Eval,
    Prune,
    Scan,
    Snapshot,
    TrainPlan,
    engine,
    ref_engine,
    feddumap_config,
)
from repro.analysis.compile_budget import expected_programs
from repro.core.backend import sim_sample_kw
from repro.core.fedap import fedap_decision, fedap_decision_sharded
from repro.core.ref_engine import SoftmaxRegression
from repro.core.rounds import engine_config
from repro.data import build_federated_data
from repro.data.pipeline import FederatedData
from repro.data.synthetic import SyntheticSpec
from repro.launch.mesh import make_host_mesh
from repro.models import SimpleCNN
from repro.models.cnn import softmax_xent_acc


N_DEV = len(jax.devices())


def host_mesh():
    """The mesh the backend would build: every local device on the client
    ('data') axis — 8-way under the CI mesh-backend job's XLA_FLAGS."""
    return make_host_mesh(model=1)


# ---------------------------------------------------------------------------
# Per-round parity: mesh == local == f64 oracle through the FULL path
# (device-side sampling included), on the closed-form softmax toy
# ---------------------------------------------------------------------------

DIM, CLASSES = 6, 4
N_CLIENTS, N_K = 8, 20
# 16 server rows / server_batch 8: the per-step server batch dim divides
# the CI job's 8-way client axis, so the FedDU server scan GENUINELY
# shards in these parity tests; 12 test rows do NOT divide 8, so the
# sharded eval's pad-and-correct path is exercised against the oracle too
N_SERVER, N_TEST = 16, 12
ROUNDS = 4


class OracleSoftmaxModel:
    """Trainer-interface adapter around the oracle's SoftmaxRegression:
    jnp loss for the engine, closed-form NumPy grads for ref_engine."""

    def __init__(self):
        self._np = SoftmaxRegression(dim=DIM, num_classes=CLASSES)

    def init(self, rng):
        return jax.tree.map(jnp.asarray, self._np.init(seed=7))

    def loss_and_acc(self, params, x, y):
        return softmax_xent_acc(x @ params["w"] + params["b"], y)

    def np_init(self):
        return self._np.init(seed=7)

    def np_grad(self, params, batch):
        return self._np.np_grad(params, batch)

    def np_loss_and_acc(self, params, batch):
        return self._np.np_loss_and_acc(params, batch)


@pytest.fixture(scope="module")
def softmax_world():
    rng = np.random.default_rng(11)
    x = lambda *lead: rng.standard_normal(lead + (DIM,)).astype(np.float32)
    y = lambda *lead: rng.integers(0, CLASSES, lead).astype(np.int64)
    dists = np.full((N_CLIENTS, CLASSES), 1.0 / CLASSES, np.float32)
    data = FederatedData(
        client_x=x(N_CLIENTS, N_K), client_y=y(N_CLIENTS, N_K),
        sizes=np.full(N_CLIENTS, float(N_K), np.float32),
        client_dists=dists,
        server_x=x(N_SERVER), server_y=y(N_SERVER),
        server_dist=np.full((CLASSES,), 1.0 / CLASSES, np.float32),
        test_x=x(N_TEST), test_y=y(N_TEST))
    cfg = feddumap_config(
        num_clients=N_CLIENTS, clients_per_round=N_CLIENTS, local_epochs=1,
        batch_size=5, lr=0.08, lr_decay=0.97, server_batch_size=8)
    return data, OracleSoftmaxModel(), cfg


def per_round_plan(rounds):
    return TrainPlan([e for _ in range(rounds) for e in (Scan(1), Eval())])


def oracle_run(data, model, cfg, rounds):
    """The f64 oracle driven by the SAME device-side sampling key chain the
    backends consume (one split per round)."""
    eng = engine_config(cfg)
    data_dev = data.device_arrays()
    kw = sim_sample_kw(cfg, data)
    key = jax.random.key(cfg.seed)
    state = ref_engine.ref_init_state(model.np_init(), eng,
                                      num_clients=data.client_x.shape[0])
    hist = {"loss": [], "acc": [], "tau_eff": []}
    for _ in range(rounds):
        key, sub = jax.random.split(key)
        batch = jax.tree.map(np.asarray,
                             engine.sample_round_batches(sub, data_dev, **kw))
        state, metrics = ref_engine.ref_round(
            eng, model.np_grad, model.np_loss_and_acc, state, batch)
        loss, acc = model.np_loss_and_acc(state["params"],
                                          (data.test_x, data.test_y))
        hist["loss"].append(loss)
        hist["acc"].append(acc)
        hist["tau_eff"].append(metrics["tau_eff"])
    return state, hist


class TestMeshOracleParity:
    def test_mesh_equals_local_equals_oracle_per_round(self, softmax_world):
        data, model, cfg = softmax_world
        plan = per_round_plan(ROUNDS)
        res_l = FederatedTrainer(model, data, cfg).run(plan)
        res_m = FederatedTrainer(model, data, cfg, backend="mesh").run(plan)
        ref_state, ref_hist = oracle_run(data, model, cfg, ROUNDS)

        for res, tag in ((res_l, "local"), (res_m, "mesh")):
            np.testing.assert_allclose(res.history["loss"], ref_hist["loss"],
                                       atol=1e-5, err_msg=f"{tag} vs oracle")
            np.testing.assert_allclose(res.history["acc"], ref_hist["acc"],
                                       atol=1e-5, err_msg=f"{tag} vs oracle")
            np.testing.assert_allclose(res.history["tau_eff"],
                                       ref_hist["tau_eff"], atol=1e-5)
            for leaf, ref_leaf in zip(jax.tree.leaves(res.params),
                                      jax.tree.leaves(ref_state["params"])):
                np.testing.assert_allclose(np.asarray(leaf), ref_leaf,
                                           atol=1e-5, err_msg=tag)
        # mesh vs local directly (tighter than through the oracle)
        np.testing.assert_allclose(res_m.history["loss"],
                                   res_l.history["loss"], atol=1e-6)
        for a, b in zip(jax.tree.leaves(res_m.params),
                        jax.tree.leaves(res_l.params)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-6)

    def test_momentum_state_tracks_oracle(self, softmax_world):
        data, model, cfg = softmax_world
        res_m = FederatedTrainer(model, data, cfg,
                                 backend="mesh").run(per_round_plan(ROUNDS))
        ref_state, _ = oracle_run(data, model, cfg, ROUNDS)
        for leaf, ref_leaf in zip(jax.tree.leaves(res_m.state["server_m"]),
                                  jax.tree.leaves(ref_state["server_m"])):
            np.testing.assert_allclose(np.asarray(leaf), ref_leaf, atol=1e-5)

    @pytest.mark.parametrize("local_m,server_m",
                             [("none", False), ("communicated", True)])
    def test_all_momentum_modes_sharded_server_scan(self, softmax_world,
                                                    local_m, server_m):
        """mesh == local == f64 oracle per round with the batch-sharded
        FedDU server scan (and sharded eval) enabled, for the momentum
        modes the module fixture (restart + server momentum) does not
        cover.  tau_eff rides on the first-step server_acc gate, so its
        parity transitively checks the sharded first server forward."""
        data, model, cfg = softmax_world
        cfg = dataclasses.replace(cfg, local_momentum=local_m,
                                  server_momentum=server_m)
        rounds = 3
        plan = per_round_plan(rounds)
        res_l = FederatedTrainer(model, data, cfg).run(plan)
        res_m = FederatedTrainer(model, data, cfg, backend="mesh").run(plan)
        ref_state, ref_hist = oracle_run(data, model, cfg, rounds)
        for res, tag in ((res_l, "local"), (res_m, "mesh")):
            np.testing.assert_allclose(res.history["loss"], ref_hist["loss"],
                                       atol=1e-5, err_msg=tag)
            np.testing.assert_allclose(res.history["tau_eff"],
                                       ref_hist["tau_eff"], atol=1e-5,
                                       err_msg=tag)
        for a, b in zip(jax.tree.leaves(res_m.params),
                        jax.tree.leaves(ref_state["params"])):
            np.testing.assert_allclose(np.asarray(a), b, atol=1e-5)
        if local_m == "communicated":
            for a, b in zip(jax.tree.leaves(res_m.state["global_m"]),
                            jax.tree.leaves(ref_state["global_m"])):
                np.testing.assert_allclose(np.asarray(a), b, atol=1e-5)

    @pytest.mark.parametrize("algo,overrides", [
        ("fedprox", dict(algorithm="fedprox",
                         fedprox=engine.FedProxConfig(mu=0.05))),
        ("feddyn", dict(algorithm="feddyn",
                        feddyn=engine.FedDynConfig(alpha=0.05))),
    ])
    def test_client_state_algorithms_mesh_equals_oracle(self, softmax_world,
                                                        algo, overrides):
        """FedProx/FedDyn through the FULL trainer path: the client_state
        slot rides the mesh carry (per-client FedDyn corrections sharded
        over the 8-way client axis in CI) and both backends must track the
        f64 oracle per round."""
        data, model, cfg = softmax_world
        cfg = dataclasses.replace(cfg, **overrides)
        rounds = 3
        plan = per_round_plan(rounds)
        res_l = FederatedTrainer(model, data, cfg).run(plan)
        res_m = FederatedTrainer(model, data, cfg, backend="mesh").run(plan)
        ref_state, ref_hist = oracle_run(data, model, cfg, rounds)
        for res, tag in ((res_l, "local"), (res_m, "mesh")):
            np.testing.assert_allclose(res.history["loss"], ref_hist["loss"],
                                       atol=1e-5, err_msg=f"{algo} {tag}")
            for a, b in zip(jax.tree.leaves(res.params),
                            jax.tree.leaves(ref_state["params"])):
                np.testing.assert_allclose(np.asarray(a), b, atol=1e-5,
                                           err_msg=f"{algo} {tag}")
        if algo == "feddyn":
            # the [N, ...] correction state itself must track the oracle —
            # on the mesh it lived sharded over the client axis all run
            for a, b in zip(jax.tree.leaves(res_m.state["client_state"]),
                            jax.tree.leaves(ref_state["client_state"])):
                np.testing.assert_allclose(np.asarray(a), b, atol=1e-5,
                                           err_msg="feddyn client_state")

    def test_straggler_dropout_mesh_equals_local_equals_oracle(
            self, softmax_world):
        """dropout_rate > 0: dropped clients contribute ZERO aggregation
        weight (delta form) on every backend, and the shared key chain
        keeps local == mesh == oracle sampling identical."""
        data, model, cfg = softmax_world
        cfg = dataclasses.replace(cfg, dropout_rate=0.4)
        rounds = 3
        plan = per_round_plan(rounds)
        res_l = FederatedTrainer(model, data, cfg).run(plan)
        res_m = FederatedTrainer(model, data, cfg, backend="mesh").run(plan)
        ref_state, ref_hist = oracle_run(data, model, cfg, rounds)
        for res, tag in ((res_l, "local"), (res_m, "mesh")):
            np.testing.assert_allclose(res.history["loss"], ref_hist["loss"],
                                       atol=1e-5, err_msg=tag)
            for a, b in zip(jax.tree.leaves(res.params),
                            jax.tree.leaves(ref_state["params"])):
                np.testing.assert_allclose(np.asarray(a), b, atol=1e-5,
                                           err_msg=tag)
        for a, b in zip(jax.tree.leaves(res_m.params),
                        jax.tree.leaves(res_l.params)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-6)


# ---------------------------------------------------------------------------
# Full TrainPlan on the mesh: Scan/Eval/Prune(mask)/Snapshot/Callback with a
# pod-side FedAP decision applied mid-run, no re-lower
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def cnn_world():
    spec = SyntheticSpec(num_classes=10, image_shape=(8, 8, 3),
                         train_size=1700, test_size=100, noise_scale=0.5)
    data = build_federated_data(num_clients=8, server_fraction=0.1,
                                device_pool=640, spec=spec)
    model = SimpleCNN(num_classes=10, image_shape=(8, 8, 3),
                      channels=(4, 8, 8), fc_width=16)
    # participants=7 (+1 server) = 8 probe sets — divisible over the CI
    # job's 8-way client axis, so the pod-side decision genuinely shards
    apcfg = FedAPConfig(prune_round=2, probe_size=8, participants=7,
                        min_rate=0.5)
    cfg = feddumap_config(num_clients=8, clients_per_round=8, local_epochs=1,
                          batch_size=10, lr=0.05, fedap=apcfg)
    return data, model, cfg


FULL_PLAN = TrainPlan(Eval(), Scan(2), Eval(), Prune(mode="mask"),
                      Snapshot(), Scan(2), Eval())


class TestMeshFullPlan:
    @pytest.fixture(scope="class")
    def runs(self, cnn_world):
        data, model, cfg = cnn_world
        tr_m = FederatedTrainer(model, data, cfg, backend="mesh")
        res_m = tr_m.run(FULL_PLAN)
        res_l = FederatedTrainer(model, data, cfg).run(FULL_PLAN)
        return tr_m, res_m, res_l

    def test_per_round_parity_and_pod_side_decision(self, runs):
        _, res_m, res_l = runs
        np.testing.assert_allclose(res_m.history["loss"],
                                   res_l.history["loss"], atol=1e-5)
        np.testing.assert_allclose(res_m.history["acc"],
                                   res_l.history["acc"], atol=1e-5)
        np.testing.assert_allclose(res_m.history["tau_eff"],
                                   res_l.history["tau_eff"], atol=1e-5)
        # the sharded (pod-side) decision picked the same filters as the
        # host decision on the local path
        kept_m = res_m.artifacts["prune"]["kept"]
        kept_l = res_l.artifacts["prune"]["kept"]
        assert {k: v.tolist() for k, v in kept_m.items()} \
            == {k: v.tolist() for k, v in kept_l.items()}
        assert sum(len(v) for v in kept_m.values()) < 4 + 8 + 8  # real prune
        for a, b in zip(jax.tree.leaves(res_m.params),
                        jax.tree.leaves(res_l.params)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-5)
        # masked coordinates are exactly zero through the post-prune rounds
        for p, m in zip(jax.tree.leaves(res_m.params),
                        jax.tree.leaves(res_m.state["masks"])):
            np.testing.assert_array_equal(
                np.asarray(p)[np.broadcast_to(m, p.shape) == 0], 0.0)

    def test_prune_applied_without_relowering(self, runs):
        """ONE chunk trace covers the whole plan: the mid-run mask
        injection (backend.masked_round_state) must not re-lower the mesh
        program."""
        tr_m, res_m, _ = runs
        be = tr_m.backend(use_masks=True)
        # budgeted in repro/analysis/compile_budget.json: the mask-mode
        # prune adds ZERO mesh programs
        assert be.chunk._cache_size() == expected_programs("mesh/prune_mask")
        assert expected_programs("mesh/prune_mask") \
            == len(FULL_PLAN.chunk_lengths())

    def test_state_and_data_shardings(self, runs):
        tr_m, res_m, _ = runs
        be = tr_m.backend(use_masks=True)
        mesh = be.mesh
        # global state replicated over the mesh
        for leaf in jax.tree.leaves(res_m.state["params"]):
            assert leaf.sharding == NamedSharding(mesh, P())
        # per-client data sharded over the client axis (divisible: 8 clients)
        d = be.device_data()
        if N_CLIENTS % mesh.shape["data"] == 0 and mesh.shape["data"] > 1:
            assert d["client_x"].sharding.spec == P("data")
        # server POOL replicated (per-step server batches are sharded
        # in-scan instead); TEST split padded to the axis size and sharded
        # on its batch dim — eval is no longer a replicated full-test pass
        assert d["server_x"].sharding == NamedSharding(mesh, P())
        size = mesh.shape["data"]
        n_test = be.data.test_x.shape[0]
        assert d["test_x"].shape[0] == n_test + (-n_test % size)
        if size > 1:
            assert d["test_x"].sharding.spec == P("data")
            assert d["test_y"].sharding.spec == P("data")

    def test_snapshot_and_callback_round_indices(self, cnn_world):
        data, model, cfg = cnn_world
        seen = []
        cb = lambda trainer, t, params: seen.append(t)
        plan = TrainPlan(Scan(2), Callback(cb), Scan(1), Snapshot(),
                         Callback(cb), Eval())
        res = FederatedTrainer(model, data, cfg, backend="mesh").run(plan)
        assert seen == [2, 3]                     # true completed rounds
        assert res.artifacts["snapshot"]["round"] == 3
        assert res.history["round"] == [3]


class TestShardedDecisionMatchesHost:
    def test_sharded_rates_close_to_host(self, cnn_world):
        """Step 1 pod-side vs host-side: the eigen-gap rate is a DISCRETE
        index search, so float noise between the sequential eager path and
        the vmapped sharded program may flip single indices — the aggregate
        rate must agree to within one flipped index per participant
        (1/probe_size after the Formula-15 weighting)."""
        data, model, cfg = cnn_world
        params = model.init(jax.random.key(3))
        kw = dict(init_params=model.init(jax.random.key(0)))
        host = fedap_decision(model, data, cfg.fedap, params,
                              rng=np.random.default_rng(5), **kw)
        pod = fedap_decision_sharded(model, data, cfg.fedap, params,
                                     rng=np.random.default_rng(5),
                                     mesh=host_mesh(), client_axes=("data",),
                                     **kw)
        assert abs(host.p_star - pod.p_star) <= 1.0 / cfg.fedap.probe_size

    def test_sharded_equals_host_at_compression_floor(self, cnn_world):
        """With the compression-budget floor binding (the production FedAP
        configuration), steps 2-4 see the identical clipped p*, so the two
        entry points must pick EXACTLY the same filters."""
        data, model, cfg = cnn_world
        apcfg = dataclasses.replace(cfg.fedap, min_rate=0.7)
        params = model.init(jax.random.key(3))
        kw = dict(init_params=model.init(jax.random.key(0)))
        host = fedap_decision(model, data, apcfg, params,
                              rng=np.random.default_rng(5), **kw)
        pod = fedap_decision_sharded(model, data, apcfg, params,
                                     rng=np.random.default_rng(5),
                                     mesh=host_mesh(), client_axes=("data",),
                                     **kw)
        assert host.p_star == pytest.approx(pod.p_star, abs=1e-6)
        assert host.layer_rates == pytest.approx(pod.layer_rates, abs=1e-6)
        assert {k: v.tolist() for k, v in host.kept.items()} \
            == {k: v.tolist() for k, v in pod.kept.items()}

    def test_ragged_probe_equals_host(self, cnn_world):
        """Ragged probe sets (server pool smaller than the requested probe,
        clients larger): the sharded path pads the stacked probe to
        rectangular and masks the padded rows out of the Fisher/Lipschitz
        statistics, so each participant's rate is computed over exactly
        the samples the host path probes.  With the compression floor
        binding the two entry points pick IDENTICAL filters (the same
        contract the rectangular floor test locks)."""
        data, model, cfg = cnn_world
        n0, n_k = data.server_x.shape[0], data.client_x.shape[1]
        probe = n_k - 4          # > n0 (=64) but <= n_k (=80): truly ragged
        assert n0 < probe <= n_k
        apcfg = dataclasses.replace(cfg.fedap, probe_size=probe,
                                    min_rate=0.7)
        params = model.init(jax.random.key(3))
        kw = dict(init_params=model.init(jax.random.key(0)))
        host = fedap_decision(model, data, apcfg, params,
                              rng=np.random.default_rng(5), **kw)
        pod = fedap_decision_sharded(model, data, apcfg, params,
                                     rng=np.random.default_rng(5),
                                     mesh=host_mesh(), client_axes=("data",),
                                     **kw)
        assert host.p_star == pytest.approx(pod.p_star, abs=1e-6)
        assert host.layer_rates == pytest.approx(pod.layer_rates, abs=1e-6)
        assert {k: v.tolist() for k, v in host.kept.items()} \
            == {k: v.tolist() for k, v in pod.kept.items()}

    def test_ragged_probe_rates_close_to_host(self, cnn_world):
        """Off the floor, the padded/masked step-1 statistics must stay
        within the discrete eigen-index tolerance of the host path (same
        contract as the rectangular closeness test)."""
        data, model, cfg = cnn_world
        probe = data.client_x.shape[1] - 4
        apcfg = dataclasses.replace(cfg.fedap, probe_size=probe)
        params = model.init(jax.random.key(3))
        kw = dict(init_params=model.init(jax.random.key(0)))
        host = fedap_decision(model, data, apcfg, params,
                              rng=np.random.default_rng(5), **kw)
        pod = fedap_decision_sharded(model, data, apcfg, params,
                                     rng=np.random.default_rng(5),
                                     mesh=host_mesh(), client_axes=("data",),
                                     **kw)
        # one flipped eigen index per participant at most, over the
        # SMALLEST actual probe (the server's n0 rows)
        assert abs(host.p_star - pod.p_star) <= 1.0 / data.server_x.shape[0]


# ---------------------------------------------------------------------------
# Batch-sharded evaluation: sharded eval == replicated eval on the same
# params (pad-and-correct path included), built without lowering the chunk
# ---------------------------------------------------------------------------

class TestShardedEval:
    def test_sharded_eval_equals_replicated(self, cnn_world):
        """The sharded eval program — test batch padded (100 -> 104 on the
        8-way axis) and sharded over the mesh — must score the SAME params
        like the replicated full-test pass, the padded rows corrected out
        exactly (up to f32 association)."""
        from repro.core.backend import MeshBackend

        data, model, cfg = cnn_world
        mesh = host_mesh()
        be_s = MeshBackend(model, data, cfg, mesh=mesh)
        be_r = MeshBackend(model, data, cfg, mesh=mesh, shard_eval=False,
                           shard_server=False)
        state = be_s.init_state(model.init(jax.random.key(1)))
        loss_s, acc_s = be_s.evaluate(state)
        loss_r, acc_r = be_r.evaluate(state)
        np.testing.assert_allclose(float(loss_s), float(loss_r), atol=1e-6)
        np.testing.assert_allclose(float(acc_s), float(acc_r), atol=1e-6)

    def test_evaluate_does_not_lower_chunk(self, cnn_world):
        """`evaluate` on a FRESH backend must not pay the full chunk
        lowering — eval-program construction is factored out of
        `_programs` (the `self._programs()`-for-side-effect satellite)."""
        from repro.core.backend import MeshBackend

        data, model, cfg = cnn_world
        be = MeshBackend(model, data, cfg, mesh=host_mesh())
        state = be.init_state(model.init(jax.random.key(1)))
        loss, acc = be.evaluate(state)
        assert np.isfinite(float(loss)) and np.isfinite(float(acc))
        assert be._chunk is None, \
            "evaluate() lowered the chunk program as a side effect"


# ---------------------------------------------------------------------------
# Shard-local shrink compaction: no host round-trip, values == host shrink
# (params AND momentum), outputs mesh-committed NamedShardings
# ---------------------------------------------------------------------------

class TestShardedShrink:
    @pytest.fixture()
    def masked_state(self, cnn_world):
        """A mesh round state two rounds in with a mask decision applied —
        the state a reuse-shrink compacts."""
        data, model, cfg = cnn_world
        tr = FederatedTrainer(model, data, cfg, backend="mesh")
        res = tr.run(TrainPlan(Scan(2), Prune(mode="mask")))
        be = tr.backend(use_masks=True)
        return be, res.state, res.artifacts["prune"]["kept"]

    def test_sharded_shrink_matches_host_and_stays_on_mesh(self,
                                                           masked_state):
        from repro.core import backend as backend_mod

        be, state, kept = masked_state
        # the host (base-class) path on the same state — the "before"
        host_state, host_extra = backend_mod._EngineBackend.apply_prune(
            be, state, "shrink", kept, compact_existing=True)

        # the sharded path may not re-place any STATE array via
        # jax.device_put (the compaction is one jitted program whose
        # out_shardings pin the mesh placement); the only device_put
        # traffic allowed is the trace-time conversion of the tiny static
        # kept-INDEX constants
        calls = []
        orig = jax.device_put
        jax.device_put = lambda x, *a, **k: calls.append(x) or orig(x, *a, **k)
        try:
            new_state, extra = be.apply_prune(state, "shrink", kept,
                                              compact_existing=True)
        finally:
            jax.device_put = orig
        for x in calls:
            assert np.issubdtype(np.asarray(x).dtype, np.integer) \
                and np.asarray(x).ndim <= 1, \
                f"sharded shrink re-placed a state array via device_put: " \
                f"{np.asarray(x).dtype} {np.asarray(x).shape}"

        # params AND momentum leaf-equal to the host shrink (pure gathers
        # of identical inputs -> exact), round preserved
        for (p1, l1), (p2, l2) in zip(
                jax.tree_util.tree_leaves_with_path(host_state),
                jax.tree_util.tree_leaves_with_path(new_state)):
            assert p1 == p2
            assert l1.shape == l2.shape, p1
            np.testing.assert_array_equal(np.asarray(l1), np.asarray(l2),
                                          err_msg=str(p1))
        # every leaf is a mesh-committed NamedSharding output of the jitted
        # compaction — the acceptance-criterion placement assertion
        for path, leaf in jax.tree_util.tree_leaves_with_path(new_state):
            assert isinstance(leaf.sharding, NamedSharding), path
            assert leaf.sharding.mesh == be.mesh, path
        # artifact contract unchanged
        assert set(extra) == set(host_extra) == {"params_before"}

    def test_mask_then_shrink_plan_parity(self, cnn_world):
        """Full executor path: Scan/Prune(mask)/Scan/Prune(shrink,
        reuse)/Scan/Eval on the mesh == local, params and compacted
        momentum within 1e-5; one chunk program per shape (the shrink's
        re-trace is the shape change, nothing else re-lowers)."""
        data, model, cfg = cnn_world
        plan = TrainPlan(Scan(2), Prune(mode="mask"), Scan(2),
                         Prune(mode="shrink", reuse="prune", name="shrink"),
                         Scan(2), Eval())
        tr_m = FederatedTrainer(model, data, cfg, backend="mesh")
        res_m = tr_m.run(plan)
        res_l = FederatedTrainer(model, data, cfg).run(plan)
        np.testing.assert_allclose(res_m.history["loss"],
                                   res_l.history["loss"], atol=1e-5)
        np.testing.assert_allclose(res_m.history["acc"],
                                   res_l.history["acc"], atol=1e-5)
        for a, b in zip(jax.tree.leaves(res_m.params),
                        jax.tree.leaves(res_l.params)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-5)
        for a, b in zip(jax.tree.leaves(res_m.state["server_m"]),
                        jax.tree.leaves(res_l.state["server_m"])):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-5)
        be = tr_m.backend(use_masks=True)
        # pre-shrink + post-shrink, budgeted in compile_budget.json
        assert be.chunk._cache_size() \
            == expected_programs("mesh/mask_then_shrink")


# ---------------------------------------------------------------------------
# Mask injection into a GENUINELY sharded SPMD round state: shardings and
# the compiled program survive it
# ---------------------------------------------------------------------------

class ShardedDictModel:
    """Toy whose hidden dim shards over the 'model' axis."""

    D_IN, D_H, D_OUT = 6, 2 * max(1, N_DEV), 4

    def init(self, rng):
        k1, k2 = jax.random.split(rng)
        return {"w1": jax.random.normal(k1, (self.D_IN, self.D_H)) * 0.3,
                "w2": jax.random.normal(k2, (self.D_H, self.D_OUT)) * 0.3}

    def loss_and_acc(self, params, batch):
        h = jax.nn.relu(batch["x"] @ params["w1"])
        return softmax_xent_acc(h @ params["w2"], batch["labels"])

    def prune_spec(self, params):
        """FedAP's seam: w1's hidden units, coupled to w2's rows — it sizes
        the round state's factored mask slot (w1 [1, D_H], w2 [D_H, 1])."""
        from repro.core.pruning import (
            CoupledParam,
            PrunableLayer,
            PruneSpec,
        )

        return PruneSpec(layers=(PrunableLayer(
            "hidden", ("w1",), 1, (CoupledParam(("w2",), 0),)),))


class TestWithMasksShardedRoundTrip:
    def test_sharded_state_roundtrip_no_relower(self):
        from repro.core.backend import masked_round_state, param_masks_for
        from repro.core.momentum import FedDUMConfig
        from repro.sharding.fl_specs import fl_state_specs
        from repro.sharding.specs import MeshPlan

        # every device on the MODEL axis: the w1/w2 hidden dim genuinely
        # shards (8-way under the CI job), clients are explicit batch rows
        mesh = jax.make_mesh((1, N_DEV), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        plan = MeshPlan(mesh=mesh, multi_pod=False, client_axes=(),
                        fsdp_axes=(), tp_axes=("model",), batch_axes=("data",),
                        num_clients=1)
        model = ShardedDictModel()
        eng = engine.EngineConfig(
            lr=0.05, use_server_update=True, local_momentum="restart",
            server_momentum=True, use_masks=True,
            feddum=FedDUMConfig(beta_server=0.9, beta_local=0.9,
                                eta_server=1.0))
        grad_fn, la_fn = engine.build_model_fns(
            eng, lambda p, b, fm: model.loss_and_acc(p, b)[0],
            lambda p, b, fm: model.loss_and_acc(p, b))
        params = model.init(jax.random.key(0))
        state = engine.init_round_state(
            params, eng, num_clients=3,
            masks=param_masks_for(model, params, {}))
        axes = {"w1": ("embed", "mlp"), "w2": ("mlp", "vocab_small")}
        specs = fl_state_specs(state, axes, plan)
        shardings = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                                 is_leaf=lambda x: isinstance(x, P))
        state = jax.device_put(state, shardings)
        # the hidden dim really shards when more than one device is present;
        # the factored masks hold the never-pruned axes at size 1, and a
        # size-1 axis is never sharded
        assert state["masks"]["w1"].shape == (1, model.D_H)
        assert state["masks"]["w2"].shape == (model.D_H, 1)
        if N_DEV > 1:
            assert state["params"]["w1"].sharding.spec == P(None, "model")
            assert state["masks"]["w1"].sharding.spec == P(None, "model")
            assert state["masks"]["w2"].sharding.spec == P("model", None)

        rng = np.random.default_rng(0)
        batch = {
            "client": {"x": jnp.asarray(rng.standard_normal(
                (3, 2, 4, model.D_IN)), jnp.float32),
                "labels": jnp.asarray(rng.integers(0, model.D_OUT, (3, 2, 4)))},
            "server": {"x": jnp.asarray(rng.standard_normal(
                (2, 4, model.D_IN)), jnp.float32),
                "labels": jnp.asarray(rng.integers(0, model.D_OUT, (2, 4)))},
            "sizes": jnp.asarray([4.0, 4.0, 4.0]),
            "d_round": jnp.float32(0.3), "d_server": jnp.float32(0.02),
            "n0": jnp.float32(100.0),
        }
        step = jax.jit(lambda st, b: engine.round_core(eng, grad_fn, la_fn,
                                                       st, b))
        compiled = step.lower(state, batch).compile()
        state1, _ = compiled(state, batch)

        # inject a decision mid-run: mask half of w1's output filters (and
        # w2's matching input rows — the coupled closure), given at the
        # parameters' full shapes as an older prune artifact holds them:
        # masked_round_state reduces them to the slot's factored shapes
        m = np.ones((model.D_H,), np.float32)
        m[model.D_H // 2:] = 0.0
        masks = {"w1": jnp.asarray(np.broadcast_to(m, (model.D_IN,
                                                       model.D_H)).copy()),
                 "w2": jnp.asarray(np.broadcast_to(m[:, None],
                                                   (model.D_H,
                                                    model.D_OUT)).copy())}
        state2 = masked_round_state(state1, masks)

        # shardings unchanged leaf-for-leaf
        flat1 = jax.tree_util.tree_leaves_with_path(state1)
        flat2 = jax.tree_util.tree_leaves_with_path(state2)
        for (p1, l1), (p2, l2) in zip(flat1, flat2):
            assert p1 == p2
            assert l1.sharding == l2.sharding, p1
            assert l1.shape == l2.shape
        # momentum restarted, params masked — the value contract
        for leaf in jax.tree.leaves(state2["server_m"]):
            np.testing.assert_array_equal(np.asarray(leaf), 0.0)
        np.testing.assert_array_equal(
            np.asarray(state2["params"]["w1"])[:, model.D_H // 2:], 0.0)

        # the PRE-PRUNE compiled executable keeps running on the new state:
        # no re-lower, and the masked coordinates stay zero
        state3, mets = compiled(state2, batch)
        assert step._cache_size() <= 1
        np.testing.assert_array_equal(
            np.asarray(state3["params"]["w1"])[:, model.D_H // 2:], 0.0)
        assert np.isfinite(float(mets["tau_eff"]))
