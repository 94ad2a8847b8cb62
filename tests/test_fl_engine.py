"""Integration tests: the federated engine + every baseline, small scale,
driven through the declarative TrainPlan API."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    FedAPConfig,
    FederatedTrainer,
    TrainPlan,
    baselines,
    fedap_plan,
    feddumap_config,
)
from repro.data import build_federated_data
from repro.data.synthetic import SyntheticSpec
from repro.models import SimpleCNN
from repro.utils import tree_size


@pytest.fixture(scope="module")
def small_world():
    # noise_scale 0.3 keeps the synthetic task learnable within a handful
    # of rounds so the convergence assertion below is signal, not luck
    spec = SyntheticSpec(num_classes=10, image_shape=(8, 8, 3),
                         train_size=2600, test_size=300, noise_scale=0.3)
    data = build_federated_data(num_clients=10, server_fraction=0.1,
                                device_pool=2000, spec=spec)
    model = SimpleCNN(num_classes=10, image_shape=(8, 8, 3), channels=(8, 16, 16),
                      fc_width=32)
    return data, model


def _run(data, model, cfg, rounds=3, hook=None):
    tr = FederatedTrainer(model, data, cfg)
    plan = (TrainPlan.with_callback(rounds, hook) if hook is not None
            else rounds)
    return tr.run(plan)


COMMON = dict(num_clients=10, clients_per_round=3, local_epochs=1,
              batch_size=10, lr=0.05)


class TestAlgorithms:
    def test_fedavg_runs_and_improves(self, small_world):
        data, model = small_world
        cfg = baselines.fedavg_config(
            **{**COMMON, "clients_per_round": 5, "local_epochs": 2})
        tr = FederatedTrainer(model, data, cfg)
        res = tr.run(12, eval_every=4)
        assert res.history["acc"][-1] > 0.2    # well above 10-class chance

    def test_feddu_tau_eff_decays(self, small_world):
        data, model = small_world
        res = _run(data, model, baselines.feddu_config(**COMMON), rounds=4)
        assert res.history["tau_eff"][0] > 0.0
        assert all(np.isfinite(res.history["tau_eff"]))

    # slow tier: per-mode numerical correctness is already locked by the
    # oracle differential suite (test_engine_diff.py); this is the full-CNN
    # integration pass over the same modes
    @pytest.mark.slow
    @pytest.mark.parametrize("maker", [
        baselines.server_momentum_config,
        baselines.device_momentum_config,
        baselines.fedda_config,
        feddumap_config,
    ])
    def test_momentum_variants_run(self, small_world, maker):
        data, model = small_world
        res = _run(data, model, maker(**COMMON), rounds=2)
        assert np.isfinite(res.history["loss"][-1])

    def test_data_sharing_transform(self, small_world):
        data, model = small_world
        shared = baselines.apply_data_sharing(data, np.random.default_rng(0))
        assert shared.client_x.shape[1] > data.client_x.shape[1]
        res = _run(shared, model, baselines.fedavg_config(**COMMON), rounds=2)
        assert np.isfinite(res.history["loss"][-1])

    def test_hybrid_fl_transform(self, small_world):
        data, model = small_world
        hyb = baselines.apply_hybrid_fl(data)
        assert hyb.client_x.shape[0] == data.client_x.shape[0] + 1
        cfg = baselines.fedavg_config(**{**COMMON, "num_clients": 11})
        res = _run(hyb, model, cfg, rounds=2)
        assert np.isfinite(res.history["loss"][-1])

    def test_distillation_hook(self, small_world):
        data, model = small_world
        hook = baselines.make_distillation_round_end(model, data, steps=2, batch=16)
        res = _run(data, model, baselines.fedavg_config(**COMMON), rounds=2,
                   hook=hook)
        assert np.isfinite(res.history["loss"][-1])


class TestPruningIntegration:
    @pytest.mark.slow  # full FedAP probe + re-materialize + re-jit cycle
    def test_fedap_shrink_event_and_training_continues(self, small_world):
        data, model = small_world
        # min_rate: the pure eigen-gap rule may prune nothing on this easy
        # synthetic task; the floor makes the shrink assertion strict
        apcfg = FedAPConfig(prune_round=2, probe_size=8, participants=2,
                            min_rate=0.4)
        cfg = feddumap_config(**COMMON, fedap=apcfg)
        tr = FederatedTrainer(model, data, cfg)
        init_params = model.init(jax.random.key(0))
        res = tr.run(fedap_plan(4, prune_round=2, mode="shrink"))
        assert res.artifacts["prune"]["kept"] is not None
        assert tree_size(res.params) < tree_size(init_params)
        assert np.isfinite(res.history["loss"][-1])

    @pytest.mark.slow  # full FedAP probe at static shapes, inside the scan
    def test_fedap_mask_event_stays_static(self, small_world):
        data, model = small_world
        apcfg = FedAPConfig(prune_round=2, probe_size=8, participants=2,
                            min_rate=0.4)
        cfg = feddumap_config(**COMMON, fedap=apcfg)
        tr = FederatedTrainer(model, data, cfg)
        init_params = model.init(jax.random.key(0))
        res = tr.run(fedap_plan(4, prune_round=2, mode="mask"))
        # static shapes: nothing shrank...
        assert (jax.tree.map(jnp.shape, res.params)
                == jax.tree.map(jnp.shape, init_params))
        # ...but a real fraction of coordinates is masked, and they stay
        # exactly zero through the post-prune rounds inside the scan
        assert "masks" in res.state
        masked_coords = 0
        for p, m in zip(jax.tree.leaves(res.params),
                        jax.tree.leaves(res.state["masks"])):
            dense = np.broadcast_to(m, p.shape)   # masks are factored
            np.testing.assert_array_equal(np.asarray(p)[dense == 0], 0.0)
            masked_coords += int(np.sum(dense == 0))
        assert masked_coords > 0
        assert np.isfinite(res.history["loss"][-1])

    @pytest.mark.slow  # mask semantics unit-tested in test_pruning.py
    def test_unstructured_hook_masks(self, small_world):
        data, model = small_world
        hook = baselines.make_unstructured_pruning_hook(rate=0.5, prune_round=2)
        res = _run(data, model, baselines.fedavg_config(**COMMON),
                   rounds=3, hook=hook)
        zeros = sum(float(jnp.mean(p == 0)) for p in jax.tree.leaves(res.params))
        assert zeros > 0.1                      # a real fraction masked
        assert np.isfinite(res.history["loss"][-1])

    def test_hrank_hook_structured(self, small_world):
        data, model = small_world
        hook = baselines.make_hrank_pruning_hook(model, data, rate=0.4,
                                                 prune_round=2, probe=8)
        res = _run(data, model, baselines.fedavg_config(**COMMON),
                   rounds=3, hook=hook)
        init_params = model.init(jax.random.key(0))
        assert tree_size(res.params) < tree_size(init_params)
        assert np.isfinite(res.history["loss"][-1])
