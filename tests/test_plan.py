"""The declarative TrainPlan API: compilation, execution, and the FedAP
mask/shrink equivalence that makes in-scan pruning trustworthy.

The heavyweight lock is ``test_masked_prune_matches_shrink``: a FedDUMAP
run with ``Prune(mode="mask")`` (every round inside compiled scan chunks,
no re-jit) must train EXACTLY like ``Prune(mode="shrink")`` (the legacy
re-materializing path) on a normalization-free model — compacting the
masked params at the kept indices reproduces the shrunk params.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    Callback,
    Eval,
    FedAPConfig,
    FederatedTrainer,
    FLConfig,
    Prune,
    Scan,
    Snapshot,
    TrainPlan,
    baselines,
    engine,
    fedap_plan,
    feddumap_config,
    pruning,
)
from repro.analysis.compile_budget import expected_programs
from repro.core.fedap import fedap_decision
from repro.data import build_federated_data
from repro.data.synthetic import SyntheticSpec
from repro.models import SimpleCNN


# ---------------------------------------------------------------------------
# Plan construction / compilation (host-only, no jit)
# ---------------------------------------------------------------------------

class TestPlanCompilation:
    def test_consecutive_scans_merge(self):
        plan = TrainPlan(Scan(3), Scan(2), Eval(), Scan(1), Scan(1), Scan(1))
        assert plan.compiled() == (Scan(5), Eval(), Scan(3))
        assert plan.total_rounds == 8
        assert plan.chunk_lengths() == (3, 5)

    def test_nested_iterables_flatten(self):
        plan = TrainPlan([Scan(2), Eval()], Scan(2))
        assert plan.events == (Scan(2), Eval(), Scan(2))

    def test_event_validation(self):
        with pytest.raises(ValueError):
            Scan(0)
        with pytest.raises(ValueError):
            Prune(mode="sparsify")
        with pytest.raises(TypeError):
            TrainPlan(Scan(1), "eval")

    def test_uses_masks(self):
        assert TrainPlan(Scan(1), Prune(mode="mask")).uses_masks
        assert not TrainPlan(Scan(1), Prune(mode="shrink")).uses_masks

    def test_standard_builder_matches_legacy_eval_cadence(self):
        plan = TrainPlan.standard(7, eval_every=3)
        assert plan.events == (Scan(3), Eval(), Scan(3), Eval(),
                               Scan(1), Eval())

    def test_fedap_plan_schedules_prune_after_round(self):
        plan = fedap_plan(6, prune_round=2, mode="mask", eval_every=3)
        assert plan.events == (Scan(2), Prune(mode="mask"), Scan(1), Eval(),
                               Scan(3), Eval())
        with pytest.raises(ValueError):
            fedap_plan(6, prune_round=7)

    def test_fedap_plan_shrink_round_schedules_reuse_shrink(self):
        """Mask-now-shrink-later: the prune round applies masks (inside
        the compiled scan) and ``shrink_round`` compacts to the SAME
        decision via Prune(mode="shrink", reuse="prune")."""
        plan = fedap_plan(6, prune_round=2, shrink_round=4, eval_every=2)
        assert plan.events == (
            Scan(2), Eval(), Prune(mode="mask"),
            Scan(2), Eval(), Prune(mode="shrink", reuse="prune",
                                   name="shrink"),
            Scan(2), Eval())
        assert plan.uses_masks
        with pytest.raises(ValueError, match="shrink_round"):
            fedap_plan(6, prune_round=2, shrink_round=2)
        with pytest.raises(ValueError, match="shrink_round"):
            fedap_plan(6, prune_round=2, shrink_round=7)
        with pytest.raises(ValueError, match="mask"):
            fedap_plan(6, prune_round=2, shrink_round=4, mode="shrink")

    def test_prune_reuse_validation(self):
        with pytest.raises(ValueError, match="reuse"):
            Prune(mode="mask", reuse="prune")
        assert Prune(mode="shrink", reuse="prune").reuse == "prune"

    def test_with_callback_interleaves(self):
        fn = lambda tr, t, p: None
        plan = TrainPlan.with_callback(4, fn, every=2, eval_every=4)
        assert plan.events == (Scan(2), Callback(fn), Scan(2), Eval(),
                               Callback(fn))

    def test_eval_every_zero_means_no_evals(self):
        fn = lambda tr, t, p: None
        plan = TrainPlan.with_callback(3, fn, eval_every=0)
        assert not any(isinstance(e, Eval) for e in plan.events)
        with pytest.raises(ValueError, match="eval_every"):
            TrainPlan.standard(3, eval_every=0)
        with pytest.raises(ValueError, match="eval_every"):
            fedap_plan(4, prune_round=2, eval_every=0)


class TestFLConfigValidation:
    def test_bad_local_momentum_fails_at_construction(self):
        with pytest.raises(ValueError, match="local_momentum"):
            FLConfig(local_momentum="nesterov")

    def test_bad_sampling_fails_fast(self):
        with pytest.raises(ValueError, match="clients_per_round"):
            FLConfig(num_clients=5, clients_per_round=10)
        with pytest.raises(ValueError, match="batch_size"):
            FLConfig(batch_size=0)
        with pytest.raises(ValueError, match="lr"):
            FLConfig(lr=-0.1)


# ---------------------------------------------------------------------------
# Execution over the real engine
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_world():
    # build_federated_data holds out 1000 training samples for the server
    # pool, so train_size must exceed device_pool + 1000
    spec = SyntheticSpec(num_classes=10, image_shape=(8, 8, 3),
                         train_size=1600, test_size=100, noise_scale=0.5)
    data = build_federated_data(num_clients=6, server_fraction=0.1,
                                device_pool=600, spec=spec)
    model = SimpleCNN(num_classes=10, image_shape=(8, 8, 3),
                      channels=(4, 8, 8), fc_width=16)
    return data, model


CFG = dict(num_clients=6, clients_per_round=3, local_epochs=1,
           batch_size=10, lr=0.05)


class TestExecutor:
    def test_run_result_structure(self, tiny_world):
        data, model = tiny_world
        tr = FederatedTrainer(model, data, feddumap_config(**CFG))
        res = tr.run(TrainPlan(Scan(2), Snapshot(name="mid"), Scan(1),
                               Eval()))
        assert res.history["round"] == [3]   # completed rounds at the Eval
        assert np.isfinite(res.history["loss"][0])
        assert res.artifacts["mid"]["round"] == 2
        assert float(res.state["round"]) == 3.0
        # snapshot is a live copy, distinct from the final params
        assert (jax.tree.leaves(res.artifacts["mid"]["params"])[0]
                is not jax.tree.leaves(res.params)[0])

    def test_snapshot_artifact_survives_donation(self, tiny_world):
        """The no-aliasing lock for the donation-aware snapshot buffer:
        the chunk jit donates its round state, so the Snapshot artifact
        must not alias the donated buffers — the Scans that follow have
        to leave it bit-identical to a run truncated at the snapshot
        point (an aliased artifact would be overwritten, or read back
        as a deleted donated array)."""
        data, model = tiny_world
        cfg = feddumap_config(**CFG)
        res = FederatedTrainer(model, data, cfg).run(
            TrainPlan(Scan(2), Snapshot(name="mid"), Scan(3), Eval()))
        res_trunc = FederatedTrainer(model, data, cfg).run(
            TrainPlan(Scan(2)))
        for a, b in zip(jax.tree.leaves(res.artifacts["mid"]["params"]),
                        jax.tree.leaves(res_trunc.params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # ... while the run itself genuinely moved on past the snapshot
        assert any(
            not np.array_equal(np.asarray(a), np.asarray(b))
            for a, b in zip(
                jax.tree.leaves(res.artifacts["mid"]["params"]),
                jax.tree.leaves(res.params)))

    def test_int_plan_equals_standard_plan(self, tiny_world):
        data, model = tiny_world
        cfg = feddumap_config(**CFG)
        res_a = FederatedTrainer(model, data, cfg).run(4, eval_every=2)
        res_b = FederatedTrainer(model, data, cfg).run(
            TrainPlan.standard(4, eval_every=2))
        np.testing.assert_allclose(res_a.history["acc"], res_b.history["acc"])
        for a, b in zip(jax.tree.leaves(res_a.params),
                        jax.tree.leaves(res_b.params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_leading_eval_records_round_zero(self, tiny_world):
        """Evaluate-before-training: a plan starting with Eval() must log
        the true round count 0 (not the fabricated round -1 of the old
        ``t - 1`` bookkeeping) with tau_eff 0.0 (no round has run)."""
        data, model = tiny_world
        tr = FederatedTrainer(model, data, feddumap_config(**CFG))
        res = tr.run(TrainPlan(Eval(), Scan(2), Eval()))
        assert res.history["round"] == [0, 2]
        assert res.history["tau_eff"][0] == 0.0
        assert res.history["tau_eff"][1] > 0.0
        assert all(np.isfinite(res.history["loss"]))

    def test_callback_replacement_restarts_state(self, tiny_world):
        """Legacy-hook contract: the callback fires at segment boundaries
        with the TRUE completed-round count (the first post-round hook
        sees 1, mirroring the Eval round fix — the old ``t - 1``
        bookkeeping fabricated a round 0), and a non-None return restarts
        the round state with the counter preserved."""
        data, model = tiny_world
        seen = []

        def cb(trainer, t, params):
            seen.append(t)
            if t == 1:
                return jax.tree.map(jnp.zeros_like, params)
            return None

        tr = FederatedTrainer(model, data, feddumap_config(**CFG))
        res = tr.run(TrainPlan.with_callback(3, cb, eval_every=3))
        assert seen == [1, 2, 3]
        assert float(res.state["round"]) == 3.0   # counter survived restart

    def test_compiled_engine_cache_shared_across_trainers(self, tiny_world):
        data, model = tiny_world
        cfg = feddumap_config(**CFG)
        tr_a = FederatedTrainer(model, data, cfg)
        tr_b = FederatedTrainer(model, data, cfg)
        assert tr_a._compiled() is tr_b._compiled()
        # different engine switches -> different compiled programs
        cfg2 = baselines.fedavg_config(**CFG)
        assert (FederatedTrainer(model, data, cfg2)._compiled()
                is not tr_a._compiled())


class TestFedAPPlan:
    @pytest.fixture(scope="class")
    def pruned_runs(self, tiny_world):
        data, model = tiny_world
        # min_rate forces a real compression budget: the pure eigen-gap rule
        # prunes nothing on this easy synthetic task, which would make the
        # equivalence below vacuous
        apcfg = FedAPConfig(prune_round=2, probe_size=8, participants=2,
                            min_rate=0.5)
        cfg = feddumap_config(**CFG, fedap=apcfg)

        def run(mode):
            tr = FederatedTrainer(model, data, cfg)
            plan = fedap_plan(4, prune_round=2, mode=mode, eval_every=2)
            return tr, plan, tr.run(plan)

        return run("mask"), run("shrink")

    def test_masked_prune_matches_shrink(self, tiny_world, pruned_runs):
        """Acceptance lock: the in-scan masked prune trains EXACTLY like the
        re-materializing prune on a norm-free model — compacting the masked
        params at the kept indices reproduces the shrunk params."""
        data, model = tiny_world
        (_, _, res_m), (_, _, res_s) = pruned_runs
        kept_m = res_m.artifacts["prune"]["kept"]
        kept_s = res_s.artifacts["prune"]["kept"]
        # the decision actually pruned (min_rate floor bit)
        assert sum(len(v) for v in kept_m.values()) < 4 + 8 + 8
        assert {k: v.tolist() for k, v in kept_m.items()} \
            == {k: v.tolist() for k, v in kept_s.items()}

        spec = model.prune_spec(res_m.params)
        compacted = pruning.shrink_params(res_m.params, spec, kept_m)
        for a, b in zip(jax.tree.leaves(compacted),
                        jax.tree.leaves(res_s.params)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-5)
        np.testing.assert_allclose(res_m.history["tau_eff"],
                                   res_s.history["tau_eff"], atol=1e-4)

    def test_masked_plan_never_rejits(self, tiny_world, pruned_runs):
        """Every round of the masked plan runs inside compiled scan chunks:
        the chunk program traces once per distinct chunk length and the
        prune event adds NO new trace (static shapes, masks in the carry).
        The expected count comes from the audited compile budget
        (repro/analysis/compile_budget.json), not an inline number."""
        (tr, plan, _), _ = pruned_runs
        ce = tr._compiled(use_masks=True)
        assert ce.chunk._cache_size() == expected_programs("local/prune_mask")
        assert expected_programs("local/prune_mask") \
            == len(plan.chunk_lengths())

    def test_masked_artifacts_and_zeroed_params(self, pruned_runs):
        (_, _, res_m), _ = pruned_runs
        art = res_m.artifacts["prune"]
        assert art["mode"] == "mask"
        assert 0.0 <= art["p_star"] <= 0.9
        assert set(art["filter_masks"]) == set(art["kept"])
        for p, m in zip(jax.tree.leaves(res_m.params),
                        jax.tree.leaves(res_m.state["masks"])):
            np.testing.assert_array_equal(
                np.asarray(p)[np.broadcast_to(m, p.shape) == 0], 0.0)

    def test_callback_after_masked_prune_keeps_masks(self, tiny_world):
        """A Callback replacing params after a Prune(mode='mask') must not
        discard the masks: the decision stays in force across the state
        rebuild and the replacement params are re-masked."""
        data, model = tiny_world
        apcfg = FedAPConfig(prune_round=1, probe_size=8, participants=2,
                            min_rate=0.5)
        cfg = feddumap_config(**CFG, fedap=apcfg)
        tr = FederatedTrainer(model, data, cfg)
        cb = lambda trainer, t, params: jax.tree.map(
            lambda p: p + 1.0, params)            # deliberately unmasked
        res = tr.run(TrainPlan(Scan(1), Prune(mode="mask"), Callback(cb),
                               Scan(1), Eval()))
        masked_coords = 0
        for p, m in zip(jax.tree.leaves(res.params),
                        jax.tree.leaves(res.state["masks"])):
            np.testing.assert_array_equal(
                np.asarray(p)[np.broadcast_to(m, p.shape) == 0], 0.0)
            masked_coords += int(np.sum(np.broadcast_to(m, p.shape) == 0))
        assert masked_coords > 0

    def test_shrink_records_params_before(self, pruned_runs):
        _, (_, _, res_s) = pruned_runs
        before = res_s.artifacts["prune"]["params_before"]
        assert (jax.tree.map(jnp.shape, before)
                != jax.tree.map(jnp.shape, res_s.params))

    def test_shrink_event_reproduces_legacy_hook_path(self, tiny_world):
        """Prune(mode="shrink") must produce exactly what the legacy
        ``on_round_end`` hook protocol produced: per-round chunks, FedAP
        decision on a copy of the params, shrink, momentum restart with the
        round counter preserved."""
        data, model = tiny_world
        apcfg = FedAPConfig(prune_round=2, probe_size=8, participants=2,
                            min_rate=0.5)
        cfg = feddumap_config(**CFG, fedap=apcfg)

        tr = FederatedTrainer(model, data, cfg)
        res = tr.run(fedap_plan(4, prune_round=2, mode="shrink",
                                eval_every=4))

        # legacy emulation: length=1 chunks + host hook after every round
        tr2 = FederatedTrainer(model, data, cfg)
        ce = tr2._compiled()
        data_dev = tr2._device_data()
        params0 = model.init(jax.random.key(cfg.seed))
        init_params = jax.tree.map(jnp.copy, params0)
        state = engine.init_round_state(jax.tree.map(jnp.copy, params0),
                                        ce.eng)
        for t in range(4):
            state, tr2._key, _ = ce.chunk(state, tr2._key, data_dev,
                                          length=1)
            if t + 1 == apcfg.prune_round:
                params = jax.tree.map(jnp.copy, state["params"])
                dec = fedap_decision(model, data, apcfg, params,
                                     init_params=init_params,
                                     rng=np.random.default_rng(cfg.seed))
                spec = model.prune_spec(params)
                round_ = state["round"]
                state = engine.init_round_state(
                    pruning.shrink_params(params, spec, dec.kept), ce.eng)
                state["round"] = round_

        for a, b in zip(jax.tree.leaves(res.params),
                        jax.tree.leaves(state["params"])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


class TestMaskNowShrinkLater:
    """fedap_plan(..., shrink_round=K): the prune round stays inside the
    compiled scan (mask), and K compacts to the SAME kept filters with the
    momentum buffers compacted, not restarted — so the trajectory equals
    shrink-from-the-start on a norm-free model while the steady-state
    rounds after K train the genuinely smaller model (the ROADMAP's
    warm-path gap)."""

    @pytest.fixture(scope="class")
    def runs(self, tiny_world):
        data, model = tiny_world
        apcfg = FedAPConfig(prune_round=2, probe_size=8, participants=2,
                            min_rate=0.5)
        cfg = feddumap_config(**CFG, fedap=apcfg)

        def run(plan):
            return FederatedTrainer(model, data, cfg).run(plan)

        res_ms = run(fedap_plan(6, prune_round=2, shrink_round=4,
                                eval_every=2))
        res_s = run(fedap_plan(6, prune_round=2, mode="shrink",
                               eval_every=2))
        return res_ms, res_s

    def test_masked_then_shrunk_equals_shrink_from_start(self, runs):
        res_ms, res_s = runs
        kept = res_ms.artifacts["prune"]["kept"]
        assert {k: v.tolist() for k, v in kept.items()} \
            == {k: v.tolist()
                for k, v in res_s.artifacts["prune"]["kept"].items()}
        assert sum(len(v) for v in kept.values()) < 4 + 8 + 8   # real prune
        # compacted shapes from round 4 on — and the same numbers round 6
        assert (jax.tree.map(jnp.shape, res_ms.params)
                == jax.tree.map(jnp.shape, res_s.params))
        for a, b in zip(jax.tree.leaves(res_ms.params),
                        jax.tree.leaves(res_s.params)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-5)
        # momentum was COMPACTED at the shrink, not restarted
        for a, b in zip(jax.tree.leaves(res_ms.state["server_m"]),
                        jax.tree.leaves(res_s.state["server_m"])):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-5)
        np.testing.assert_allclose(res_ms.history["tau_eff"],
                                   res_s.history["tau_eff"], atol=1e-4)

    def test_shrink_artifact_records_reuse(self, runs):
        res_ms, _ = runs
        art = res_ms.artifacts["shrink"]
        assert art["mode"] == "shrink"
        assert art["reused"] == "prune"
        assert art["p_star"] == res_ms.artifacts["prune"]["p_star"]
        # the artifact has the same summary shape as a decision-backed
        # prune (consumers index kept_counts)
        assert art["kept_counts"] == {k: len(v)
                                      for k, v in art["kept"].items()}
        # one FedAP decision for the whole plan: the shrink carries the
        # mask event's kept indices verbatim
        assert {k: v.tolist() for k, v in art["kept"].items()} \
            == {k: v.tolist()
                for k, v in res_ms.artifacts["prune"]["kept"].items()}

    def test_reuse_resolves_most_recent_decision(self, tiny_world):
        """Two mask prunes then a reuse-shrink: record() files the second
        decision as 'prune#1', and the shrink must compact to THAT one —
        the decision actually in force — not the stale first artifact."""
        data, model = tiny_world
        apcfg = FedAPConfig(prune_round=1, probe_size=8, participants=2,
                            min_rate=0.5)
        cfg = feddumap_config(**CFG, fedap=apcfg)
        tr = FederatedTrainer(model, data, cfg)
        res = tr.run(TrainPlan(Scan(1), Prune(mode="mask"), Scan(1),
                               Prune(mode="mask"), Scan(1),
                               Prune(mode="shrink", reuse="prune",
                                     name="shrink"), Scan(1), Eval()))
        live = res.artifacts["prune#1"]["kept"]
        assert {k: v.tolist() for k, v in res.artifacts["shrink"]
                ["kept"].items()} \
            == {k: v.tolist() for k, v in live.items()}
        # the compacted shapes match the in-force decision's kept counts
        from repro.core.pruning import get_path
        spec = model.prune_spec(model.init(jax.random.key(0)))
        for layer in spec.layers:
            w = get_path(res.params, layer.weight)
            assert w.shape[layer.filter_axis] == len(live[layer.name])
        assert np.isfinite(res.history["loss"][-1])

    def test_reuse_without_prior_prune_fails(self, tiny_world):
        data, model = tiny_world
        cfg = feddumap_config(**CFG)
        tr = FederatedTrainer(model, data, cfg)
        with pytest.raises(ValueError, match="reuse"):
            tr.run(TrainPlan(Scan(1),
                             Prune(mode="shrink", reuse="prune")))


class TestPrefetchSampling:
    """Double-buffered in-scan sampling must be a pure scheduling change:
    bit-identical history, params and key chain vs the serial draw."""

    def test_prefetch_bit_exact(self, tiny_world):
        import dataclasses as dc

        data, model = tiny_world
        plan = TrainPlan(Scan(2), Eval(), Scan(3), Eval())
        cfg_pf = feddumap_config(**CFG)
        cfg_serial = dc.replace(cfg_pf, prefetch_sampling=False)
        assert cfg_pf.prefetch_sampling        # the default
        res_pf = FederatedTrainer(model, data, cfg_pf).run(plan)
        res_serial = FederatedTrainer(model, data, cfg_serial).run(plan)
        assert res_pf.history["loss"] == res_serial.history["loss"]
        assert res_pf.history["acc"] == res_serial.history["acc"]
        assert res_pf.history["tau_eff"] == res_serial.history["tau_eff"]
        for a, b in zip(jax.tree.leaves(res_pf.params),
                        jax.tree.leaves(res_serial.params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_prefetch_key_chain_identical(self, tiny_world):
        """The chunk consumes exactly one key split per round in BOTH
        modes, so a run split across chunk boundaries stays aligned."""
        import dataclasses as dc

        data, model = tiny_world
        cfg = feddumap_config(**CFG)
        tr_pf = FederatedTrainer(model, data, cfg)
        tr_serial = FederatedTrainer(
            model, data, dc.replace(cfg, prefetch_sampling=False))
        tr_pf.run(TrainPlan(Scan(3)))
        tr_serial.run(TrainPlan(Scan(3)))
        np.testing.assert_array_equal(
            np.asarray(jax.random.key_data(tr_pf._key)),
            np.asarray(jax.random.key_data(tr_serial._key)))


class TestMaskedComputeKernel:
    """masked_compute="kernel": the engine threads filter masks into the
    model fns (differentiable Pallas masked_matmul under the masked dense
    layers) — and must train EXACTLY like the param-masking engine, which
    in turn equals the re-materializing shrink path on norm-free models."""

    @pytest.fixture(scope="class")
    def three_runs(self, tiny_world):
        data, model = tiny_world
        apcfg = FedAPConfig(prune_round=2, probe_size=8, participants=2,
                            min_rate=0.5)

        def run(mode, masked_compute):
            cfg = feddumap_config(**CFG, fedap=apcfg,
                                  masked_compute=masked_compute)
            tr = FederatedTrainer(model, data, cfg)
            plan = fedap_plan(4, prune_round=2, mode=mode, eval_every=2)
            return tr, plan, tr.run(plan)

        return (run("mask", "kernel"), run("mask", "params"),
                run("shrink", "params"))

    def test_kernel_equals_params_equals_shrink(self, tiny_world, three_runs):
        data, model = tiny_world
        (_, _, res_k), (_, _, res_p), (_, _, res_s) = three_runs
        kept = res_k.artifacts["prune"]["kept"]
        assert {k: v.tolist() for k, v in kept.items()} \
            == {k: v.tolist()
                for k, v in res_p.artifacts["prune"]["kept"].items()}
        # the decision pruned for real (min_rate floor bit)
        assert sum(len(v) for v in kept.values()) < 4 + 8 + 8
        for a, b in zip(jax.tree.leaves(res_k.params),
                        jax.tree.leaves(res_p.params)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-5)
        spec = model.prune_spec(res_k.params)
        compacted = pruning.shrink_params(res_k.params, spec, kept)
        for a, b in zip(jax.tree.leaves(compacted),
                        jax.tree.leaves(res_s.params)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-5)
        np.testing.assert_allclose(res_k.history["tau_eff"],
                                   res_p.history["tau_eff"], atol=1e-5)

    def test_kernel_mode_carries_filter_masks_without_rejit(self, three_runs):
        (tr, plan, res_k), _, _ = three_runs
        assert set(res_k.state["filter_masks"]) == {"conv1", "conv2", "conv3"}
        for name, fm in res_k.state["filter_masks"].items():
            np.testing.assert_array_equal(
                np.asarray(fm),
                np.asarray(res_k.artifacts["prune"]["filter_masks"][name]))
        # the prune event swapped carry contents only — one chunk program
        # (budgeted in repro/analysis/compile_budget.json)
        ce = tr._compiled(use_masks=True)
        assert ce.chunk._cache_size() \
            == expected_programs("local/prune_mask_kernel")

    def test_shrink_after_mask_in_kernel_mode(self, tiny_world):
        """The ROADMAP's mask-now-shrink-later pattern must run in kernel
        mode: the shrink event rebuilds the carry with all-ones filter
        masks at the SHRUNK shapes instead of crashing on the missing
        filter_masks slot."""
        data, model = tiny_world
        apcfg = FedAPConfig(prune_round=1, probe_size=8, participants=2,
                            min_rate=0.5)
        cfg = feddumap_config(**CFG, fedap=apcfg, masked_compute="kernel")
        tr = FederatedTrainer(model, data, cfg)
        res = tr.run(TrainPlan(Scan(1), Prune(mode="mask"), Scan(1),
                               Prune(mode="shrink"), Scan(1), Eval()))
        # compacted shapes after the shrink, all-ones filter masks
        assert (jax.tree.map(jnp.shape, res.params)
                != jax.tree.map(jnp.shape, res.artifacts["prune#1"]
                                ["params_before"]))
        for fm in res.state["filter_masks"].values():
            np.testing.assert_array_equal(np.asarray(fm), 1.0)
        assert np.isfinite(res.history["loss"][-1])

    def test_callback_preserves_filter_masks(self, tiny_world):
        data, model = tiny_world
        apcfg = FedAPConfig(prune_round=1, probe_size=8, participants=2,
                            min_rate=0.5)
        cfg = feddumap_config(**CFG, fedap=apcfg, masked_compute="kernel")
        tr = FederatedTrainer(model, data, cfg)
        cb = lambda trainer, t, params: jax.tree.map(lambda p: p + 1.0,
                                                     params)
        res = tr.run(TrainPlan(Scan(1), Prune(mode="mask"), Callback(cb),
                               Scan(1), Eval()))
        pruned_filters = sum(
            int(np.sum(np.asarray(m) == 0))
            for m in res.state["filter_masks"].values())
        assert pruned_filters > 0


class AlignedMLP:
    """192 -> 128 -> 128(prunable, masked_dense) -> 10 — a model whose
    masked layer IS 128-aligned, so kernel-mode training genuinely routes
    through the Pallas masked_matmul (SimpleCNN's prunable layers are all
    convs: its kernel mode only exercises feature-map masking)."""

    def init(self, rng):
        k1, k2, k3 = jax.random.split(rng, 3)
        d = 8 * 8 * 3
        he = lambda k, s, fi: (jax.random.normal(k, s)
                               * (2.0 / fi) ** 0.5).astype(jnp.float32)
        return {"fc1": {"w": he(k1, (d, 128), d),
                        "b": jnp.zeros((128,), jnp.float32)},
                "fc2": {"w": he(k2, (128, 128), 128),
                        "b": jnp.zeros((128,), jnp.float32)},
                "out": {"w": he(k3, (128, 10), 128),
                        "b": jnp.zeros((10,), jnp.float32)}}

    def apply(self, params, x, *, collect=False, masks=None):
        from repro.models.cnn import masked_dense

        h = x.reshape(x.shape[0], -1)
        h = jax.nn.relu(h @ params["fc1"]["w"] + params["fc1"]["b"])
        fmaps = {"fc1": h}
        if masks is not None and "fc2" in masks:
            h = jax.nn.relu(masked_dense(h, params["fc2"]["w"],
                                         masks["fc2"], params["fc2"]["b"]))
        else:
            h = jax.nn.relu(h @ params["fc2"]["w"] + params["fc2"]["b"])
        fmaps["fc2"] = h
        logits = h @ params["out"]["w"] + params["out"]["b"]
        return (logits, fmaps) if collect else logits

    def loss_and_acc(self, params, x, y, *, masks=None):
        from repro.models.cnn import softmax_xent_acc

        return softmax_xent_acc(self.apply(params, x, masks=masks), y)

    def feature_maps(self, params, x):
        return self.apply(params, x, collect=True)[1]

    def prune_spec(self, params):
        from repro.core.pruning import (CoupledParam, PrunableLayer,
                                        PruneSpec)

        return PruneSpec(layers=(
            PrunableLayer("fc2", ("fc2", "w"), 1,
                          (CoupledParam(("fc2", "b"), 0),
                           CoupledParam(("out", "w"), 0))),))


class TestKernelPathInsideEngine:
    """The Pallas masked_matmul must actually EXECUTE inside kernel-mode
    engine training (not just in unit tests), and still match params mode."""

    def test_kernel_routes_and_matches_params_mode(self, tiny_world,
                                                   monkeypatch):
        from repro.kernels import ops

        data, _ = tiny_world
        model = AlignedMLP()
        apcfg = FedAPConfig(prune_round=1, probe_size=8, participants=2,
                            min_rate=0.5)

        def run(mc):
            cfg = feddumap_config(**CFG, fedap=apcfg, masked_compute=mc)
            tr = FederatedTrainer(model, data, cfg)
            return tr.run(fedap_plan(3, prune_round=1, mode="mask",
                                     eval_every=3))

        calls = []
        real = ops.masked_matmul

        def spy(*a, **kw):
            calls.append(a[0].shape)
            return real(*a, **kw)

        monkeypatch.setattr(ops, "masked_matmul", spy)
        res_k = run("kernel")
        # the kernel branch was traced into the engine's compiled round —
        # local steps (B=10 -> padded 16) and server steps (B=32)
        assert calls, "masked_matmul never routed inside the engine"
        res_p = run("params")
        kept = res_k.artifacts["prune"]["kept"]["fc2"]
        assert 0 < len(kept) < 128            # the prune bit
        for a, b in zip(jax.tree.leaves(res_k.params),
                        jax.tree.leaves(res_p.params)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-5)
        for p, m in zip(jax.tree.leaves(res_k.params),
                        jax.tree.leaves(res_k.state["masks"])):
            np.testing.assert_array_equal(
                np.asarray(p)[np.broadcast_to(m, p.shape) == 0], 0.0)


class TestFedAPParticipantsClamp:
    def test_config_validates_at_construction(self):
        with pytest.raises(ValueError, match="participants"):
            FedAPConfig(participants=-1)
        with pytest.raises(ValueError, match="probe_size"):
            FedAPConfig(probe_size=0)

    def test_probe_draw_clamped_to_num_clients(self, tiny_world):
        """participants > num_clients must not crash with an opaque numpy
        error: the draw clamps to every available client, with a warning."""
        data, model = tiny_world
        apcfg = FedAPConfig(probe_size=8, participants=50, min_rate=0.5)
        params = model.init(jax.random.key(0))
        with pytest.warns(UserWarning, match="participants"):
            dec = fedap_decision(model, data, apcfg, params,
                                 init_params=params,
                                 rng=np.random.default_rng(0))
        assert 0.0 <= dec.p_star <= apcfg.max_rate


class TestMaskedModelRouting:
    def test_masked_apply_equals_masked_params(self, tiny_world):
        """Model-level mask routing (feature-map masking + masked_dense) is
        numerically the mask-multiplied parameter tree."""
        data, model = tiny_world
        params = model.init(jax.random.key(1))
        spec = model.prune_spec(params)
        kept = {l.name: np.sort(np.random.default_rng(0).choice(
            pruning.get_path(params, l.weight).shape[l.filter_axis],
            size=3, replace=False)) for l in spec.layers}
        fmask = pruning.filter_masks(params, spec, kept)
        pmask = pruning.param_masks(params, spec, kept)
        x = jnp.asarray(data.server_x[:4])

        via_masks = model.apply(params, x, masks=fmask)
        via_params = model.apply(engine.apply_masks(params, pmask), x)
        np.testing.assert_allclose(np.asarray(via_masks),
                                   np.asarray(via_params), atol=1e-6)

    def test_masked_dense_routes_pallas_when_aligned(self):
        """128-aligned shapes go through the Pallas masked_matmul kernel
        (interpret mode on CPU): fully-pruned column blocks are skipped,
        partially-kept blocks are re-masked elementwise — exact."""
        from repro.models import masked_dense

        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.standard_normal((128, 256)), jnp.float32)
        w = jnp.asarray(rng.standard_normal((256, 256)), jnp.float32)
        mask = np.ones((256,), np.float32)
        mask[128:] = 0.0          # second block fully pruned
        mask[7] = 0.0             # first block partially pruned
        b = jnp.asarray(rng.standard_normal((256,)), jnp.float32)
        out = masked_dense(x, w, jnp.asarray(mask), b)
        ref = (x @ w + b) * mask
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-4)

    def test_masked_dense_kernel_branch_taken_for_real_batch(self,
                                                            monkeypatch):
        """Regression: the Pallas branch used to be gated on ``m % block ==
        0``, so realistic batch sizes (10, 32) silently fell back to the
        dense XLA matmul.  The M-padding shim must route B=32 through the
        kernel — and still match the dense reference exactly."""
        from repro.kernels import ops
        from repro.models import masked_dense

        calls = []
        real = ops.masked_matmul

        def spy(x, w, block_mask, **kw):
            calls.append(x.shape)
            return real(x, w, block_mask, **kw)

        monkeypatch.setattr(ops, "masked_matmul", spy)
        rng = np.random.default_rng(0)
        w = jnp.asarray(rng.standard_normal((256, 256)), jnp.float32)
        mask = np.ones((256,), np.float32)
        mask[128:] = 0.0
        b = jnp.asarray(rng.standard_normal((256,)), jnp.float32)
        for batch, padded in [(32, 32), (10, 16)]:
            x = jnp.asarray(rng.standard_normal((batch, 256)), jnp.float32)
            out = masked_dense(x, w, jnp.asarray(mask), b)
            # padded only to the 8-row sublane multiple, not a full
            # 128-row block of wasted work — then sliced back
            assert calls[-1] == (padded, 256)
            assert out.shape == (batch, 256)
            ref = (x @ w + b) * mask
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                       atol=1e-4)
        assert len(calls) == 2           # the kernel branch ran both times

    def test_masked_dense_threads_nondefault_block(self):
        """block=64 must thread into the kernel's mask granularity (K=N=192
        passes the 64-gate but is not 128-aligned: the tiles take each
        dimension whole)."""
        from repro.models import masked_dense

        rng = np.random.default_rng(2)
        x = jnp.asarray(rng.standard_normal((10, 192)), jnp.float32)
        w = jnp.asarray(rng.standard_normal((192, 192)), jnp.float32)
        mask = np.ones((192,), np.float32)
        mask[64:128] = 0.0
        out = masked_dense(x, w, jnp.asarray(mask), block=64)
        ref = (x @ w) * mask
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-4)

    def test_lenet_masked_fc_fallback(self):
        """LeNet's fc widths are not 128-aligned: masked_dense falls back to
        the XLA path and must still equal the mask-multiplied params."""
        from repro.models import LeNet5

        model = LeNet5(num_classes=10, image_shape=(8, 8, 3))
        params = model.init(jax.random.key(0))
        spec = model.prune_spec(params)
        kept = {"fc1": np.arange(0, 120, 2), "fc2": np.arange(0, 84, 3)}
        spec = type(spec)(layers=tuple(l for l in spec.layers
                                       if l.name in kept))
        fmask = pruning.filter_masks(params, spec, kept)
        pmask = pruning.param_masks(params, spec, kept)
        x = jnp.asarray(np.random.default_rng(1).standard_normal(
            (4, 8, 8, 3)), jnp.float32)
        via_masks = model.apply(params, x, masks=fmask)
        via_params = model.apply(engine.apply_masks(params, pmask), x)
        np.testing.assert_allclose(np.asarray(via_masks),
                                   np.asarray(via_params), atol=1e-5)
