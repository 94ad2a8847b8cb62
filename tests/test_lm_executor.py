"""The unified LM path: TrainPlan-driven transformer fine-tuning on the
SAME PlanExecutor stack as the CNN repro.

Locks, mirroring the CNN suites (tests/test_plan.py, test_engine_diff.py):

  * pruning_lm decision invariants — `_aligned_keep` monotone in the
    rate / a multiple of the alignment / never 0, uniform kept count
    across the scanned stack, and construction-time validation naming
    the rate, the alignment and the layer;
  * mask/shrink forward equivalence on a tiny LM — the filter-mask
    forward and the masked-params forward zero exactly the same units
    (the coupling-closed zero set contributes silu(0)=0 through wo) and
    agree to float32 accumulation, the all-ones mask is a no-op to the
    same tolerance, the kernel path is bit-identical to itself across
    calls, and both match the structurally
    shrunk forward to float tolerance (compacting the zero rows changes
    the K-reduction association — the same 5e-5-class budget as the
    CNN's masked-vs-shrink lock);
  * a full fedap_plan run with Prune(mode="mask") on the local scan
    backend — layer-adaptive FedAP injected as keep-masks carried in
    the layer scan, ZERO extra chunk programs (budgeted in
    compile_budget.json), kernel mode matching params mode;
  * mesh == local parity <= 1e-5 per round through the full
    FederatedTrainer path (adapts to the available device count, like
    tests/test_mesh_backend.py — 8-way under the CI job's XLA_FLAGS);
  * the scan-compiled engine vs the f64 `ref_engine` oracle on explicit
    LM batches for FedAvg and the FedDUM momentum wiring (masked row
    included): the oracle runs the ROUND ARITHMETIC (aggregation,
    momentum, dynamic server update) in float64 around the shared jax
    grad function, so any disagreement > 1e-5 is engine wiring, not
    model float noise.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.compile_budget import expected_programs
from repro.configs.base import ModelConfig
from repro.core import engine, ref_engine
from repro.core.engine import EngineConfig
from repro.core.plan import fedap_plan
from repro.core.pruning import FedAPConfig
from repro.core.pruning_lm import (
    _aligned_keep,
    ffn_kept_indices,
    ffn_param_masks,
)
from repro.core.rounds import FederatedTrainer, feddumap_config
from repro.data.pipeline import build_lm_federated_data
from repro.data.synthetic import TokenSpec
from repro.models.layers import apply_mlp, masked_dense
from repro.models.lm import LM

TINY = dict(name="dense-tiny", family="dense", rope="1d", norm="rmsnorm",
            act="silu", param_dtype="float32", remat="none",
            num_layers=2, d_model=128, num_heads=4, num_kv_heads=2,
            d_ff=512, vocab_size=2048)


def tiny_model():
    """A FRESH LM per run: the session compile cache is keyed on the
    model instance, and init is a pure function of (cfg, key), so every
    fresh instance starts from identical params."""
    return LM(ModelConfig(**TINY))


@pytest.fixture(scope="module")
def lm_data():
    return build_lm_federated_data(
        num_clients=8,
        spec=TokenSpec(vocab_size=2048, num_topics=16, seq_len=17,
                       num_sequences=256))


def lm_cfg(**kw):
    return feddumap_config(num_clients=8, clients_per_round=4,
                           local_epochs=1, batch_size=4,
                           server_batch_size=8, lr=3e-3, lr_decay=1.0,
                           fedap=FedAPConfig(align=128, min_rate=0.5,
                                             probe_size=4, participants=2),
                           **kw)


MASK_PLAN = lambda: fedap_plan(4, prune_round=2, mode="mask", eval_every=1)


@pytest.fixture(scope="module")
def local_mask_run(lm_data):
    """The reference run: fedap_plan with Prune(mode="mask") on the local
    scan backend — shared by the artifact, budget, mesh and kernel locks."""
    tr = FederatedTrainer(tiny_model(), lm_data, lm_cfg())
    return tr, tr.run(MASK_PLAN())


@pytest.fixture(scope="module")
def local_kernel_run(lm_data):
    """The same run with masked_compute="kernel": the FFN matmuls go
    through the Pallas masked_matmul kernel."""
    tr = FederatedTrainer(tiny_model(), lm_data,
                          lm_cfg(masked_compute="kernel"))
    return tr, tr.run(MASK_PLAN())


# ---------------------------------------------------------------------------
# pruning_lm decision invariants (host-side, no training)
# ---------------------------------------------------------------------------

class TestPruningLMInvariants:
    def test_aligned_keep_monotone_in_rate(self):
        keeps = [_aligned_keep(512, r, 128) for r in
                 (0.0, 0.1, 0.3, 0.5, 0.7, 0.9)]
        assert keeps == sorted(keeps, reverse=True)
        assert keeps[0] == 512                      # rate 0 keeps everything

    def test_aligned_keep_multiple_of_alignment_and_never_zero(self):
        for rate in (0.1, 0.5, 0.74, 0.9, 0.999):
            keep = _aligned_keep(512, rate, 128)
            assert keep % 128 == 0 and 1 <= keep <= 512
        # narrower than the alignment: falls back to the raw count, >= 1
        assert _aligned_keep(64, 0.9, 128) == 7
        assert _aligned_keep(8, 0.999, None) == 1

    def test_rate_validation_names_rate_and_layer(self):
        with pytest.raises(ValueError, match=r"rate.*\[0, 1\).*1\.0"):
            _aligned_keep(512, 1.0, 128)
        with pytest.raises(ValueError, match="mlp stack"):
            ffn_kept_indices({"layers": {"mlp": {
                "wi": jnp.ones((2, 16, 96)), "wg": jnp.ones((2, 16, 96)),
                "wo": jnp.ones((2, 96, 16))}}}, ModelConfig(**TINY), -0.1)

    def test_alignment_overflow_names_alignment_and_width(self):
        # width 192 >= align 128 but not a multiple: rate 0.1 keeps 173,
        # which aligns UP to 256 > 192
        with pytest.raises(ValueError, match="128-lane-aligned.*192"):
            _aligned_keep(192, 0.1, 128, layer="mlp stack (d_ff=192)")

    def test_uniform_kept_count_across_scanned_stack(self):
        model = tiny_model()
        params = model.init(jax.random.key(0))
        idx = ffn_kept_indices(params, model.cfg, 0.5, align=128)
        assert idx.shape == (TINY["num_layers"], 256)   # ONE count, all layers
        # rows are sorted unique unit ids — a valid gather per layer
        for row in idx:
            assert len(set(row.tolist())) == len(row)
            assert (np.diff(row) > 0).all()

    def test_decide_kept_matches_pruning_lm(self):
        model = tiny_model()
        params = model.init(jax.random.key(0))
        kept = model.decide_kept(params, 0.5)
        np.testing.assert_array_equal(
            np.asarray(kept["mlp"]),
            ffn_kept_indices(params, model.cfg, 0.5, align=128))


def _f32_sum_tol(ref):
    """Float32 accumulation tolerance of the forward: the longest
    contraction (d_ff) times float32's epsilon, times the largest
    reference magnitude."""
    return (TINY["d_ff"] * np.finfo(np.float32).eps
            * float(np.abs(np.asarray(ref)).max()))


class TestMaskShrinkEquivalence:
    @pytest.fixture(scope="class")
    def forwards(self):
        model = tiny_model()
        params = model.init(jax.random.key(3))
        rng = np.random.default_rng(5)
        batch = {"tokens": jnp.asarray(
            rng.integers(0, TINY["vocab_size"], (2, 16)), jnp.int32)}
        kept = model.decide_kept(params, 0.5)
        return model, params, batch, kept

    def test_filter_mask_equals_param_mask_exactly(self, forwards):
        """The coupling-closed zero set: masking the FFN pre-activation
        (filter masks, the Pallas kernel) and masking the params (wi/wg
        cols + wo rows, XLA's dot) zero EXACTLY the same units, and agree
        elsewhere to float32 accumulation.  Bit equality of the two
        paths would depend on the host's dot codegen, so what any host
        holds is checked: pruned pre-activations and contributions are
        exact zeros on both paths, the logits agree within a tolerance
        derived from the contraction, and the masked path is
        bit-identical to itself across two calls."""
        model, params, batch, kept = forwards
        fmasks = model.filter_masks(params, kept)
        pmasks = model.param_masks(params, kept)
        mlp, pmlp = params["layers"]["mlp"], pmasks["layers"]["mlp"]
        x = jnp.asarray(np.random.default_rng(6).standard_normal(
            (32, TINY["d_model"])), jnp.float32)
        for layer in range(TINY["num_layers"]):
            unit = np.asarray(fmasks["mlp"][layer])
            pruned = unit == 0
            assert pruned.any() and not pruned.all()
            for via_mask, via_params in (
                    (masked_dense(x, mlp["wi"][layer], fmasks["mlp"][layer]),
                     x @ (mlp["wi"][layer] * pmlp["wi"][layer])),
                    (masked_dense(x, mlp["wg"][layer], fmasks["mlp"][layer]),
                     x @ (mlp["wg"][layer] * pmlp["wg"][layer]))):
                assert np.all(np.asarray(via_mask)[:, pruned] == 0.0)
                assert np.all(np.asarray(via_params)[:, pruned] == 0.0)
            # a pruned unit contributes an exact zero through wo: cutting
            # its wo rows changes no bit of the FFN output
            one = {k: v[layer] for k, v in mlp.items()}
            h = apply_mlp(one, x, TINY["act"], fmasks["mlp"][layer])
            h_cut = apply_mlp({**one, "wo": one["wo"] * pmlp["wo"][layer]},
                              x, TINY["act"], fmasks["mlp"][layer])
            np.testing.assert_array_equal(np.asarray(h), np.asarray(h_cut))
        logits_fm, _ = model.apply(params, batch, masks=fmasks)
        again, _ = model.apply(params, batch, masks=fmasks)
        masked = jax.tree.map(jnp.multiply, params, pmasks)
        logits_pm, _ = model.apply(masked, batch)
        np.testing.assert_array_equal(np.asarray(logits_fm),
                                      np.asarray(again))
        np.testing.assert_allclose(np.asarray(logits_fm),
                                   np.asarray(logits_pm), rtol=0,
                                   atol=_f32_sum_tol(logits_pm))

    def test_masked_forward_matches_shrunk_forward(self, forwards):
        """Pruning as masks == pruning as structure, to float tolerance:
        compacting the kept units changes the wo K-reduction association
        (the zero rows vanish), so the budget is the CNN suite's
        5e-5-class one, not bit equality."""
        model, params, batch, kept = forwards
        logits_fm, _ = model.apply(params, batch,
                                   masks=model.filter_masks(params, kept))
        logits_sh, _ = model.apply(model.shrink_params(params, kept), batch)
        np.testing.assert_allclose(np.asarray(logits_fm),
                                   np.asarray(logits_sh), atol=5e-5)

    def test_all_ones_masks_are_a_bit_exact_noop(self, forwards):
        """All-ones masks prune nothing: the kernel path agrees with the
        dense forward within float32 accumulation over the contraction,
        and is bit-identical to itself across two calls (bit equality
        with XLA's dot would depend on the host's dot codegen)."""
        model, params, batch, _ = forwards
        ones = model.filter_masks(params, {})
        logits, _ = model.apply(params, batch)
        logits_m, _ = model.apply(params, batch, masks=ones)
        again, _ = model.apply(params, batch, masks=ones)
        np.testing.assert_array_equal(np.asarray(logits_m),
                                      np.asarray(again))
        np.testing.assert_allclose(np.asarray(logits_m), np.asarray(logits),
                                   rtol=0, atol=_f32_sum_tol(logits))

    def test_param_masks_zero_exactly_the_shrunk_coordinates(self, forwards):
        model, params, _, kept = forwards
        masks = ffn_param_masks(params, kept)
        mlp = masks["layers"]["mlp"]
        unit = np.zeros((TINY["num_layers"], TINY["d_ff"]), np.float32)
        np.put_along_axis(unit, np.asarray(kept["mlp"]), 1.0, axis=1)
        np.testing.assert_array_equal(np.asarray(mlp["wi"]),
                                      np.broadcast_to(unit[:, None, :],
                                                      mlp["wi"].shape))
        np.testing.assert_array_equal(np.asarray(mlp["wo"]),
                                      np.broadcast_to(unit[:, :, None],
                                                      mlp["wo"].shape))
        # everything outside the mlp stays all-ones
        for leaf in jax.tree.leaves({k: v for k, v in
                                     masks["layers"].items() if k != "mlp"}):
            np.testing.assert_array_equal(np.asarray(leaf), 1.0)

    def test_moe_mask_mode_rejected(self):
        """A zeroed router logit is not -inf: MoE stacks must refuse
        mask-mode pruning up front and point at Prune(mode='shrink')."""
        from repro.configs import get_config
        from repro.models.api import build_model

        model = build_model(get_config("arctic-480b").reduced())
        with pytest.raises(ValueError, match="MoE"):
            model.apply({}, {"tokens": jnp.zeros((1, 4), jnp.int32)},
                        masks={"mlp": jnp.ones((1, 4))})


# ---------------------------------------------------------------------------
# The executor path: fedap_plan on the local backend, budget, kernel, mesh
# ---------------------------------------------------------------------------

class TestLMExecutor:
    def test_mask_plan_prunes_at_the_lane_boundary(self, local_mask_run):
        """What Algorithm 3 guarantees, whatever eigen-gap index the
        probes pick: p* lies in [min_rate, max_rate], the kept count is
        d_ff - floor(p* d_ff) rounded UP to the 128-lane boundary, and the
        realized layer rate follows from it.  (The gap index itself moves
        with the PRNG bits behind init and sampling; pinning one count
        would lock the random stream, not the algorithm.)"""
        tr, res = local_mask_run
        art = res.artifacts["prune"]
        d_ff, ap = TINY["d_ff"], tr.cfg.fedap
        assert ap.min_rate <= art["p_star"] <= ap.max_rate
        keep = _aligned_keep(d_ff, art["p_star"], 128)
        # min_rate 0.5 keeps at most 256 of 512, max_rate at least 128
        assert keep in (128, 256)
        assert art["kept_counts"] == {"mlp": keep}
        assert np.asarray(art["kept"]["mlp"]).shape == (2, keep)
        assert art["layer_rates"] == {"mlp": 1.0 - keep / d_ff}
        assert res.history["round"] == [1, 2, 3, 4]
        assert all(np.isfinite(res.history["loss"]))
        # the param-structured keep-masks are in force in the round state:
        # exactly `keep` surviving wi columns in every layer
        m_wi = np.asarray(res.state["masks"]["layers"]["mlp"]["wi"])
        np.testing.assert_array_equal(m_wi.sum(axis=2), float(keep))

    def test_mask_prune_adds_zero_chunk_programs(self, local_mask_run):
        """The LM leg of the zero-re-lowering contract: the Prune(mask)
        event swaps scan-carried masks only — the chunk program count is
        the compile_budget.json LM baseline (== the no-prune count)."""
        tr, _ = local_mask_run
        ce = tr._compiled(use_masks=True)
        assert ce.chunk._cache_size() \
            == expected_programs("local/lm_prune_mask")
        assert expected_programs("local/lm_prune_mask") \
            == expected_programs("local/scan_eval")

    def test_kernel_mode_matches_params_mode(self, local_mask_run,
                                             local_kernel_run):
        """masked_compute="kernel" routes the masked FFN matmuls through
        the Pallas masked_matmul — same decision, same training to 1e-5."""
        _, res_p = local_mask_run
        tr, res_k = local_kernel_run
        assert {k: np.asarray(v).tolist()
                for k, v in res_k.artifacts["prune"]["kept"].items()} \
            == {k: np.asarray(v).tolist()
                for k, v in res_p.artifacts["prune"]["kept"].items()}
        for a, b in zip(jax.tree.leaves(res_k.params),
                        jax.tree.leaves(res_p.params)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-5)
        np.testing.assert_allclose(res_k.history["loss"],
                                   res_p.history["loss"], atol=1e-5)
        assert tr._compiled(use_masks=True).chunk._cache_size() \
            == expected_programs("local/lm_prune_mask_kernel")

    def test_mesh_kernel_mode_matches_local_per_round(self, lm_data,
                                                      local_kernel_run):
        """Kernel mode on the mesh runs local training and the FedDU
        server scan under shard_map (GSPMD cannot partition a Mosaic
        kernel) — the numbers stay the local kernel run's <= 1e-5."""
        _, res_l = local_kernel_run
        tr = FederatedTrainer(tiny_model(), lm_data,
                              lm_cfg(masked_compute="kernel"),
                              backend="mesh")
        res_m = tr.run(MASK_PLAN())
        for key in ("loss", "acc", "tau_eff"):
            np.testing.assert_allclose(
                res_m.history[key], res_l.history[key], atol=1e-5,
                err_msg=f"mesh kernel history[{key}] diverged from local")
        for a, b in zip(jax.tree.leaves(res_m.params),
                        jax.tree.leaves(res_l.params)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-5)

    def test_mesh_matches_local_per_round(self, lm_data, local_mask_run):
        """mesh == local <= 1e-5 PER ROUND through the full trainer path
        (1-way mesh under plain tier-1, 8-way under the CI job)."""
        _, res_l = local_mask_run
        tr = FederatedTrainer(tiny_model(), lm_data, lm_cfg(),
                              backend="mesh")
        res_m = tr.run(MASK_PLAN())
        for key in ("loss", "acc", "tau_eff"):
            np.testing.assert_allclose(
                res_m.history[key], res_l.history[key], atol=1e-5,
                err_msg=f"mesh history[{key}] diverged from local")
        for a, b in zip(jax.tree.leaves(res_m.params),
                        jax.tree.leaves(res_l.params)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-5)


# ---------------------------------------------------------------------------
# Engine vs the widened f64 oracle on explicit LM batches
# ---------------------------------------------------------------------------

O = dict(num_layers=2, d_model=64, num_heads=2, num_kv_heads=1, d_ff=128,
         vocab_size=256)
CLIENTS, STEPS, BATCH, TAU, SBATCH, SEQ, ROUNDS = 2, 2, 2, 2, 2, 8, 2

ORACLE_ROWS = {
    "fedavg": (dict(use_server_update=False, local_momentum="none",
                    server_momentum=False), False),
    "feddum-masked": (dict(use_server_update=True, local_momentum="restart",
                           server_momentum=True), True),
}


@pytest.fixture(scope="module")
def oracle_world():
    model = LM(ModelConfig(**{**TINY, **O}))
    params = model.init(jax.random.key(1))
    rng = np.random.default_rng(17)

    def toks(lead):
        t = rng.integers(0, O["vocab_size"], lead + (SEQ + 1,))
        return (t[..., :-1].astype(np.int32), t[..., 1:].astype(np.int32))

    rounds = []
    for _ in range(ROUNDS):
        rounds.append({
            "client": toks((CLIENTS, STEPS, BATCH)),
            "sizes": np.asarray([30.0, 20.0], np.float32),
            "server": toks((TAU, SBATCH)),
            "d_round": np.float32(0.3),
            "d_server": np.float32(0.02),
            "n0": np.float32(50.0),
        })
    return model, params, rounds


@pytest.mark.parametrize("row", list(ORACLE_ROWS))
def test_lm_engine_matches_f64_oracle(oracle_world, row):
    """round_core under scan+jit vs ref_round: the oracle's aggregation,
    momentum and FedDU server update run in float64 around the SAME jax
    grad function, so a per-round drift > 1e-5 is engine wiring."""
    model, params, rounds = oracle_world
    mode, use_masks = ORACLE_ROWS[row]
    cfg = EngineConfig(lr=0.05, lr_decay=0.97, use_masks=use_masks, **mode)

    masks = None
    if use_masks:
        masks = ffn_param_masks(
            params, {"mlp": ffn_kept_indices(params, model.cfg, 0.5,
                                             align=64)})

    def la(p, b):
        return model.loss_and_acc(p, b[0], b[1])

    def grad(p, b):
        return jax.grad(lambda q: la(q, b)[0])(p)

    def np_la(p, b):
        p32 = jax.tree.map(lambda x: jnp.asarray(x, jnp.float32), p)
        loss, acc = la(p32, (jnp.asarray(b[0]), jnp.asarray(b[1])))
        return float(loss), float(acc)

    def np_grad(p, b):
        p32 = jax.tree.map(lambda x: jnp.asarray(x, jnp.float32), p)
        g = grad(p32, (jnp.asarray(b[0]), jnp.asarray(b[1])))
        return jax.tree.map(lambda x: np.asarray(x, np.float64), g)

    # oracle leg: naive f64 loops, per-round history
    ref = ref_engine.ref_init_state(params, cfg, masks=masks)
    ref_params, ref_taus = [], []
    for b in rounds:
        ref, met = ref_engine.ref_round(cfg, np_grad, np_la, ref, b)
        ref_params.append(ref["params"])
        ref_taus.append(met["tau_eff"])

    # engine leg: round_core under lax.scan + jit, per-round history
    state0 = engine.init_round_state(jax.tree.map(jnp.asarray, params), cfg)
    if masks is not None:
        state0["masks"] = jax.tree.map(jnp.asarray, masks)
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs),
                           *[jax.tree.map(jnp.asarray, b) for b in rounds])

    @jax.jit
    def run(state, batches):
        def body(st, b):
            st, metrics = engine.round_core(cfg, grad, la, st, b)
            return st, (metrics["tau_eff"], st["params"])
        return jax.lax.scan(body, state, batches)

    _, (taus, phist) = run(state0, stacked)

    # Round-arithmetic budget: both legs share the SAME f32 jax grad, so
    # the only divergence is the federated arithmetic (aggregation,
    # momentum, FedDU update) in f64 vs f32 — measured worst drift is
    # ~2.5e-7 over ROUNDS rounds; 2e-6 gives ~8x headroom.  (The model
    # forward's own f32 error is locked separately against the NumPy-f64
    # oracle in tests/test_ref64.py.)
    for r in range(ROUNDS):
        for leaf, ref_leaf in zip(jax.tree.leaves(phist),
                                  jax.tree.leaves(ref_params[r])):
            np.testing.assert_allclose(
                np.asarray(leaf[r]), ref_leaf, atol=2e-6,
                err_msg=f"[{row}] params diverged from oracle at round {r}")
    np.testing.assert_allclose(np.asarray(taus), np.asarray(ref_taus),
                               atol=2e-6, err_msg=f"[{row}] tau_eff")
    if masks is not None:
        for leaf, m in zip(jax.tree.leaves(phist), jax.tree.leaves(masks)):
            np.testing.assert_array_equal(
                np.asarray(leaf[-1])[
                    np.broadcast_to(m, leaf.shape[1:]) == 0], 0.0)
