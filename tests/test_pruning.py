"""FedAP (Algorithm 3): rates, threshold, HRank selection, shrink."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.pruning import (
    CoupledParam,
    FedAPConfig,
    PrunableLayer,
    PruneSpec,
    aggregate_rates,
    expected_rate_from_spectrum,
    feature_map_ranks,
    filter_masks,
    get_path,
    global_threshold,
    param_masks,
    per_layer_rates,
    select_filters,
    set_path,
    shrink_params,
)


class TestEigenGapRule:
    def test_clear_gap_selected(self):
        eigs = jnp.asarray([0.0, 0.1, 0.2, 10.0, 11.0])
        # gap 0.2 -> 10.0 = 9.8 > 4 * 1.0 at index m=3 (ascending)
        rate = expected_rate_from_spectrum(eigs, jnp.asarray(1.0))
        assert float(rate) == pytest.approx(3 / 5)

    def test_first_gap_not_largest_index(self):
        """Paper: 'take the FIRST m_k' — two qualifying gaps, the earlier
        one wins (regression: max-index selection pruned ~90%)."""
        eigs = jnp.asarray([0.0, 0.1, 10.0, 10.1, 10.2, 50.0, 51.0, 52.0])
        rate = expected_rate_from_spectrum(eigs, jnp.asarray(1.0))
        assert float(rate) == pytest.approx(2 / 8)

    def test_no_gap_means_no_pruning(self):
        eigs = jnp.linspace(0.0, 1.0, 10)
        rate = expected_rate_from_spectrum(eigs, jnp.asarray(5.0))
        assert float(rate) == 0.0

    def test_capped_at_max_rate(self):
        eigs = jnp.asarray([0.0] * 9 + [1000.0])
        rate = expected_rate_from_spectrum(eigs, jnp.asarray(0.001), max_rate=0.5)
        assert float(rate) <= 0.5

    @pytest.mark.parametrize("pad", [0, 1, 4])
    def test_padded_spectrum_equals_unpadded(self, pad):
        """The ragged-probe contract: a spectrum padded with leading zero
        eigenvalues (what zeroed per-sample-gradient rows add to the Gram)
        searched with ``valid=d`` must give EXACTLY the unpadded rate —
        including the boundary gap between the last padding zero and the
        smallest valid eigenvalue, which must never qualify."""
        rng = np.random.default_rng(0)
        for lip in (0.01, 0.5, 5.0):
            eigs = jnp.asarray(np.sort(rng.gamma(1.0, 2.0, size=12)))
            base = expected_rate_from_spectrum(eigs, jnp.asarray(lip))
            padded = jnp.concatenate([jnp.zeros((pad,)), eigs])
            got = expected_rate_from_spectrum(padded, jnp.asarray(lip),
                                              valid=eigs.shape[0])
            assert float(got) == pytest.approx(float(base))

    def test_boundary_gap_excluded(self):
        """A huge jump from the padding zeros into the valid spectrum is
        NOT an eigen-gap (the host path has no gap before valid[0])."""
        eigs = jnp.asarray([100.0, 101.0, 102.0, 103.0])  # no internal gap
        base = expected_rate_from_spectrum(eigs, jnp.asarray(1.0))
        padded = jnp.concatenate([jnp.zeros((3,)), eigs])
        got = expected_rate_from_spectrum(padded, jnp.asarray(1.0), valid=4)
        assert float(base) == float(got) == 0.0


class TestFormula15:
    def test_low_niid_dominates(self):
        """A participant whose data is near-IID (small D) gets MORE weight."""
        rates = jnp.asarray([0.9, 0.1])
        sizes = jnp.asarray([100.0, 100.0])
        niid = jnp.asarray([1e-6, 1.0])      # first participant near-IID
        out = float(aggregate_rates(rates, sizes, niid))
        assert out > 0.8

    def test_size_weighting(self):
        rates = jnp.asarray([0.9, 0.1])
        sizes = jnp.asarray([1000.0, 1.0])
        niid = jnp.asarray([0.5, 0.5])
        assert float(aggregate_rates(rates, sizes, niid)) > 0.8

    @given(st.lists(st.floats(0.0, 1.0), min_size=3, max_size=3))
    @settings(max_examples=30, deadline=None)
    def test_convex_combination(self, rates):
        sizes = jnp.asarray([10.0, 20.0, 30.0])
        niid = jnp.asarray([0.1, 0.2, 0.3])
        out = float(aggregate_rates(jnp.asarray(rates), sizes, niid))
        assert min(rates) - 1e-6 <= out <= max(rates) + 1e-6


class TestThresholdAndLayerRates:
    def _spec(self):
        return PruneSpec(layers=(
            PrunableLayer("a", ("a", "w"), 1),
            PrunableLayer("b", ("b", "w"), 1),
        ))

    def test_global_threshold_quantile(self):
        params = {"a": {"w": jnp.asarray([[0.1, 0.2, 0.3, 0.4]])},
                  "b": {"w": jnp.asarray([[0.5, 0.6, 0.7, 0.8]])}}
        thr = global_threshold(params, self._spec(), jnp.asarray(0.5))
        assert float(thr) == pytest.approx(0.5)

    def test_per_layer_rates_reflect_magnitudes(self):
        params = {"a": {"w": jnp.asarray([[0.01, 0.02, 0.9, 0.9]])},
                  "b": {"w": jnp.asarray([[0.9, 0.9, 0.9, 0.9]])}}
        rates = per_layer_rates(params, self._spec(), jnp.asarray(0.5))
        assert float(rates["a"]) == pytest.approx(0.5)
        assert float(rates["b"]) == pytest.approx(0.0)


class TestSelection:
    def test_keeps_highest_scores(self):
        scores = np.asarray([5.0, 1.0, 4.0, 2.0, 3.0, 0.0])
        kept = select_filters(scores, 0.5)
        assert set(kept) == {0, 2, 4}

    def test_alignment_prunes_less_never_more(self):
        scores = np.arange(256).astype(float)
        kept = select_filters(scores, 0.3, align=128)
        # 256 * 0.7 = 179.2 -> aligned UP to 256
        assert len(kept) == 256 or len(kept) % 128 == 0
        assert len(kept) >= 256 - int(0.3 * 256)

    @given(st.integers(4, 64), st.floats(0.0, 0.95))
    @settings(max_examples=40, deadline=None)
    def test_min_keep(self, d, rate):
        kept = select_filters(np.random.default_rng(0).random(d), rate)
        assert 1 <= len(kept) <= d


class TestShrink:
    def test_coupled_shapes(self):
        params = {
            "conv": {"w": jnp.zeros((3, 3, 4, 16)), "b": jnp.zeros((16,))},
            "next": {"w": jnp.zeros((3, 3, 16, 8))},
        }
        spec = PruneSpec(layers=(
            PrunableLayer("conv", ("conv", "w"), 3,
                          (CoupledParam(("conv", "b"), 0),
                           CoupledParam(("next", "w"), 2))),
        ))
        kept = {"conv": np.asarray([0, 3, 7, 11])}
        out = shrink_params(params, spec, kept)
        assert out["conv"]["w"].shape == (3, 3, 4, 4)
        assert out["conv"]["b"].shape == (4,)
        assert out["next"]["w"].shape == (3, 3, 4, 8)

    def test_masks_match_kept(self):
        params = {"conv": {"w": jnp.zeros((3, 3, 4, 8))}}
        spec = PruneSpec(layers=(PrunableLayer("conv", ("conv", "w"), 3),))
        masks = filter_masks(params, spec, {"conv": np.asarray([1, 2])})
        np.testing.assert_allclose(masks["conv"], [0, 1, 1, 0, 0, 0, 0, 0])

    def test_param_masks_zero_exactly_the_shrunk_slices(self):
        params = {
            "conv": {"w": jnp.ones((3, 3, 4, 16)), "b": jnp.ones((16,))},
            "next": {"w": jnp.ones((3, 3, 16, 8))},
        }
        spec = PruneSpec(layers=(
            PrunableLayer("conv", ("conv", "w"), 3,
                          (CoupledParam(("conv", "b"), 0),
                           CoupledParam(("next", "w"), 2))),
        ))
        kept = {"conv": np.asarray([0, 3, 7, 11])}
        masks = param_masks(params, spec, kept)
        keep = np.zeros(16)
        keep[kept["conv"]] = 1.0
        np.testing.assert_allclose(masks["conv"]["b"], keep)
        # factored: only the pruned axis is held at full size
        np.testing.assert_allclose(masks["conv"]["w"],
                                   keep.reshape(1, 1, 1, 16))
        np.testing.assert_allclose(masks["next"]["w"],
                                   keep.reshape(1, 1, 16, 1))
        # the dual invariant: shrinking the mask-multiplied params drops
        # only ones, shrinking the complement drops only zeros
        masked = jax.tree.map(lambda p, m: p * m, params, masks)
        shrunk = shrink_params(masked, spec, kept)
        assert all(bool(jnp.all(x == 1)) for x in jax.tree.leaves(shrunk))


class TestPathAddressing:
    """get_path/set_path go through jax.tree_util key-paths, so PruneSpec
    works on non-dict pytrees (lists, tuples, namedtuples, registered
    dataclasses) — regression for the dict-only implementation."""

    def test_list_and_tuple_pytrees(self):
        tree = [{"w": jnp.ones((2, 4))}, ({"w": jnp.zeros((4, 3))},)]
        assert get_path(tree, (0, "w")).shape == (2, 4)
        out = set_path(tree, (1, 0, "w"), jnp.ones((4, 3)))
        assert float(jnp.sum(out[1][0]["w"])) == 12.0
        assert float(jnp.sum(tree[1][0]["w"])) == 0.0   # functional update
        assert isinstance(out[1], tuple)                # structure kept

    def test_missing_path_raises(self):
        with pytest.raises(KeyError):
            get_path({"a": {"w": jnp.zeros(2)}}, ("a", "nope"))
        with pytest.raises(KeyError):
            set_path({"a": {"w": jnp.zeros(2)}}, ("b",), jnp.zeros(2))

    def test_shrink_on_dataclass_pytree(self):
        import dataclasses

        @dataclasses.dataclass
        class Block:
            w: object
            b: object

        jax.tree_util.register_dataclass(Block, data_fields=["w", "b"],
                                         meta_fields=[])
        params = [Block(w=jnp.zeros((3, 3, 4, 16)), b=jnp.zeros((16,))),
                  Block(w=jnp.zeros((3, 3, 16, 8)), b=jnp.zeros((8,)))]
        spec = PruneSpec(layers=(
            PrunableLayer("conv", (0, "w"), 3,
                          (CoupledParam((0, "b"), 0),
                           CoupledParam((1, "w"), 2))),
        ))
        kept = {"conv": np.asarray([0, 3, 7, 11])}
        out = shrink_params(params, spec, kept)
        assert out[0].w.shape == (3, 3, 4, 4)
        assert out[0].b.shape == (4,)
        assert out[1].w.shape == (3, 3, 4, 8)
        masks = param_masks(params, spec, kept)
        assert masks[0].w.shape == (1, 1, 1, 16)
        assert masks[1].w.shape == (1, 1, 16, 1)
        assert masks[1].b.shape == (1,)
        assert float(jnp.sum(masks[0].b)) == 4.0


class TestHRankScores:
    def test_conv_rank_orders_by_information(self):
        rng = np.random.default_rng(0)
        b, hw, d = 4, 8, 3
        rank1 = np.outer(rng.standard_normal(hw), rng.standard_normal(hw))
        full = rng.standard_normal((hw, hw))
        fmap = np.stack([np.zeros((hw, hw)), rank1, full], axis=-1)
        fmap = np.broadcast_to(fmap, (b, hw, hw, d))
        scores = feature_map_ranks(jnp.asarray(fmap))
        assert float(scores[0]) < float(scores[1]) < float(scores[2])

    def test_fc_energy(self):
        fmap = jnp.asarray([[0.0, 1.0, 2.0]] * 5)
        scores = feature_map_ranks(fmap)
        assert float(scores[0]) < float(scores[1]) < float(scores[2])
