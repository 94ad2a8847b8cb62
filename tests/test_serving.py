"""Serving-path locks: continuous batching, pruned decode, checkpoints.

* **Mask == shrink at decode.**  Serving a FedAP mask-mode checkpoint
  through the block-skipping kernel (``decode_step(..., masks=)``) and
  serving its structural compaction (``shrink_ffn_at``) are the same
  model: per-step logits agree <= 1e-5; all-ones masks agree with the
  plain dense step to float32 accumulation and are bit-identical to
  themselves across steps.
* **Continuous batching is just batching.**  The ``DecodeEngine`` —
  ragged prompts, chunked prefill, slot reuse, on-device done-mask —
  emits token-for-token what a naive one-sequence-at-a-time greedy loop
  over ``decode_step`` emits.
* **Zero re-traces.**  A whole serving session compiles exactly the
  budgeted program count (``compile_budget.json`` ``serving/*`` rows)
  no matter how many requests are admitted and retired.
* **Checkpoints round-trip.**  ``RunResult.save`` -> ``load_artifact``
  -> ``load_servable`` reconstructs params, kept filters, masks and the
  ``ModelConfig``, for all three serve modes.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.compile_budget import expected_programs
from repro.configs.base import ModelConfig
from repro.core import pruning_lm
from repro.core.plan import RunResult, load_artifact
from repro.launch.mesh import make_host_mesh
from repro.models.lm import LM
from repro.serving import (
    DecodeEngine,
    ServeConfig,
    Servable,
    load_servable,
)

CFG = ModelConfig(name="dense-tiny", family="dense", rope="1d",
                  norm="rmsnorm", act="silu", param_dtype="float32",
                  remat="none", num_layers=2, d_model=128, num_heads=4,
                  num_kv_heads=2, d_ff=512, vocab_size=2048)


@pytest.fixture(scope="module")
def world():
    """(model, params, kept, fmasks, zeroed, shrunk_model, shrunk) — the
    dense model, a 0.5-rate FedAP keep decision, its mask-mode params
    (pruned coordinates zeroed) and its structural compaction."""
    model = LM(CFG)
    params = model.init(jax.random.key(0))
    kept = model.decide_kept(params, 0.5)        # 128-lane-aligned
    fmasks = model.filter_masks(params, kept)
    zeroed = jax.tree.map(jnp.multiply, params, model.param_masks(params, kept))
    d_kept = int(np.asarray(kept["mlp"]).shape[-1])
    shrunk_model = LM(dataclasses.replace(CFG, d_ff=d_kept))
    shrunk = pruning_lm.shrink_ffn_at(params, kept["mlp"])
    return model, params, kept, fmasks, zeroed, shrunk_model, shrunk


def ragged_prompts(n, max_prompt, vocab, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, size=int(rng.integers(1, max_prompt + 1)))
            .astype(np.int32) for _ in range(n)]


def naive_greedy(model, params, prompt, max_new, cache_len, masks=None):
    """One sequence at a time through the scalar-index decode_step —
    chunked prefill (one prompt token per step), then argmax decoding.
    The oracle the continuous-batching engine must match exactly."""
    cache = model.init_cache(1, cache_len)
    step = jax.jit(lambda p, c, t: model.decode_step(
        p, c, {"tokens": t}, masks=masks))
    tok = jnp.asarray([[prompt[0]]], jnp.int32)
    out, consumed = [], 0
    while len(out) < max_new:
        logits, cache = step(params, cache, tok)
        nxt = int(jnp.argmax(logits[0, 0]))
        consumed += 1
        if consumed < len(prompt):
            tok = jnp.asarray([[prompt[consumed]]], jnp.int32)
        else:
            out.append(nxt)
            tok = jnp.asarray([[nxt]], jnp.int32)
    return np.asarray(out, np.int32)


# ---------------------------------------------------------------------------
# Mask == shrink at decode
# ---------------------------------------------------------------------------

class TestPrunedDecodeParity:
    def test_masked_step_equals_shrunk_step(self, world):
        """Logits of the masked decode path (dense shapes, block-skipping
        kernel) equal the compacted model's <= 1e-5 at every step."""
        model, _, _, fmasks, zeroed, s_model, shrunk = world
        b, cache_len = 2, 8
        cm = model.init_cache(b, cache_len)
        cs = s_model.init_cache(b, cache_len)
        rng = np.random.default_rng(1)
        for _ in range(4):
            tok = jnp.asarray(rng.integers(0, CFG.vocab_size, (b, 1)),
                              jnp.int32)
            lm_, cm = model.decode_step(zeroed, cm, {"tokens": tok},
                                        masks=fmasks)
            ls_, cs = s_model.decode_step(shrunk, cs, {"tokens": tok})
            np.testing.assert_allclose(np.asarray(lm_), np.asarray(ls_),
                                       atol=1e-5, rtol=1e-5)

    def test_all_ones_masks_bit_exact(self, world):
        """masks of all-ones must not perturb the dense step: the kernel
        path agrees with it within float32 accumulation over the longest
        contraction (d_ff), and is bit-identical to itself, step after
        step (bit equality with XLA's dot would depend on the host's dot
        codegen)."""
        model, params, _, _, _, _, _ = world
        ones = {"mlp": jnp.ones((CFG.num_layers, CFG.d_ff), jnp.float32)}
        b, cache_len = 2, 8
        ca = model.init_cache(b, cache_len)
        cb = model.init_cache(b, cache_len)
        cc = model.init_cache(b, cache_len)
        rng = np.random.default_rng(2)
        for _ in range(3):
            tok = jnp.asarray(rng.integers(0, CFG.vocab_size, (b, 1)),
                              jnp.int32)
            la, ca = model.decode_step(params, ca, {"tokens": tok})
            lb, cb = model.decode_step(params, cb, {"tokens": tok},
                                       masks=ones)
            lc, cc = model.decode_step(params, cc, {"tokens": tok},
                                       masks=ones)
            assert np.array_equal(np.asarray(lb), np.asarray(lc))
            tol = (CFG.d_ff * np.finfo(np.float32).eps
                   * float(np.abs(np.asarray(la)).max()))
            np.testing.assert_allclose(np.asarray(lb), np.asarray(la),
                                       rtol=0, atol=tol)

    def test_masked_engine_equals_shrunk_engine(self, world):
        """End-to-end: the two pruned serve modes emit identical tokens."""
        model, _, _, fmasks, zeroed, s_model, shrunk = world
        scfg = ServeConfig(slots=2, cache_len=8, max_prompt=4,
                           max_new_tokens=4, steps_per_wave=3)
        prompts = ragged_prompts(5, 4, CFG.vocab_size, seed=3)
        got_m = DecodeEngine(model, zeroed, scfg, masks=fmasks).run(prompts)
        got_s = DecodeEngine(s_model, shrunk, scfg).run(prompts)
        assert [c.uid for c in got_m] == [c.uid for c in got_s]
        for a, b in zip(got_m, got_s):
            assert np.array_equal(a.tokens, b.tokens)


# ---------------------------------------------------------------------------
# Continuous batching == naive decoding
# ---------------------------------------------------------------------------

class TestEngineSemantics:
    def test_engine_matches_naive_greedy(self, world):
        """Ragged prompts + slot reuse through 2 slots: every completion
        equals the one-sequence naive loop, token for token."""
        model, params, _, _, _, _, _ = world
        scfg = ServeConfig(slots=2, cache_len=8, max_prompt=4,
                           max_new_tokens=4, steps_per_wave=3)
        prompts = ragged_prompts(5, scfg.max_prompt, CFG.vocab_size, seed=4)
        eng = DecodeEngine(model, params, scfg)
        done = eng.run(prompts)
        assert [c.uid for c in done] == list(range(len(prompts)))
        for comp in done:
            want = naive_greedy(model, params, comp.prompt,
                                scfg.max_new_tokens, scfg.cache_len)
            np.testing.assert_array_equal(comp.tokens, want)

    def test_eos_stops_early(self, world):
        """An eos_id in-vocabulary retires a slot before max_new_tokens;
        the engine still drains and uids stay stable."""
        model, params, _, _, _, _, _ = world
        # pick the token the model emits first for prompt [7] as the eos
        first = int(naive_greedy(model, params, np.asarray([7]), 1, 8)[0])
        scfg = ServeConfig(slots=2, cache_len=8, max_prompt=4,
                           max_new_tokens=4, eos_id=first, steps_per_wave=2)
        eng = DecodeEngine(model, params, scfg)
        done = eng.run([np.asarray([7], np.int32),
                        np.asarray([11, 3], np.int32)])
        assert len(done) == 2
        got = done[0].tokens
        assert got[-1] == first and len(got) <= scfg.max_new_tokens

    def test_interleaved_submission(self, world):
        """submit() between waves — the admission path mid-session —
        completes everything with the same per-request tokens."""
        model, params, _, _, _, _, _ = world
        scfg = ServeConfig(slots=2, cache_len=8, max_prompt=4,
                           max_new_tokens=4, steps_per_wave=2)
        prompts = ragged_prompts(4, 4, CFG.vocab_size, seed=5)
        eng = DecodeEngine(model, params, scfg)
        eng.submit(prompts[0])
        done = []
        done.extend(eng.step_wave())
        for p in prompts[1:]:
            eng.submit(p)
        while eng.pending:
            done.extend(eng.step_wave())
        assert sorted(c.uid for c in done) == list(range(len(prompts)))
        by_uid = {c.uid: c for c in done}
        for uid, p in enumerate(prompts):
            want = naive_greedy(model, params, p, scfg.max_new_tokens,
                                scfg.cache_len)
            np.testing.assert_array_equal(by_uid[uid].tokens, want)

    def test_mesh_engine_matches_local(self, world):
        """Slot axis sharded over the host mesh (1-way under tier-1,
        8-way under the CI mesh job) == the mesh-less engine."""
        model, params, _, _, _, _, _ = world
        mesh = make_host_mesh(model=1)
        n = mesh.shape["data"]
        slots = 2 * n
        scfg = ServeConfig(slots=slots, cache_len=8, max_prompt=4,
                           max_new_tokens=4, steps_per_wave=3)
        prompts = ragged_prompts(2 * slots + 1, 4, CFG.vocab_size, seed=6)
        local = DecodeEngine(model, params, scfg).run(prompts)
        sharded = DecodeEngine(model, params, scfg, mesh=mesh).run(prompts)
        assert [c.uid for c in local] == [c.uid for c in sharded]
        for a, b in zip(local, sharded):
            assert np.array_equal(a.tokens, b.tokens)

    def test_config_validation(self, world):
        model, params, _, _, _, _, _ = world
        with pytest.raises(ValueError, match="cache_len"):
            ServeConfig(slots=2, cache_len=6, max_prompt=4, max_new_tokens=4)
        eng = DecodeEngine(model, params,
                           ServeConfig(slots=1, cache_len=8, max_prompt=4,
                                       max_new_tokens=4))
        with pytest.raises(ValueError, match="prompt length"):
            eng.submit(np.arange(5, dtype=np.int32))

    def test_unservable_family_rejected(self):
        """The engine's per-slot index semantics need the scanned KV
        stack — a recurrent-state model must be refused, not silently
        mis-served."""
        from repro.configs import get_config
        from repro.models.api import build_model

        cfg = get_config("xlstm-125m").reduced()
        model = build_model(cfg)
        params = model.init(jax.random.key(0))
        with pytest.raises(ValueError, match="scanned-KV"):
            DecodeEngine(model, params, ServeConfig(
                slots=2, cache_len=8, max_prompt=4, max_new_tokens=4))


# ---------------------------------------------------------------------------
# Zero re-traces (the serving compile-budget contract, asserted in-process)
# ---------------------------------------------------------------------------

class TestServingCompileBudget:
    def test_steady_state_no_retrace(self, world):
        """Admissions, retirements and slot reuse never re-trace: the
        session-wide program count equals the compile_budget.json
        serving row after EVERY wave."""
        model, params, _, _, _, _, _ = world
        want = expected_programs("serving/decode_dense")
        scfg = ServeConfig(slots=2, cache_len=8, max_prompt=4,
                           max_new_tokens=4, steps_per_wave=2)
        eng = DecodeEngine(model, params, scfg)
        for p in ragged_prompts(6, 4, CFG.vocab_size, seed=7):
            eng.submit(p)
        waves = 0
        while eng.pending:
            eng.step_wave()
            waves += 1
            assert sum(eng.program_counts().values()) == want, \
                f"re-trace at wave {waves}: {eng.program_counts()}"
        assert waves >= 3            # slot reuse actually happened
        assert eng.program_counts() == {"admit": 1, "wave": 1}

    def test_budget_rows_agree_across_modes(self):
        for mode in ("dense", "masked", "shrunk"):
            assert expected_programs(f"serving/decode_{mode}") == 2


# ---------------------------------------------------------------------------
# Checkpoint round-trip + load_servable
# ---------------------------------------------------------------------------

def masked_run_result(params, kept, fmasks):
    return RunResult(
        params=params,
        history={"round": [2], "acc": [0.5], "loss": [1.2],
                 "tau_eff": [1.0], "time": [0.1]},
        artifacts={"prune": {"mode": "mask", "p_star": 0.5,
                             "layer_rates": [0.5, 0.5], "kept": dict(kept),
                             "filter_masks": dict(fmasks)}},
        state={})


class TestCheckpoint:
    def test_save_load_roundtrip(self, tmp_path, world):
        model, _, kept, fmasks, zeroed, _, _ = world
        masked_run_result(zeroed, kept, fmasks).save(
            tmp_path / "ckpt", model_config=CFG)
        art = load_artifact(tmp_path / "ckpt")
        assert art["mode"] == "mask"
        assert art["model_config"] == CFG
        assert art["history"]["acc"] == [0.5]
        assert art["meta"]["prune"]["kept_counts"] == {
            "mlp": int(np.asarray(kept["mlp"]).shape[-1])}
        np.testing.assert_array_equal(art["kept"]["mlp"],
                                      np.asarray(kept["mlp"]))
        np.testing.assert_array_equal(art["filter_masks"]["mlp"],
                                      np.asarray(fmasks["mlp"]))
        got = jax.tree.leaves(art["params"])
        want = jax.tree.leaves(zeroed)
        assert len(got) == len(want)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(g, np.asarray(w))

    def test_dense_run_saves_without_prune(self, tmp_path, world):
        model, params, _, _, _, _, _ = world
        RunResult(params=params, history={}, artifacts={}, state={}).save(
            tmp_path / "ckpt", model_config=CFG)
        art = load_artifact(tmp_path / "ckpt")
        assert art["kept"] is None and art["mode"] is None
        sv = load_servable(tmp_path / "ckpt")
        assert sv.mode == "dense" and sv.masks is None

    def test_format_guard(self, tmp_path):
        (tmp_path / "ckpt").mkdir()
        (tmp_path / "ckpt" / "meta.json").write_text('{"format": "other"}')
        with pytest.raises(ValueError, match="not a repro checkpoint"):
            load_artifact(tmp_path / "ckpt")

    def test_servable_modes_agree(self, tmp_path, world):
        """auto (-> masked for a mask-mode run), masked and shrunk loads
        of the SAME checkpoint produce token-identical engines; shrunk
        actually compacts d_ff."""
        model, _, kept, fmasks, zeroed, _, _ = world
        masked_run_result(zeroed, kept, fmasks).save(
            tmp_path / "ckpt", model_config=CFG)
        d_kept = int(np.asarray(kept["mlp"]).shape[-1])

        servables = {m: load_servable(tmp_path / "ckpt", m)
                     for m in ("auto", "masked", "shrunk", "dense")}
        assert servables["auto"].mode == "masked"
        assert servables["shrunk"].model.cfg.d_ff == d_kept
        assert servables["masked"].model.cfg.d_ff == CFG.d_ff

        scfg = ServeConfig(slots=2, cache_len=8, max_prompt=4,
                           max_new_tokens=4, steps_per_wave=3)
        prompts = ragged_prompts(3, 4, CFG.vocab_size, seed=8)
        runs = {}
        for m, sv in servables.items():
            assert isinstance(sv, Servable)
            runs[m] = DecodeEngine(sv.model, sv.params, scfg,
                                   masks=sv.masks).run(prompts)
        for m in ("masked", "shrunk", "dense"):
            for a, b in zip(runs["auto"], runs[m]):
                assert np.array_equal(a.tokens, b.tokens), m

    def test_shrunk_checkpoint_loads_shrunk(self, tmp_path, world):
        """A shrink-mode run's params are already compacted: the recorded
        (pre-shrink) config's d_ff is overridden by the param shapes and
        re-shrinking is a no-op."""
        model, _, kept, _, _, s_model, shrunk = world
        res = RunResult(
            params=shrunk,
            history={},
            artifacts={"prune": {"mode": "shrink", "p_star": 0.5,
                                 "layer_rates": [0.5, 0.5],
                                 "kept": dict(kept)}},
            state={})
        res.save(tmp_path / "ckpt", model_config=CFG)   # dense-time cfg
        sv = load_servable(tmp_path / "ckpt")
        assert sv.mode == "shrunk"
        assert sv.model.cfg.d_ff == int(np.asarray(kept["mlp"]).shape[-1])
        prompts = ragged_prompts(2, 4, CFG.vocab_size, seed=9)
        scfg = ServeConfig(slots=2, cache_len=8, max_prompt=4,
                           max_new_tokens=4)
        got = DecodeEngine(sv.model, sv.params, scfg).run(prompts)
        want = DecodeEngine(s_model, shrunk, scfg).run(prompts)
        for a, b in zip(got, want):
            assert np.array_equal(a.tokens, b.tokens)

    def test_missing_config_is_loud(self, tmp_path, world):
        model, params, _, _, _, _, _ = world
        RunResult(params=params, history={}, artifacts={}, state={}).save(
            tmp_path / "ckpt")                          # no model_config
        with pytest.raises(ValueError, match="model_config"):
            load_servable(tmp_path / "ckpt")

    def test_in_memory_run_result_source(self, world):
        """load_servable accepts the RunResult itself (no disk trip)."""
        model, _, kept, fmasks, zeroed, _, _ = world
        res = masked_run_result(zeroed, kept, fmasks)
        sv = load_servable(res, "auto", model_config=CFG)
        assert sv.mode == "masked" and sv.masks is not None

    def test_bf16_params_and_param_free_layers_round_trip(self, tmp_path):
        """OLMo's shape of checkpoint: bfloat16 params (npz alone returns
        them as raw 2-byte voids) and a non-parametric norm (an empty
        subtree npz alone drops) both come back as saved."""
        cfg = dataclasses.replace(CFG, norm="nonparam",
                                  param_dtype="bfloat16")
        model = LM(cfg)
        params = model.init(jax.random.key(4))
        assert params["layers"]["norm_a"] == {}
        RunResult(params=params, history={}, artifacts={}, state={}).save(
            tmp_path / "ckpt", model_config=cfg)
        art = load_artifact(tmp_path / "ckpt")
        assert (jax.tree.structure(art["params"])
                == jax.tree.structure(params))
        for g, w in zip(jax.tree.leaves(art["params"]),
                        jax.tree.leaves(params)):
            assert g.dtype == jnp.bfloat16
            np.testing.assert_array_equal(g, np.asarray(w))
        sv = load_servable(tmp_path / "ckpt")
        logits, _ = sv.model.decode_step(
            sv.params, sv.model.init_cache(1, 8),
            {"tokens": jnp.zeros((1, 1), jnp.int32)})
        assert logits.dtype == jnp.bfloat16


class TestNextLogits:
    def test_next_logits_is_the_next_step_and_does_not_advance(self, world):
        """``next_logits`` mid-prefill equals the naive decode_step's
        logits after the same prompt prefix, and leaves the state (and
        hence the completions) untouched."""
        model, params, _, _, _, _, _ = world
        scfg = ServeConfig(slots=2, cache_len=8, max_prompt=6,
                           max_new_tokens=2, steps_per_wave=3)
        prompts = [np.arange(1, 7, dtype=np.int32),
                   np.arange(20, 26, dtype=np.int32)]
        eng = DecodeEngine(model, params, scfg)
        for p in prompts:
            eng.submit(p)
        eng.step_wave()                  # 3 of 6 prompt tokens consumed
        got = eng.next_logits()
        np.testing.assert_array_equal(got, eng.next_logits())
        cache = model.init_cache(2, scfg.cache_len)
        step = jax.jit(lambda c, t: model.decode_step(params, c,
                                                      {"tokens": t}))
        for t in range(4):               # 3 consumed + the next one
            want, cache = step(cache, jnp.asarray(
                [[prompts[0][t]], [prompts[1][t]]], jnp.int32))
        np.testing.assert_allclose(got, np.asarray(want[:, 0]), atol=1e-5)
        done = eng.run()
        ref = DecodeEngine(model, params, scfg).run(prompts)
        for a, b in zip(done, ref):
            np.testing.assert_array_equal(a.tokens, b.tokens)
