"""The benchmark's operation and byte counts against hand counts at small
shapes, its metric readers on synthetic traces, and its configuration at
the published widths."""
import numpy as np
import pytest

from bench import core, lm_math, trace

DM = {"L": 1, "d": 4, "h": 2, "kv": 1, "hd": 2, "ff": 8, "V": 10}
PEAKS = {"flops_bf16": 100.0, "hbm_bytes_per_s": 10.0}


def test_token_flops_by_hand():
    # q,k,v 2*4*(2+1+1)*2 = 64, out 2*2*2*4 = 32, attention 4*2*2*3 = 48,
    # swiglu over 4 kept units 3*2*4*4 = 96, head 2*4*10 = 80
    assert lm_math.token_flops(DM, 3, 4) == 64 + 32 + 48 + 96 + 80


def test_sequence_flops_is_causal():
    seq = lm_math.sequence_flops(DM, 3, 4)
    assert seq == sum(lm_math.token_flops(DM, i + 1, 4) for i in range(3))
    assert seq == 912


def test_train_round_and_eval_flops():
    hp = {"clients_per_round": 2, "local_steps": 3, "batch_size": 2,
          "tau": 1, "server_batch_size": 2, "row_shape": (3,),
          "test_rows": 5}
    assert lm_math.train_round_flops(DM, hp, 4) == 3 * (2 * 3 * 2 + 2) * 912
    assert lm_math.eval_flops(DM, hp, 4) == 5 * 912


def test_decode_attention_work_by_hand():
    flops, byts = lm_math.decode_attention_work(DM, [3, 5])
    assert flops == 4 * 2 * 2 * 3 + 4 * 2 * 2 * 5
    # per slot: q and output 2*h*hd, K and V rows 2*ctx*kv*hd; 2 bytes each
    assert byts == 2 * ((8 + 12) + (8 + 20))


def _reduced(ops, spans=None, window=(0, 10 ** 9)):
    rec = {"device": ops, "host": spans or []}
    return trace.reduce(rec, window=window)


def test_masked_matmul_roofline_by_hand():
    read = core.metric_reader("masked_matmul_roofline")
    mod = __import__("sys").modules["bench_metric_masked_matmul_roofline"]
    hp = {"clients_per_round": 2, "local_steps": 1, "tau": 1,
          "row_shape": (2,), "batch_size": 1, "server_batch_size": 1,
          "test_rows": 1}
    # one round, one eval: grads batched over clients -> (1 + 1) * L * 2
    ops = ([["masked_matmul_fwd", i * 10, 5, ""] for i in range(6)]
           + [["masked_matmul_dx", 100 + i * 10, 5, ""] for i in range(4)]
           + [["masked_matmul_dw", 200 + i * 10, 5, ""] for i in range(4)])
    layer = {"reduced": _reduced(ops), "dims": DM, "hp": hp,
             "kept": {"mlp": np.zeros((1, 4), np.int32)},
             "rounds": 1, "evals": 1, "peaks": PEAKS}
    rows = 2
    fwd = max(2 * rows * 4 * 4 / 100, 2 * (rows * 4 + 4 * 4 + rows * 4) / 10)
    assert mod._pass(rows, 4, 4, PEAKS, False) == 2 * fwd
    least = (2 * mod._pass(rows, 4, 4, PEAKS, True)
             + mod._pass(rows, 4, 4, PEAKS, True)
             + mod._pass(rows, 4, 4, PEAKS, False))
    assert read(layer) == pytest.approx(100 * least / (14 * 5e-9))
    # a trace with another number of gradient kernels is not read
    layer["reduced"] = _reduced(ops[:-1])
    assert read(layer) is None


def test_decode_attention_roofline_and_mfu_by_hand():
    roof = core.metric_reader("decode_attention_roofline")
    mfu = core.metric_reader("decode_mfu")
    contexts = [[[3, 5], []]]            # one wave of 2 steps, L = 1
    ops = [["decode_attention", 10, 4, ""], ["decode_attention", 30, 4, ""]]
    spans = [["wave", 0, 100]]
    layer = {"reduced": _reduced(ops, spans), "dims": DM, "peaks": PEAKS,
             "ff_kept": 4, "contexts": contexts}
    f, b = lm_math.decode_attention_work(DM, [3, 5])
    assert roof(layer) == pytest.approx(
        100 * max(f / 100, b / 10) / 8e-9)
    flops = lm_math.token_flops(DM, 3, 4) + lm_math.token_flops(DM, 5, 4)
    assert mfu(layer) == pytest.approx(100 * flops / (100e-9 * 100))
    layer["reduced"] = _reduced(ops[:1], spans)
    assert roof(layer) is None


def test_train_mfu_and_idle_by_hand():
    mfu = core.metric_reader("train_mfu")
    idle = core.metric_reader("device_idle.train")
    hp = {"clients_per_round": 1, "local_steps": 1, "batch_size": 1,
          "tau": 1, "server_batch_size": 1, "row_shape": (3,),
          "test_rows": 1}
    red = _reduced([["fusion", 0, 250, ""], ["fusion", 100, 400, ""]],
                   window=(0, 1000))
    layer = {"reduced": red, "dims": DM, "hp": hp, "peaks": PEAKS,
             "rounds": 2, "evals": 1,
             "flops": {"round": lm_math.train_round_flops(DM, hp, 4),
                       "eval": lm_math.eval_flops(DM, hp, 4)}}
    flops = 2 * 3 * 2 * 912 + 912
    assert mfu(layer) == pytest.approx(100 * flops / (1e-6 * 100))
    assert idle(layer) == pytest.approx(50.0)


def test_wave_host_time_by_hand():
    read = core.metric_reader("wave_host_ms")
    red = _reduced([["w", 10, 50, ""], ["w", 120, 40, ""]],
                   [["wave", 0, 100], ["wave", 100, 100]], window=(0, 200))
    layer = {"reduced": red, "contexts": [[]]}
    assert read(layer) == pytest.approx(((100 - 50) + (100 - 40)) / 2 * 1e-6)


def test_olmo_config_keeps_the_published_widths():
    sizes, mod = core.config("olmo-1b-l4")
    published = {"hidden_size": 2048, "intermediate_size": 8192,
                 "num_attention_heads": 16, "num_key_value_heads": 16,
                 "head_dim": 128, "vocab_size": 50304,
                 "max_position_embeddings": 2048,
                 "tie_word_embeddings": True}
    assert {k: sizes[k] for k in published} == published
    assert sizes["reduced"] == ["num_hidden_layers"]
    assert sizes["published"] == {"num_hidden_layers": 16}
    cfg = mod.program_config(sizes)
    from repro.configs import get_config

    olmo = get_config("olmo-1b")
    assert (cfg.d_model, cfg.d_ff, cfg.num_heads, cfg.vocab_size) == \
        (olmo.d_model, olmo.d_ff, olmo.num_heads, olmo.vocab_size)
    assert cfg.num_layers == 4 and olmo.num_layers == 16
    kept = mod.kept_units(sizes, 0.5, 128, np.random.default_rng(0))
    assert kept["mlp"].shape == (4, sizes["fedap"]["kept_units"])


def test_served_olmo_config_is_whole():
    """The serving configuration is OLMo-1B as published, all 16 layers,
    with the same FedAP decision as the training one."""
    sizes, mod = core.config("olmo-1b")
    train, _ = core.config("olmo-1b-l4")
    assert sizes["reduced"] == [] and sizes["num_hidden_layers"] == 16
    same = {k: v for k, v in train.items()
            if k not in ("name", "num_hidden_layers", "reduced", "published",
                         "assumed", "deployment", "param_dtype", "precision")}
    assert {k: sizes[k] for k in same} == same
    assert mod.program_config(sizes).num_layers == 16
    kept = mod.kept_units(sizes, sizes["fedap"]["rate"],
                          sizes["fedap"]["align"], np.random.default_rng(0))
    assert kept["mlp"].shape == (16, sizes["fedap"]["kept_units"])


def test_reference_matches_the_program_forward_at_toy_size():
    """The plain reference and the program's LM agree on seeded weights
    (float32, toy widths): the reference computes the same model."""
    import jax
    import jax.numpy as jnp

    sizes, mod = core.config("olmo-1b-l4")
    sizes = dict(sizes, hidden_size=64, intermediate_size=256,
                 num_attention_heads=2, num_key_value_heads=2, head_dim=32,
                 num_hidden_layers=2, vocab_size=96)
    params = jax.tree.map(lambda a: a.astype(jnp.float32),
                          mod.init_params(sizes, jax.random.key(3)))
    kept = mod.kept_units(sizes, 0.5, 128, np.random.default_rng(1))
    fmask = jnp.asarray(mod.filter_rows(sizes, kept))
    tokens = jax.random.randint(jax.random.key(4), (2, 16), 0, 96)
    import dataclasses

    from repro.models.lm import LM

    model = LM(dataclasses.replace(mod.program_config(sizes),
                                   param_dtype="float32"))
    with jax.default_matmul_precision("highest"):
        got, _ = model.apply(params, {"tokens": tokens},
                             masks={"mlp": fmask})
    want = mod.logits(params, tokens, fmask)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_resnet_count_is_the_programs():
    """The benchmark's copy of the ResNet-18 operation count equals the
    program's ``flops_per_example`` at the published widths, and the
    round and eval counts follow from it."""
    import jax

    sizes, mod = core.config("resnet18-gn")
    model = mod.train_model(sizes)
    shapes = jax.eval_shape(model.init, jax.random.key(0))
    per = mod.image_flops(sizes)
    assert per == model.flops_per_example(shapes)
    hp = {"clients_per_round": 10, "local_steps": 200, "batch_size": 10,
          "tau": 62, "server_batch_size": 32, "test_rows": 10000}
    f = mod.train_flops(sizes, hp, None)
    assert f["round"] == 3 * (10 * 200 * 10 + 62 * 32) * per
    assert f["eval"] == 10000 * per


def test_resnet_config_keeps_the_published_widths():
    import jax

    sizes, mod = core.config("resnet18-gn")
    assert sizes["stage_widths"] == [64, 128, 256, 512]
    assert sizes["blocks_per_stage"] == [2, 2, 2, 2]
    assert (sizes["stem_width"], sizes["num_classes"]) == (64, 10)
    assert sizes["image_shape"] == [32, 32, 3] and sizes["reduced"] == []
    model = mod.train_model(sizes)
    want = jax.eval_shape(model.init, jax.random.key(0))
    got = jax.eval_shape(lambda k: mod.init_params(sizes, k),
                         jax.random.key(0))
    assert jax.tree.structure(got) == jax.tree.structure(want)
    assert [(a.shape, a.dtype) for a in jax.tree.leaves(got)] == \
        [(a.shape, a.dtype) for a in jax.tree.leaves(want)]


def test_resnet_reference_matches_the_program_forward():
    """The plain ResNet reference and the program's ResNet18 agree on
    seeded weights at the published widths (a few small images)."""
    import jax
    import jax.numpy as jnp

    sizes, mod = core.config("resnet18-gn")
    model = mod.train_model(sizes)
    params = mod.init_params(sizes, jax.random.key(5))
    x = jax.random.normal(jax.random.key(6), (2, 32, 32, 3), jnp.float32)
    with jax.default_matmul_precision("highest"):
        got = model.apply(params, x)
    want = mod.logits(params, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
