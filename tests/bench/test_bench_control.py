"""A whole benchmark run at toy size on the CPU, past the look for a chip,
with the timed path broken underneath: ``correct`` must come out false
for each fault a cell can have, and true when nothing is broken.

The cells run on one chip, so the fault of an exchange between chips
left out does not apply.  The control itself (the reference in float8)
is read on the chip at the cells' own size by ``bench/control.py``; here
it runs at toy size.
"""
import time
import types

import numpy as np
import pytest

from bench import core

fl = core.driver("fl_rounds")
serve = core.driver("serve_open_loop")

TOY = dict(hidden_size=128, intermediate_size=256, num_attention_heads=2,
           num_key_value_heads=2, head_dim=64, num_hidden_layers=2,
           vocab_size=512)


RESNET_TOY = dict(stem_width=8, stage_widths=[8, 16, 32, 64],
                  image_shape=[8, 8, 3])


def _ctx(cell, traffic, seconds, config="olmo-1b-l4", toy=TOY):
    sizes, cfgmod = core.config(config)
    return types.SimpleNamespace(
        t0=time.perf_counter(), seed=2 ** 33 + 17, seconds=seconds,
        trace=False, trace_dir=None, sizes=dict(sizes, **toy),
        cfgmod=cfgmod, traffic=traffic,
        limits=core.load_json(core.BENCH / "limits" / f"{cell}.json"),
        peaks=core.peaks("TPU v5 lite"), compiles=core.CompileCounter(),
        memory_peak=lambda: 0)


def _correct(out):
    return all(v <= lim for v, lim in out["checks"].values()) \
        and out["compiles_in_window"] == 0


def _fl_ctx():
    t = core.traffic("fl-masked")
    t = dict(t, data=dict(t["data"], seq_len=32), chunk=2)
    ctx = _ctx("olmo-1b-l4.fl-masked", t, 1.0)
    # the CPU multiplies float32 at full precision where the TPU's default
    # is one bfloat16 pass, so here the reference follows the CPU
    ctx.cfgmod = types.SimpleNamespace(
        **{**vars(ctx.cfgmod), "REFERENCE_PRECISION": "f32"})
    return ctx


def _paper_ctx():
    t = core.traffic("fl-paper")
    t = dict(t, data=dict(t["data"], train=1000, test=100, device_pool=800),
             clients=10, clients_per_round=3, local_epochs=1,
             batch_size=10, server_batch_size=8, chunk=2)
    return _ctx("resnet18-gn.fl-paper", t, 1.0, "resnet18-gn", RESNET_TOY)


FL_CELLS = [(_fl_ctx, "repro.models.lm", "LM"),
            (_paper_ctx, "repro.models.cnn", "ResNet18")]
FL_IDS = ["olmo-fl-masked", "resnet-fl-paper"]


def _serve_ctx():
    t = core.traffic("serve-prompt-heavy")
    t = dict(t, engine=dict(slots=4, cache_len=128, max_prompt=64,
                            max_new_tokens=8, steps_per_wave=4, eos_id=-1),
             prompt=dict(median=16, sigma=0.8, min=4, max=64),
             rate_per_s=4.0)
    return _ctx("olmo-1b.serve-prompt-heavy", t, 2.0, "olmo-1b")


@pytest.mark.parametrize("ctx,module,model", FL_CELLS, ids=FL_IDS)
def test_training_run_is_correct(ctx, module, model):
    out = fl.run(ctx())
    assert _correct(out), out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0


@pytest.mark.parametrize("ctx,module,model", FL_CELLS, ids=FL_IDS)
def test_training_step_that_returns_its_state_unchanged(monkeypatch, ctx,
                                                        module, model):
    from repro.core.backend import LocalScanBackend

    def frozen(self, state, key, length):
        return state, key, {"health": np.zeros(length),
                            "tau_eff": np.zeros(length)}

    monkeypatch.setattr(LocalScanBackend, "run_chunk", frozen)
    assert not _correct(fl.run(ctx()))


@pytest.mark.parametrize("ctx,module,model", FL_CELLS, ids=FL_IDS)
def test_training_on_half_the_batch(monkeypatch, ctx, module, model):
    import importlib

    cls = getattr(importlib.import_module(module), model)
    whole = cls.loss_and_acc

    def half(self, params, x, y, *, masks=None):
        n = max(1, x.shape[0] // 2)
        return whole(self, params, x[:n], y[:n], masks=masks)

    monkeypatch.setattr(cls, "loss_and_acc", half)
    assert not _correct(fl.run(ctx()))


def test_serving_run_is_correct():
    out = serve.run(_serve_ctx())
    assert _correct(out), out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0


def test_serving_token_altered_where_produced(monkeypatch):
    from repro.serving import DecodeEngine

    step = DecodeEngine.step_wave

    def altered(self):
        done = step(self)
        for c in done:
            c.tokens[len(c.tokens) // 2] = (c.tokens[len(c.tokens) // 2]
                                            + 1) % self.model.cfg.vocab_size
        return done

    monkeypatch.setattr(DecodeEngine, "step_wave", altered)
    assert not _correct(serve.run(_serve_ctx()))


@pytest.mark.parametrize("cell,ctx", [("fl", _fl_ctx), ("fl", _paper_ctx),
                                      ("serve", _serve_ctx)],
                         ids=["olmo-fl-masked", "resnet-fl-paper",
                              "serve"])
def test_control_reads_above_the_program(cell, ctx):
    """The float8 control fails the cell's limit at toy size too."""
    c = ctx()
    drv = fl if cell == "fl" else serve
    out = drv.control(c.sizes, c.cfgmod, c.traffic, 5, c.seconds)
    if cell == "fl":
        assert any(out["control_fp8"][k] > c.limits[k] for k in c.limits)
    else:
        assert out["control_fp8"] > c.limits["served_logit_gap"]
        assert out["token_altered"] > c.limits["served_logit_gap"]
        assert out["program"] <= c.limits["served_logit_gap"]
