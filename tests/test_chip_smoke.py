"""chip_smoke.py at a tiny size on the CPU, so the script cannot rot
between chip runs.

The whole one-chip path (train with a FedAP mask prune, save, serve
masked and shrunk, compare logits) runs here with the Pallas kernels in
interpret mode, and then must stop at the kernel check: an interpreted
kernel is not a Mosaic kernel.  The script's ``main`` refuses the CPU.
"""
import dataclasses
import importlib.util
import pathlib

import numpy as np
import pytest

from repro.serving import ServeConfig

_PATH = pathlib.Path(__file__).resolve().parents[1] / "chip_smoke.py"


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", _PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def tiny(smoke):
    """OLMo-1B's family, norm, tying and bf16 params at toy widths."""
    return dataclasses.replace(smoke.olmo_config(2), d_model=128,
                               num_heads=2, num_kv_heads=2, head_dim=64,
                               d_ff=512, vocab_size=512)


def test_main_refuses_a_cpu(smoke, capsys):
    with pytest.raises(SystemExit) as exit_:
        smoke.main([])
    assert "'cpu'" in str(exit_.value.code)
    assert '"ok"' not in capsys.readouterr().out


def test_one_chip_path_runs_and_refuses_interpreted_kernels(smoke, capsys):
    cfg = tiny(smoke)
    scfg = ServeConfig(slots=2, cache_len=512, max_prompt=24,
                       max_new_tokens=4, steps_per_wave=4)
    with pytest.raises(smoke.SmokeFailure, match="no Mosaic kernel"):
        smoke.one_chip(cfg, seq_len=16, num_sequences=160, scfg=scfg,
                       prompt_lens=(8, 24), n_prompts=4)
    out = capsys.readouterr().out
    assert out.count("loss ") == smoke.ROUNDS
    assert "FedAP: p_star" in out
    assert out.count("4 completions {'ok': 4}") == 2      # masked, shrunk
    assert "masked vs shrunk decode_step logits" in out
    assert "tpu_custom_call count 0" in out


def test_compare_refuses_past_tolerance(smoke):
    want = np.array([1.0, -2.0])
    assert smoke.compare("x", want + 0.01, want, 0.01) == pytest.approx(0.01)
    with pytest.raises(smoke.SmokeFailure, match="exceeds"):
        smoke.compare("x", want + 0.1, want, 0.01)
