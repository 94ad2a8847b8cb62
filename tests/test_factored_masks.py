"""FedAP's keep-masks ride in the round state FACTORED: each leaf of
``state["masks"]`` keeps only the axes along which FedAP can zero its
parameter (size 1 on every other axis), and the engine's ``x * m``
broadcasts.

Locked here:

* **Equality.**  Per round, training with factored masks is
  ``array_equal`` to training with parameter-shaped masks (the form the
  slot had before) — params, server momentum and the round metrics — on
  the LM in kernel and params mode and the CNN in params mode, on the
  local and the mesh backend, across a ``Prune(mode="mask")`` and a later
  momentum-preserving shrink (``reuse="prune"``'s apply).  The slot's leaf
  shapes are asserted at init and after each event.
* **Old checkpoints.**  A checkpoint whose masks are parameter-shaped
  resumes bit-identically into the factored slot; a mask that varies
  along an axis FedAP never prunes is refused, naming the leaf.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.core import (
    Eval,
    FedAPConfig,
    FederatedTrainer,
    Prune,
    Scan,
    TrainPlan,
    engine,
    feddumap_config,
)
from repro.core.backend import (
    LocalScanBackend,
    MeshBackend,
    param_masks_for,
)
from repro.core.plan import CheckpointError
from repro.core.pruning import factor_masks, prunable_axes
from repro.data import build_federated_data
from repro.data.pipeline import build_lm_federated_data
from repro.data.synthetic import SyntheticSpec, TokenSpec
from repro.models import SimpleCNN
from repro.models.lm import LM
from repro.reliability import KillAfterChunk, SimulatedCrash

TINY_LM = dict(name="dense-tiny", family="dense", rope="1d", norm="rmsnorm",
               act="silu", param_dtype="float32", remat="none",
               num_layers=2, d_model=128, num_heads=4, num_kv_heads=2,
               d_ff=512, vocab_size=2048)
CNN_CFG = dict(num_clients=6, clients_per_round=3, local_epochs=1,
               batch_size=10, lr=0.05)
LM_CFG = dict(num_clients=8, clients_per_round=4, local_epochs=1,
              batch_size=4, server_batch_size=8, lr=3e-3, lr_decay=1.0)


class FullMasks:
    """``model`` with parameter-shaped keep-masks: the form the round
    state's mask slot had before it was factored, kept as the reference
    of the equality tests."""

    def __init__(self, model):
        self._model = model

    def __getattr__(self, name):
        return getattr(self._model, name)

    def param_masks(self, params, kept):
        return jax.tree.map(lambda m, p: jnp.broadcast_to(m, p.shape),
                            param_masks_for(self._model, params, kept),
                            params)


def _cnn_data_model():
    spec = SyntheticSpec(num_classes=10, image_shape=(8, 8, 3),
                         train_size=1600, test_size=100, noise_scale=0.5)
    data = build_federated_data(num_clients=6, server_fraction=0.1,
                                device_pool=600, spec=spec)
    model = SimpleCNN(num_classes=10, image_shape=(8, 8, 3),
                      channels=(4, 8, 8), fc_width=16)
    return data, model


@pytest.fixture(scope="module")
def cnn_world():
    return _cnn_data_model()


@pytest.fixture(scope="module")
def lm_data():
    return build_lm_federated_data(
        num_clients=8, spec=TokenSpec(vocab_size=2048, num_topics=16,
                                      seq_len=17, num_sequences=256))


def _cnn_kept(model, params):
    rng = np.random.default_rng(0)
    kept = {}
    for layer in model.prune_spec(params).layers:
        w = params[layer.weight[0]][layer.weight[1]]
        d = w.shape[layer.filter_axis]
        kept[layer.name] = np.sort(rng.choice(d, size=d // 2, replace=False))
    return kept


def _factored_shapes(model, params):
    """What the slot's leaves must be: each parameter's shape with every
    axis FedAP never prunes held at 1."""
    if isinstance(model, LM):
        def one(path, p):
            name = jax.tree_util.keystr(path)
            if name.endswith("['mlp']['wi']") or name.endswith("['mlp']['wg']"):
                return (p.shape[0], 1, p.shape[2])
            if name.endswith("['mlp']['wo']"):
                return (p.shape[0], p.shape[1], 1)
            return (1,) * p.ndim
        return jax.tree_util.tree_map_with_path(one, params)
    axes = prunable_axes(params, model.prune_spec(params))

    def one(path, p):
        key = tuple(k.key for k in path)
        return tuple(d if i in axes.get(key, ()) else 1
                     for i, d in enumerate(p.shape))
    return jax.tree_util.tree_map_with_path(one, params)


def _shapes(tree):
    return jax.tree.map(lambda x: tuple(x.shape), tree)


def _assert_round_equal(sf, so, mf, mo, where):
    for k in ("params", "server_m"):
        for a, b in zip(jax.tree.leaves(sf[k]), jax.tree.leaves(so[k])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=f"{where}: {k}")
    for k in mf:
        np.testing.assert_array_equal(np.asarray(mf[k]), np.asarray(mo[k]),
                                      err_msg=f"{where}: {k}")
    for m, full in zip(jax.tree.leaves(sf["masks"]),
                       jax.tree.leaves(so["masks"])):
        np.testing.assert_array_equal(
            np.broadcast_to(np.asarray(m), full.shape), np.asarray(full),
            err_msg=f"{where}: masks")


WORLDS = {
    "cnn-params": ("cnn", "params"),
    "lm-params": ("lm", "params"),
    "lm-kernel": ("lm", "kernel"),
}


# XLA's default CPU fusion emitters compile an update fusion differently
# when a mask operand is broadcast, and the CNN's updates then differ from
# the full-mask run by one float32 ulp in a few elements (the LM's do not).
# The arithmetic is the same: with the CPU's earlier emitters both runs are
# bit-identical, so the CNN's comparison runs there, in a child process
# (XLA's flags are read once, when a process starts its CPU backend).
_CPU_EMITTER_FLAG = "--xla_cpu_use_fusion_emitters=false"


def _in_child_with_legacy_cpu_emitters(world: str, backend: str) -> None:
    import os
    import pathlib
    import subprocess
    import sys

    tests = pathlib.Path(__file__).resolve().parent
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"{os.environ.get('XLA_FLAGS', '')} "
                         f"{_CPU_EMITTER_FLAG}".strip(),
               PYTHONPATH=os.pathsep.join(
                   [str(tests.parent / "src"), str(tests),
                    os.environ.get("PYTHONPATH", "")]))
    code = ("import test_factored_masks as t\n"
            f"t.run_equality({world!r}, {backend!r}, *t._cnn_data_model())\n")
    done = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=900)
    assert done.returncode == 0, done.stderr[-4000:]


@pytest.mark.parametrize("backend", ["local", "mesh"])
@pytest.mark.parametrize("world", list(WORLDS))
def test_factored_masks_train_as_full_masks(lm_data, world, backend):
    """One round before the prune, one under the injected masks, one
    after the momentum-preserving shrink: factored == full every round."""
    if WORLDS[world][0] == "cnn":
        _in_child_with_legacy_cpu_emitters(world, backend)
    else:
        run_equality(world, backend, lm_data, LM(ModelConfig(**TINY_LM)))


def run_equality(world, backend, data, model):
    """The per-round comparison of factored against full masks."""
    kind, compute = WORLDS[world]
    cfg = feddumap_config(**(CNN_CFG if kind == "cnn" else LM_CFG),
                          masked_compute=compute)
    params = model.init(jax.random.key(0))
    kept = (_cnn_kept(model, params) if kind == "cnn"
            else model.decide_kept(params, 0.5))
    cls = LocalScanBackend if backend == "local" else MeshBackend
    be_f = cls(model, data, cfg, use_masks=True)
    be_o = cls(FullMasks(model), data, cfg, use_masks=True)

    s_f, s_o = be_f.init_state(params), be_o.init_state(params)
    assert _shapes(s_f["masks"]) == _factored_shapes(model, params)
    assert _shapes(s_o["masks"]) == _shapes(params)
    k_f = k_o = jax.random.key(7)

    def one_round(tag):
        nonlocal s_f, s_o, k_f, k_o
        s_f, k_f, m_f = be_f.run_chunk(s_f, k_f, 1)
        s_o, k_o, m_o = be_o.run_chunk(s_o, k_o, 1)
        _assert_round_equal(s_f, s_o, m_f, m_o, f"{world}/{backend} {tag}")

    one_round("before the prune")
    s_f, _ = be_f.apply_prune(s_f, "mask", kept)
    s_o, _ = be_o.apply_prune(s_o, "mask", kept)
    assert _shapes(s_f["masks"]) == _factored_shapes(model, params)
    assert any(float(jnp.min(m)) == 0.0 for m in jax.tree.leaves(s_f["masks"]))
    one_round("masked")
    s_f, _ = be_f.apply_prune(s_f, "shrink", kept, compact_existing=True)
    s_o, _ = be_o.apply_prune(s_o, "shrink", kept, compact_existing=True)
    assert _shapes(s_f["masks"]) == _factored_shapes(model, s_f["params"])
    assert _shapes(s_f["params"]) != _shapes(params)
    one_round("shrunk")


def test_factor_masks_reduces_constant_axes_and_names_the_offender():
    like = {"a": {"w": jax.ShapeDtypeStruct((1, 1, 4), jnp.float32)},
            "b": jax.ShapeDtypeStruct((3, 1), jnp.float32)}
    unit = np.asarray([1.0, 0.0, 1.0, 1.0], np.float32)
    full = {"a": {"w": np.broadcast_to(unit, (2, 5, 4))},
            "b": np.asarray([[1.0], [0.0], [1.0]], np.float32)}
    out = factor_masks(full, like)
    np.testing.assert_array_equal(out["a"]["w"], unit.reshape(1, 1, 4))
    assert out["b"] is full["b"]          # already factored: untouched
    bad = np.array(full["a"]["w"])
    bad[1, 3, 0] = 0.0                    # varies along a broadcast axis
    with pytest.raises(CheckpointError, match="a/w"):
        factor_masks({"a": {"w": bad}, "b": full["b"]}, like)
    with pytest.raises(CheckpointError, match="'b'"):  # not its param's
        factor_masks({"a": full["a"], "b": np.ones((4, 1), np.float32)},
                     like)


def test_init_round_state_slot_shapes(cnn_world):
    _, model = cnn_world
    params = model.init(jax.random.key(0))
    eng = engine.EngineConfig(use_masks=True)
    # without the seam's masks every leaf is (1,) * ndim
    state = engine.init_round_state(params, eng)
    assert _shapes(state["masks"]) == jax.tree.map(
        lambda p: (1,) * p.ndim, params)
    state = engine.init_round_state(params, eng,
                                    masks=param_masks_for(model, params, {}))
    assert _shapes(state["masks"]) == _factored_shapes(model, params)
    assert _shapes(state["masks"])["conv2"]["w"] == (1, 1, 4, 8)
    assert _shapes(state["masks"])["fc1"]["w"][0] == 1


PRUNE_PLAN = (Scan(1), Prune(mode="mask"), Scan(1), Scan(1), Eval())


def _prune_cfg(**kw):
    return feddumap_config(**CNN_CFG, fedap=FedAPConfig(
        prune_round=1, probe_size=8, participants=2, min_rate=0.5), **kw)


def _checkpoint_with_full_masks(cnn_world, tmp_path, edit=None):
    """Kill a masked run after its first post-prune chunk, then rewrite
    the checkpoint's masks at their parameters' full shapes, as a
    checkpoint written before the slot was factored holds them."""
    data, model = cnn_world
    ckpt = tmp_path / "ckpt"
    with pytest.raises(SimulatedCrash):
        FederatedTrainer(model, data, _prune_cfg(
            faults=(KillAfterChunk(2),))).run(
                TrainPlan(*PRUNE_PLAN, checkpoint_dir=ckpt))
    step = sorted(ckpt.glob("step-*"))[-1]
    with np.load(step / "arrays.npz") as z:
        arrays = {k: z[k] for k in z.files}
    masks = [k for k in arrays if k.startswith("/state/masks/")]
    assert masks
    for k in masks:
        p = arrays[k.replace("/state/masks/", "/state/params/")]
        arrays[k] = np.broadcast_to(arrays[k], p.shape).copy()
    if edit is not None:
        edit(arrays)
    np.savez(step / "arrays.npz", **arrays)
    return ckpt


def test_full_shape_checkpoint_resumes_bit_identically(cnn_world, tmp_path):
    data, model = cnn_world
    full = FederatedTrainer(model, data, _prune_cfg()).run(
        TrainPlan(*PRUNE_PLAN))
    ckpt = _checkpoint_with_full_masks(cnn_world, tmp_path)
    res = FederatedTrainer(model, data, _prune_cfg()).resume(ckpt)
    assert _shapes(res.state["masks"]) == _factored_shapes(model, res.params)
    for a, b in zip(jax.tree.leaves(res.params), jax.tree.leaves(full.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree.leaves(res.state["masks"]),
                    jax.tree.leaves(full.state["masks"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert res.history["loss"] == full.history["loss"]


def test_non_factorable_checkpoint_mask_is_refused(cnn_world, tmp_path):
    data, model = cnn_world

    def vary_along_kernel_rows(arrays):
        m = arrays["/state/masks/conv2/w"]
        i, j = np.argwhere(m[0, 0] == 1.0)[0]
        m[0, 0, i, j] = 0.0           # one kernel tap of a kept pair

    ckpt = _checkpoint_with_full_masks(cnn_world, tmp_path,
                                       edit=vary_along_kernel_rows)
    with pytest.raises(CheckpointError, match="conv2/w"):
        FederatedTrainer(model, data, _prune_cfg()).resume(ckpt)
