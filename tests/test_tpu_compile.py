"""The main path's Pallas kernels compile for a TPU v5e at OLMo-1B widths.

Nothing runs: each test compiles for a *described* v5e chip (the TPU
compiler ships with jaxlib and needs no attached device), so a block
shape or a memory space that Mosaic refuses fails here, in the CPU test
run, instead of on the chip.  Interpret mode (tests/test_kernels.py)
cannot see these: it accepts any block shape.

The topology is described inside a fixture, never at import time: only
one process may load the TPU library, and pytest-xdist workers each
import every test file.  The persistent compile cache is off around these
compiles, because an entry written for a described chip cannot be read
back without one.
"""
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attention import decode_attention
from repro.kernels.masked_matmul import masked_matmul
from repro.launch.hlo_cost import HloCostModel, _shape_dims

# OLMo-1B (src/repro/configs/olmo_1b.py): d_model 2048, d_ff 8192,
# 16 heads = 16 KV heads of head_dim 128.
D_MODEL, D_FF, KV_HEADS, HEAD_DIM = 2048, 8192, 16, 128


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _mosaic_calls(compiled) -> int:
    return compiled.as_text().count("tpu_custom_call")


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_masked_matmul_forward_compiles(one_chip, dtype):
    # M=8: the decode wave's 8 slots, one 8-row block (masked_dense pads
    # M only to 8, also for bf16, whose native tile is 16 rows)
    m = 8
    fwd = jax.jit(lambda x, w, mask: masked_matmul(x, w, mask))
    compiled = fwd.lower(_spec((m, D_MODEL), dtype, one_chip),
                         _spec((D_MODEL, D_FF), dtype, one_chip),
                         _spec((D_FF // 128,), jnp.float32, one_chip)
                         ).compile()
    assert _mosaic_calls(compiled) >= 1


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_masked_matmul_gradient_compiles(one_chip, dtype):
    m = 8

    def loss(x, w, mask):
        y = masked_matmul(x, w, mask)
        return jnp.sum(y.astype(jnp.float32))

    grad = jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))
    compiled = grad.lower(_spec((m, D_MODEL), dtype, one_chip),
                          _spec((D_MODEL, D_FF), dtype, one_chip),
                          _spec((D_FF // 128,), jnp.float32, one_chip)
                          ).compile()
    # forward + dx + dw kernels
    assert _mosaic_calls(compiled) >= 3


# The training shape: one client's 2 sequences of 512 tokens against an
# FFN projection, float32 master weights and bfloat16 weights.  A tile
# Mosaic refuses, or one over the kernel's VMEM limit, fails here.
TRAIN_M = 1024
DTYPES = [jnp.float32, jnp.bfloat16]


@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "bf16"])
def test_masked_matmul_training_forward_compiles(one_chip, dtype):
    fwd = jax.jit(lambda x, w, mask: masked_matmul(x, w, mask))
    compiled = fwd.lower(_spec((TRAIN_M, D_MODEL), dtype, one_chip),
                         _spec((D_MODEL, D_FF), dtype, one_chip),
                         _spec((D_FF // 128,), jnp.float32, one_chip)
                         ).compile()
    assert _mosaic_calls(compiled) >= 1


@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "bf16"])
def test_masked_matmul_training_gradient_compiles(one_chip, dtype):
    def loss(x, w, mask):
        y = masked_matmul(x, w, mask)
        return jnp.sum(y.astype(jnp.float32))

    # the two clients of a round, vmapped as the round engine does
    grad = jax.jit(jax.vmap(jax.value_and_grad(loss, argnums=(0, 1)),
                            in_axes=(0, 0, None)))
    compiled = grad.lower(_spec((2, TRAIN_M, D_MODEL), dtype, one_chip),
                          _spec((2, D_MODEL, D_FF), dtype, one_chip),
                          _spec((D_FF // 128,), jnp.float32, one_chip)
                          ).compile()
    assert _mosaic_calls(compiled) >= 3


@pytest.mark.parametrize("dtype", DTYPES, ids=["f32", "bf16"])
def test_masked_ffn_layer_scan_gradient_compiles(one_chip, dtype):
    """The FFN's masked projections in a scan over 2 layers, differentiated
    as a client's local steps are: XLA fuses each weight gradient's kernel
    with the in-place update of the scan's stacked gradient and gives that
    fusion only the default scoped VMEM, so a tile that fits the kernel
    alone can fail here."""
    layers = 2

    def loss(params, h, mask):
        def layer(h, lp):
            wi, wg, wo = lp
            up = masked_matmul(h, wi, mask)
            gate = masked_matmul(h, wg, mask)
            return h + (jax.nn.silu(gate) * up) @ wo, None

        h, _ = jax.lax.scan(jax.checkpoint(layer), h, params)
        return jnp.sum(h.astype(jnp.float32) ** 2)

    grad = jax.jit(jax.grad(loss))
    up = _spec((layers, D_MODEL, D_FF), dtype, one_chip)
    compiled = grad.lower(
        (up, up, _spec((layers, D_FF, D_MODEL), dtype, one_chip)),
        _spec((TRAIN_M, D_MODEL), dtype, one_chip),
        _spec((D_FF // 128,), jnp.float32, one_chip)).compile()
    assert _mosaic_calls(compiled) >= 6


def test_decode_attention_with_lengths_compiles(one_chip):
    slots, cache = 8, 1024
    dec = jax.jit(lambda q, k, v, lens: decode_attention(q, k, v, lens,
                                                         block_k=512))
    kv = _spec((slots, cache, KV_HEADS, HEAD_DIM), jnp.bfloat16, one_chip)
    compiled = dec.lower(
        _spec((slots, 1, KV_HEADS, HEAD_DIM), jnp.bfloat16, one_chip),
        kv, kv, _spec((slots,), jnp.int32, one_chip)).compile()
    assert _mosaic_calls(compiled) >= 1


# ---------------------------------------------------------------------------
# FedAP's keep-masks in the round state are factored: no compiled pass of
# the round reads a parameter-sized mask
# ---------------------------------------------------------------------------

# Instructions that only route a value (no pass over its bytes).
_PLUMBING = {"tuple", "get-tuple-element", "while", "call", "conditional",
             "bitcast", "parameter", "opt-barrier"}


def _both(a, b):
    if isinstance(a, list) and isinstance(b, list):
        return [_both(x, y) for x, y in zip(a, b)]
    return a is True and b is True


def _entry_params(text: str) -> list[tuple[str, str]]:
    """(op_name, type) of every parameter of the compiled entry."""
    cm = HloCostModel(text)
    out = []
    for ins in cm.comps[cm.entry]:
        if ins.opcode == "parameter":
            m = re.search(r'op_name="([^"]*)"', ins.line)
            out.append((m.group(1).replace("\\'", "'") if m else "",
                        ins.type_str))
    return out


def _mask_fed(text: str, full_numels: set) -> list[tuple]:
    """Instructions that read a float32 value of a full parameter size
    computed from the state's masks alone.

    A value is mask-derived when every operand that is neither a scalar
    nor a constant is: the entry parameters of ``state['masks']``, and what
    broadcasts, slices, copies or fuses them, followed through tuples,
    while loops (the body sees the loop's initial tuple) and calls.  Each
    non-routing instruction that takes such a value of a size in
    ``full_numels`` is reported as (computation, instruction, operand,
    type)."""
    cm = HloCostModel(text)
    hits = set()

    def analyze(comp, param_taint):
        tab = {i.name: i for i in cm.comps[comp]}
        taint, root = {}, None
        for ins in cm.comps[comp]:
            ops = [o for o in ins.operands if o in tab]
            if ins.opcode == "parameter":
                idx = int(re.search(r"parameter\((\d+)\)", ins.line).group(1))
                t = param_taint(idx, ins)
            elif ins.opcode == "get-tuple-element":
                k = int(re.search(r"index=(\d+)", ins.line).group(1))
                src = taint.get(ops[0])
                t = src[k] if isinstance(src, list) else False
            elif ins.opcode == "tuple":
                t = [taint.get(o, False) for o in ops]
            elif ins.opcode == "while":
                body = re.search(r"body=%([\w.\-]+)", ins.line).group(1)
                init = taint.get(ops[0], False)
                t = _both(init, analyze(body, lambda i, _: init))
            elif ins.opcode == "call":
                f = re.search(r"to_apply=%([\w.\-]+)", ins.line).group(1)
                t = analyze(f, lambda i, _: taint.get(ops[i], False))
            else:
                vals = []
                for o in ops:
                    dims = _shape_dims(tab[o].type_str)
                    if tab[o].opcode in ("constant", "iota") or (
                            len(dims) == 1 and math.prod(dims[0][1]) == 1):
                        continue
                    vals.append(taint.get(o, False))
                    if (taint.get(o) is True and ins.opcode not in _PLUMBING
                            and len(dims) == 1 and dims[0][0] == "f32"
                            and math.prod(dims[0][1]) in full_numels):
                        hits.add((comp, ins.name, o,
                                  tab[o].type_str.split("{")[0]))
                t = bool(vals) and all(v is True for v in vals)
            taint[ins.name] = t
            if ins.line.lstrip().startswith("ROOT"):
                root = ins.name
        return taint[root]

    analyze(cm.entry, lambda i, ins: "state['masks']"
            in ins.line.replace("\\'", "'"))
    return sorted(hits)


@pytest.mark.parametrize("mask_shape,expect_hit", [
    ((D_MODEL, D_FF), True), ((1, D_FF), False)], ids=["full", "factored"])
def test_mask_flow_check_sees_a_full_mask(one_chip, mask_shape, expect_hit):
    """The check below on one masked update step: a parameter-shaped mask
    operand is found, a factored one is not."""
    def step(state):
        p, m = state["params"]["w"], state["masks"]["w"]
        return p - 0.1 * (p * p) * m

    f32 = lambda shape: _spec(shape, jnp.float32, one_chip)
    text = jax.jit(step).lower({"params": {"w": f32((D_MODEL, D_FF))},
                                "masks": {"w": f32(mask_shape)}}
                               ).compile().as_text()
    assert bool(_mask_fed(text, {D_MODEL * D_FF})) == expect_hit


@pytest.fixture(scope="module")
def olmo_round(one_chip):
    """One FedDUMAP round (``engine.round_core``) of a 1-layer LM at
    OLMo-1B widths with float32 master weights: 2 clients of 2 local steps
    of 2x512 tokens, tau 2, restart local momentum and server momentum,
    FedAP masks on the masked-matmul kernel — compiled for the described
    v5e.  Returns (optimized HLO text, the round state's shapes)."""
    import dataclasses

    import repro.kernels.ops as ops
    from repro.configs.olmo_1b import CONFIG
    from repro.core import engine
    from repro.core.backend import model_fns, param_masks_for
    from repro.models.lm import LM

    model = LM(dataclasses.replace(CONFIG, num_layers=1,
                                   param_dtype="float32"))
    eng = engine.EngineConfig(lr=1e-2, use_server_update=True,
                              local_momentum="restart",
                              server_momentum=True, use_masks=True,
                              masked_compute="kernel")
    grad_fn, la_fn = model_fns(model, eng)
    state = jax.eval_shape(
        lambda p: engine.init_round_state(
            p, eng, filter_masks=model.filter_masks(p, {}),
            masks=param_masks_for(model, p, {})),
        jax.eval_shape(model.init, jax.random.key(0)))
    state = jax.tree.map(lambda s: _spec(s.shape, s.dtype, one_chip), state)
    tok = lambda *lead: _spec(lead + (512,), jnp.int32, one_chip)
    scalar = _spec((), jnp.float32, one_chip)
    batch = {"client": (tok(2, 2, 2), tok(2, 2, 2)),
             "sizes": _spec((2,), jnp.float32, one_chip),
             "server": (tok(2, 2), tok(2, 2)),
             "d_round": scalar, "d_server": scalar, "n0": scalar}
    # the kernels pick interpret mode from the (CPU) default backend; the
    # compile is for the chip
    was = ops._interpret
    ops._interpret = lambda: False
    try:
        # the argument names are what the entry parameters' op_name says
        compiled = jax.jit(
            lambda state, batch: engine.round_core(eng, grad_fn, la_fn,
                                                   state, batch)
        ).lower(state, batch).compile()
    finally:
        ops._interpret = was
    return compiled.as_text(), state


def _nbytes(type_str: str) -> int:
    return sum(4 * math.prod(d) for _, d in _shape_dims(type_str))


def test_round_mask_slot_is_under_a_percent_of_params(olmo_round):
    text, state = olmo_round
    params = _entry_params(text)
    mask_b = sum(_nbytes(t) for n, t in params
                 if n.startswith("state['masks']"))
    param_b = sum(_nbytes(t) for n, t in params
                  if n.startswith("state['params']"))
    assert len([n for n, _ in params if n.startswith("state['masks']")]) \
        == len(jax.tree.leaves(state["masks"]))
    assert param_b > 500e6           # embed + one layer, float32
    assert 0 < mask_b < 0.01 * param_b


def test_round_reads_no_parameter_sized_mask(olmo_round):
    """No fusion (or any other pass) of the compiled round takes a float32
    operand of a full parameter size that comes from ``state['masks']``:
    the factored masks stay broadcast inside the update fusions."""
    text, state = olmo_round
    full = set()
    for p in jax.tree.leaves(state["params"]):
        n = math.prod(p.shape)
        full |= {n, 2 * n, n // p.shape[0]}      # + client dim, a layer
    assert _mask_fed(text, full) == []
