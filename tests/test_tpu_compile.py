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
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attention import decode_attention
from repro.kernels.masked_matmul import masked_matmul

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
