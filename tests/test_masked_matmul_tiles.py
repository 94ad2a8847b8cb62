"""The masked-matmul kernels at tiles wider than the mask's granularity.

Interpret mode, small shapes.  A tile of ``tn`` columns covers several
128-column mask blocks: it is skipped only when all of them are pruned,
its pruned blocks of ``y`` and ``dw`` are written as exact zeros, and
``dx`` never sees the cotangent of a pruned column.  Checked against the
float64 oracles of ``kernels/ref.py`` with one and with several
contraction steps, for float32 and for bfloat16 operands (the serving
path, accumulated in float32); and the tile chooser on the shapes the
model runs.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.masked_matmul import (
    VMEM_BUDGET,
    Tiles,
    _masked_matmul,
    choose_tiles,
)

M, K, N = 64, 256, 1024
# 8 mask blocks of 128 under 512-column tiles: tile 0 mixes kept and
# pruned blocks, tile 1 is wholly pruned
MASK = [1, 0, 1, 1, 0, 0, 0, 0]
TILES = {
    # one contraction step in every kernel (tk = K, tn = N for dx, tm = M)
    "one_step": Tiles(fwd=(32, 256, 512), dx=(32, 128, 1024),
                      dw=(64, 128, 512)),
    # several contraction steps in every kernel
    "several_steps": Tiles(fwd=(32, 128, 512), dx=(32, 128, 512),
                           dw=(32, 128, 512)),
}


def _case(seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((M, K)) * 0.1, jnp.float32)
    w = jnp.asarray(rng.standard_normal((K, N)) * 0.1, jnp.float32)
    # dy is nonzero on pruned columns too: the kernel must not pass it on
    dy = jnp.asarray(rng.standard_normal((M, N)) * 0.1, jnp.float32)
    return x, w, jnp.asarray(MASK, jnp.float32), dy


def _run(x, w, mask, dy, tiles):
    """y, dx, dw of the custom VJP at the given tiles, interpreted."""
    cfg = (tiles, 128, True)

    def f(x_, w_):
        y = _masked_matmul(cfg, x_, w_, mask)
        return jnp.sum(y * dy), y

    (_, y), (dx, dw) = jax.value_and_grad(f, argnums=(0, 1),
                                          has_aux=True)(x, w)
    return tuple(np.asarray(a, np.float32) for a in (y, dx, dw))


def _bf16(a):
    return np.asarray(jnp.asarray(a, jnp.bfloat16).astype(jnp.float32))


@pytest.mark.parametrize("tiles", TILES.values(), ids=TILES.keys())
def test_wide_tiles_match_f64_oracles(tiles):
    x, w, mask, dy = _case()
    y, dx, dw = _run(x, w, mask, dy, tiles)
    dx_ref, dw_ref = ref.masked_matmul_vjp_ref64(x, w, mask, dy)
    np.testing.assert_allclose(y, ref.masked_matmul_fwd_ref64(x, w, mask),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(dx, dx_ref, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(dw, dw_ref, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("tiles", TILES.values(), ids=TILES.keys())
def test_pruned_columns_exactly_zero(tiles):
    x, w, mask, dy = _case(1)
    y, _, dw = _run(x, w, mask, dy, tiles)
    cols = np.repeat(np.asarray(MASK) == 0, 128)
    assert np.all(y[:, cols] == 0.0) and np.all(dw[:, cols] == 0.0)
    assert np.all(np.abs(y[:, ~cols]).max(axis=0) > 0.0)
    assert np.all(np.abs(dw[:, ~cols]).max(axis=0) > 0.0)


@pytest.mark.parametrize("tiles", TILES.values(), ids=TILES.keys())
def test_bfloat16_operands_float32_accumulation(tiles):
    """bfloat16 operands (serving): the products accumulate in float32
    over the contraction and are rounded to bfloat16 once, at the end;
    pruned columns stay exact zeros."""
    x, w, mask, dy = (a.astype(jnp.bfloat16) for a in _case(2))
    y, dx, dw = _run(x, w, mask, dy, tiles)
    xb, wb, dyb = (np.asarray(a, np.float64) for a in (x, w, dy))
    cols = np.repeat(np.asarray(MASK, np.float64), 128)
    dyb_m = dyb * cols
    want = {"y": (xb @ wb) * cols, "dx": dyb_m @ wb.T, "dw": xb.T @ dyb_m}
    eps = np.finfo(np.float32).eps
    for name, got, contraction in (("y", y, K), ("dx", dx, N), ("dw", dw, M)):
        np.testing.assert_allclose(
            got, want[name], rtol=2.0 ** -8,
            atol=contraction * eps * np.abs(want[name]).max(), err_msg=name)
    pruned = np.repeat(np.asarray(MASK) == 0, 128)
    assert np.all(y[:, pruned] == 0.0) and np.all(dw[:, pruned] == 0.0)


SHAPES = {
    "train": (1024, 2048, 8192, jnp.float32),
    "train_bf16": (1024, 2048, 8192, jnp.bfloat16),
    "eval": (4096, 2048, 8192, jnp.float32),
    "decode8": (8, 2048, 8192, jnp.bfloat16),
    "decode16": (16, 2048, 8192, jnp.bfloat16),
    "cnn_head": (32, 512, 256, jnp.float32),
}


@pytest.mark.parametrize("shape", SHAPES.values(), ids=SHAPES.keys())
def test_tile_chooser_divides_aligns_and_fits(shape):
    m, k, n, dtype = shape
    tiles = choose_tiles(m, k, n, dtype)
    rows = 32 // jnp.dtype(dtype).itemsize
    for kind, contraction in (("fwd", k), ("dx", n), ("dw", m)):
        tm, tk, tn = getattr(tiles, kind)
        assert m % tm == 0 and k % tk == 0 and n % tn == 0, (kind, tiles)
        assert tm == m or tm % rows == 0, (kind, tiles)
        assert tk % 128 == 0 and tn % 128 == 0, (kind, tiles)
        a, b, o, tr = {"fwd": (tm * tk, tk * tn, tm * tn, tk),
                       "dx": (tm * tn, tk * tn, tm * tk, tn),
                       "dw": (tm * tk, tm * tn, tk * tn, tm)}[kind]
        # double-buffered operands and output, the float32 product, and
        # the float32 accumulator of a contraction in several steps
        vmem = (2 * (a + b + o) * jnp.dtype(dtype).itemsize + 4 * o
                + (4 * o if contraction > tr else 0))
        assert vmem <= VMEM_BUDGET, (kind, tiles)
        # a few hundred grid steps at most, never the 128-tile's thousands
        assert (m // tm) * (k // tk) * (n // tn) <= 512, (kind, tiles)


def test_training_tiles_are_mxu_sized():
    """The training shape takes large tiles: half the per-client M or
    more, and each grid step at least 64x the work of a 128-cube tile."""
    for tm, tk, tn in choose_tiles(1024, 2048, 8192, jnp.float32):
        assert tm >= 512 and tm * tk * tn >= 64 * 128 ** 3
