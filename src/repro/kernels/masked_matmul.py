"""FedAP structured-pruning matmul (TPU Pallas), differentiable.

``masked_matmul(x, w, block_mask)`` computes ``x @ w`` where ``block_mask``
([N / block_n] of 0/1) marks column blocks of ``w`` as pruned.  Pruned
blocks are SKIPPED on the MXU (``pl.when`` guards the dot), so structured
pruning's FLOP savings are realized with static shapes inside a live jit —
the mechanism FedAP uses between the pruning round and the re-jit to the
compacted model (DESIGN.md Section 3).

The op carries a ``jax.custom_vjp``, so it is usable inside the TRAINING
engine (``EngineConfig.masked_compute="kernel"``), not just on the
eval/serving path.  The backward pass skips the same MXU work as the
forward:

  dx = dy @ w.T    — the pruned column blocks of ``w`` are ROW blocks of
                     ``w.T``; their contraction slices are skipped, which
                     is exact because the forward zeroed the matching
                     columns of the output (so any upstream cotangent on
                     them is discarded by the chain rule);
  dw = x.T @ dy    — pruned COLUMN blocks are skipped and their output
                     blocks are written as exact zeros (a pruned filter
                     receives an exactly-zero gradient, keeping mask-mode
                     training self-sustaining inside a compiled scan).

Block layout (all three kernels): contraction dim innermost, f32
accumulator in VMEM scratch.  Mask granularity = bn (128-aligned, the MXU
lane width), matching FedAP's 128-aligned kept-filter counts.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def _masked_mm_kernel(mask_ref, x_ref, w_ref, o_ref, acc_scr, *, nk: int):
    """Forward: o[i, j] = sum_k x[i, k] @ w[k, j], skipped when block j is
    pruned (grid (M/bm, N/bn, K/bk), K innermost)."""
    ki = pl.program_id(2)
    keep = mask_ref[pl.program_id(1)] > 0

    @pl.when(ki == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(keep)
    def _mac():
        acc_scr[...] += jax.lax.dot_general(
            x_ref[...].astype(jnp.float32), w_ref[...].astype(jnp.float32),
            (((1,), (0,)), ((), ())))

    @pl.when(ki == nk - 1)
    def _finish():
        o_ref[...] = jnp.where(keep, acc_scr[...], 0.0).astype(o_ref.dtype)


def _masked_dx_kernel(mask_ref, dy_ref, w_ref, dx_ref, acc_scr, *, nn: int):
    """Backward-x: dx[i, j] = sum_n dy[i, n] @ w.T[n, j] with pruned ROW
    blocks of ``w.T`` (= pruned column blocks n of ``w``) skipped
    (grid (M/bm, K/bk, N/bn), N innermost).  Exact: the forward zeroed the
    pruned output columns, so their cotangent never contributes."""
    ni = pl.program_id(2)
    keep = mask_ref[ni] > 0

    @pl.when(ni == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(keep)
    def _mac():
        # dy block [bm, bn] x w block [bk, bn] contracted on the N axis
        # == dy_blk @ w_blk.T, without materializing the transpose.
        acc_scr[...] += jax.lax.dot_general(
            dy_ref[...].astype(jnp.float32), w_ref[...].astype(jnp.float32),
            (((1,), (1,)), ((), ())))

    @pl.when(ni == nn - 1)
    def _finish():
        dx_ref[...] = acc_scr[...].astype(dx_ref.dtype)


def _masked_dw_kernel(mask_ref, x_ref, dy_ref, dw_ref, acc_scr, *, nm: int):
    """Backward-w: dw[i, j] = sum_m x.T[i, m] @ dy[m, j] with pruned column
    blocks j skipped and their outputs written as EXACT zeros
    (grid (K/bk, N/bn, M/bm), M innermost)."""
    mi = pl.program_id(2)
    keep = mask_ref[pl.program_id(1)] > 0

    @pl.when(mi == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(keep)
    def _mac():
        # x block [bm, bk] x dy block [bm, bn] contracted on the M axis
        # == x_blk.T @ dy_blk, without materializing the transpose.
        acc_scr[...] += jax.lax.dot_general(
            x_ref[...].astype(jnp.float32), dy_ref[...].astype(jnp.float32),
            (((0,), (0,)), ((), ())))

    @pl.when(mi == nm - 1)
    def _finish():
        dw_ref[...] = jnp.where(keep, acc_scr[...], 0.0).astype(dw_ref.dtype)


# ---------------------------------------------------------------------------
# pallas_call wrappers (blocks = (block_m, block_n, block_k, interpret))
#
# The block mask is a scalar-prefetch operand: it lands in SMEM whole, and
# each grid step reads its own entry by program id.  (A rank-1 (1,) VMEM
# block is refused by the TPU lowering, which tiles rank-1 blocks in 128s.)
# ---------------------------------------------------------------------------

def _call(kernel, name, grid, in_specs, out_spec, out_shape, acc_shape,
          interpret, block_mask, *operands):
    keep = (block_mask > 0).astype(jnp.int32)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=grid, in_specs=in_specs,
            out_specs=out_spec,
            scratch_shapes=[pltpu.VMEM(acc_shape, jnp.float32)]),
        out_shape=out_shape,
        interpret=interpret,
        name=name,
    )(keep, *operands)


def _fwd_call(blocks, x, w, block_mask):
    bm, bn, bk, interpret = blocks
    m, kdim = x.shape
    n = w.shape[1]
    nk = kdim // bk
    return _call(
        functools.partial(_masked_mm_kernel, nk=nk), "masked_matmul_fwd",
        (m // bm, n // bn, nk),
        [pl.BlockSpec((bm, bk), lambda i, j, k, _: (i, k)),
         pl.BlockSpec((bk, bn), lambda i, j, k, _: (k, j))],
        pl.BlockSpec((bm, bn), lambda i, j, k, _: (i, j)),
        jax.ShapeDtypeStruct((m, n), x.dtype), (bm, bn), interpret,
        block_mask, x, w)


def _dx_call(blocks, dy, w, block_mask):
    bm, bn, bk, interpret = blocks
    m, n = dy.shape
    kdim = w.shape[0]
    nn = n // bn
    return _call(
        functools.partial(_masked_dx_kernel, nn=nn), "masked_matmul_dx",
        (m // bm, kdim // bk, nn),
        [pl.BlockSpec((bm, bn), lambda i, j, k, _: (i, k)),
         pl.BlockSpec((bk, bn), lambda i, j, k, _: (j, k))],
        pl.BlockSpec((bm, bk), lambda i, j, k, _: (i, j)),
        jax.ShapeDtypeStruct((m, kdim), dy.dtype), (bm, bk), interpret,
        block_mask, dy, w)


def _dw_call(blocks, x, dy, block_mask):
    bm, bn, bk, interpret = blocks
    m, kdim = x.shape
    n = dy.shape[1]
    nm = m // bm
    return _call(
        functools.partial(_masked_dw_kernel, nm=nm), "masked_matmul_dw",
        (kdim // bk, n // bn, nm),
        [pl.BlockSpec((bm, bk), lambda i, j, k, _: (k, i)),
         pl.BlockSpec((bm, bn), lambda i, j, k, _: (k, j))],
        pl.BlockSpec((bk, bn), lambda i, j, k, _: (i, j)),
        jax.ShapeDtypeStruct((kdim, n), x.dtype), (bk, bn), interpret,
        block_mask, x, dy)


# ---------------------------------------------------------------------------
# custom VJP
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _masked_matmul(blocks, x, w, block_mask):
    return _fwd_call(blocks, x, w, block_mask)


def _masked_matmul_fwd(blocks, x, w, block_mask):
    return _fwd_call(blocks, x, w, block_mask), (x, w, block_mask)


def _masked_matmul_bwd(blocks, residuals, dy):
    x, w, block_mask = residuals
    dx = _dx_call(blocks, dy, w, block_mask)
    dw = _dw_call(blocks, x, dy, block_mask)
    return dx, dw, jnp.zeros_like(block_mask)


_masked_matmul.defvjp(_masked_matmul_fwd, _masked_matmul_bwd)


# ---------------------------------------------------------------------------
# public entry point
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "block_k",
                                             "interpret"))
def masked_matmul(x, w, block_mask, *, block_m: int = 128, block_n: int = 128,
                  block_k: int = 128, interpret: bool = False):
    """x [M, K] @ w [K, N] with pruned column blocks skipped, differentiable.

    block_mask: [N // block_n] float/int (1 = keep, 0 = pruned).

    Shape/alignment preconditions raise ``ValueError`` at trace time (not
    ``assert``: they must survive ``python -O`` and name the offending
    shapes).
    """
    if x.ndim != 2 or w.ndim != 2:
        raise ValueError(f"masked_matmul expects 2-D operands, got "
                         f"x.shape={x.shape} w.shape={w.shape}")
    m, kdim = x.shape
    k2, n = w.shape
    if kdim != k2:
        raise ValueError(f"masked_matmul contraction mismatch: x.shape="
                         f"{x.shape} vs w.shape={w.shape} (K {kdim} != {k2})")
    if m % block_m or n % block_n or kdim % block_k:
        raise ValueError(
            f"masked_matmul shapes must be block-aligned: x.shape={x.shape} "
            f"w.shape={w.shape} vs blocks (block_m={block_m}, "
            f"block_n={block_n}, block_k={block_k}); pad M (see "
            f"repro.models.cnn.masked_dense) or pick divisible blocks")
    block_mask = jnp.asarray(block_mask, jnp.float32)
    if block_mask.shape != (n // block_n,):
        raise ValueError(
            f"masked_matmul block_mask must have shape (N // block_n,) = "
            f"({n // block_n},), got {block_mask.shape} for w.shape={w.shape} "
            f"block_n={block_n}")
    return _masked_matmul((block_m, block_n, block_k, interpret),
                          x, w, block_mask)
