"""FedAP structured-pruning matmul (TPU Pallas), differentiable.

``masked_matmul(x, w, block_mask)`` computes ``x @ w`` where ``block_mask``
([N / block_n] of 0/1) marks column blocks of ``w`` as pruned.  A tile
whose blocks are all pruned is SKIPPED on the MXU (``pl.when`` guards the
dot), so structured pruning's FLOP savings are realized with static shapes
inside a live jit — the mechanism FedAP uses between the pruning round
and the re-jit to the compacted model (DESIGN.md Section 3).

The op carries a ``jax.custom_vjp``, so it is usable inside the TRAINING
engine (``EngineConfig.masked_compute="kernel"``), not just on the
eval/serving path.  The backward pass skips the same MXU work as the
forward:

  dx = dy @ w.T    — the pruned column blocks of ``w`` are ROW blocks of
                     ``w.T``; their contraction slices are excluded: a
                     wholly pruned tile is skipped, and inside a partly
                     kept tile ``dy``'s pruned blocks are zeroed before
                     the dot, so an upstream cotangent on a pruned column
                     never reaches ``dx``, whether or not the caller
                     masks ``y`` afterwards;
  dw = x.T @ dy    — pruned column blocks are skipped and their output
                     blocks are written as exact zeros (a pruned filter
                     receives an exactly-zero gradient, keeping mask-mode
                     training self-sustaining inside a compiled scan).

Layout (all three kernels): grid (output rows, output columns,
contraction), contraction innermost, float32 accumulation.  The tiles are
chosen from the operand shapes by :func:`choose_tiles` — MXU-sized, each
operand tile fetched as few times as the VMEM budget allows — and are
independent of the mask's granularity ``block_n`` (128-aligned, the MXU
lane width, matching FedAP's 128-aligned kept-filter counts): a tile of
``tn`` columns covers ``tn / block_n`` mask entries, is skipped only when
all of them are 0, and writes its pruned ``block_n``-column sub-blocks of
``y`` and ``dw`` as exact zeros.

Each dot multiplies the operand tiles in their input type and accumulates
in float32.  On a v5e Mosaic multiplies float32 operands in one bfloat16
pass, bit-equal to XLA's DEFAULT precision for every other float32 matmul
of the model (PERF.md section 6).
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# ---------------------------------------------------------------------------
# tiles
# ---------------------------------------------------------------------------

class Tiles(NamedTuple):
    """One (tm, tk, tn) tile per kernel, in the forward's names: x is
    [M, K], w is [K, N]."""
    fwd: tuple[int, int, int]
    dx: tuple[int, int, int]
    dw: tuple[int, int, int]


# VMEM the tiles of one call may take, as _vmem counts them.  XLA may fuse
# a kernel with the in-place update of a scan's stacked output (the weight
# gradients of a layer scan) and then gives the fusion only the default
# scoped VMEM, 16 MiB on a v5e, whatever the kernel asks for.  Compiled
# for a described v5e at the training shape, every weight-gradient tiling
# counted at 14 MiB or less fits there, and one counted at 16 MiB does not
# (Mosaic's own scratch is not counted), so the budget is 14 MiB.
VMEM_BUDGET = 14 * 2**20
# The cost model's constants, fitted to 72 tilings of the training shape
# timed on a v5e (PERF.md section 6): the MXU rate a large tile reaches,
# the HBM rate of the pipeline's copies, and the fixed cost of a grid step.
_MXU_FLOPS, _HBM_BYTES_PER_S, _STEP_S = 180e12, 700e9, 0.2e-6


def _divisors(dim: int, align: int) -> list[int]:
    """Tile sizes for a dimension: ``align``-multiples that divide it, and
    the whole dimension (always a legal block)."""
    return sorted({t for t in range(align, dim, align) if dim % t == 0}
                  | {dim})


def _vmem(a_tile: int, b_tile: int, o_tile: int, nr: int,
          in_bytes: int) -> int:
    """Bytes of VMEM for tiles of the given element counts: double-buffered
    operands and output, the float32 product, and the float32 accumulator
    when the contraction takes several steps."""
    return (2 * (a_tile + b_tile + o_tile) * in_bytes + o_tile * 4
            + (o_tile * 4 if nr > 1 else 0))


def _pick(p: int, q: int, r: int, aligns: tuple[int, int, int],
          in_bytes: int) -> tuple[int, int, int]:
    """The (tp, tq, tr) of one kernel with output [P, Q] and contraction R,
    grid (P/tp, Q/tq, R/tr): the least modelled time whose buffers fit.

    Operand A's block moves with (p, r), B's with (r, q).  The pipeline
    skips a fetch whose block index did not change, so with one
    contraction step A is read once and B once per row of tiles (once in
    all with one column of tiles); otherwise A is read once per column and
    B once per row of tiles.  Time = the larger of MXU and HBM time, plus
    the first tiles' fetch and the last output's write-back (not
    overlapped), plus a fixed cost per grid step.
    """
    best = None
    for tp in _divisors(p, aligns[0]):
        for tq in _divisors(q, aligns[1]):
            for tr in _divisors(r, aligns[2]):
                np_, nq, nr = p // tp, q // tq, r // tr
                a_tile, b_tile, o_tile = tp * tr, tr * tq, tp * tq
                if _vmem(a_tile, b_tile, o_tile, nr, in_bytes) > VMEM_BUDGET:
                    continue
                a_reads = 1 if nr == 1 else nq
                b_reads = 1 if nr == 1 and nq == 1 else np_
                hbm = (p * r * a_reads + r * q * b_reads + p * q) * in_bytes
                exposed = (a_tile + b_tile + o_tile) * in_bytes
                t = (max(2 * p * q * r / _MXU_FLOPS, hbm / _HBM_BYTES_PER_S)
                     + exposed / _HBM_BYTES_PER_S + np_ * nq * nr * _STEP_S)
                # a tie goes to the taller tile: the MXU streams more rows
                # through each operand tile it holds
                if best is None or (t, -tp) < best[0]:
                    best = ((t, -tp), (tp, tq, tr))
    if best is None:
        raise ValueError(
            f"no masked_matmul tile of output [{p}, {q}], contraction {r} "
            f"fits {VMEM_BUDGET} bytes of VMEM")
    return best[1]


def choose_tiles(m: int, k: int, n: int, dtype, *,
                 block_n: int = 128) -> Tiles:
    """Tiles of the three kernels for x [M, K] @ w [K, N] in ``dtype``.

    Lane dimensions (K, N) take 128-multiples (N's also multiples of the
    mask granularity ``block_n``) and M multiples of the dtype's sublane
    tile (8 rows of 32 bits, 16 of bfloat16); any dimension may also be
    taken whole, the one choice where none of those divides it.  Pure
    in its arguments, so training, eval (M=4096) and decode (M=8) shapes
    each get their own tiles from the same rule.
    """
    in_b = jnp.dtype(dtype).itemsize
    rows = 32 // in_b
    lane_n = math.lcm(128, block_n)
    fm, fn, fk = _pick(m, n, k, (rows, lane_n, 128), in_b)
    xm, xk, xn = _pick(m, k, n, (rows, 128, lane_n), in_b)
    wk, wn, wm = _pick(k, n, m, (128, lane_n, rows), in_b)
    return Tiles(fwd=(fm, fk, fn), dx=(xm, xk, xn), dw=(wm, wk, wn))


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

_NN = (((1,), (0,)), ((), ()))   # a @ b
_NT = (((1,), (1,)), ((), ()))   # a @ b.T
_TN = (((0,), (0,)), ((), ()))   # a.T @ b


def _kept(mask_ref, j, sub: int):
    """(any, all) of the ``sub`` mask entries of column tile ``j``."""
    vals = [mask_ref[j * sub + s] > 0 for s in range(sub)]
    return (functools.reduce(jnp.logical_or, vals),
            functools.reduce(jnp.logical_and, vals))


def _zero_pruned(v, mask_ref, j, sub: int, block_n: int):
    """``v`` [rows, sub * block_n] with the pruned sub-blocks of column
    tile ``j`` set to exact zeros."""
    col = jax.lax.broadcasted_iota(jnp.int32, (1, sub * block_n), 1)
    keep = jnp.zeros((1, sub * block_n), jnp.int32)
    for s in range(sub):
        inside = (col >= s * block_n) & (col < (s + 1) * block_n)
        keep = jnp.where(inside, mask_ref[j * sub + s], keep)
    return jnp.where(keep > 0, v, jnp.zeros_like(v))


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _emit(o_ref, scratch, r, nr: int, sub: int, flags, product, finish):
    """Accumulate ``product(partial)`` over the contraction steps whose
    tile is kept and write ``finish(total, partial)`` at the last.

    ``flags`` = (any, all) kept of the tile the mask governs; ``partial``
    (static) is true in the branch where some but not all of its
    sub-blocks are kept, which exists only when a tile holds several
    (``sub`` > 1).  ``finish`` None means the flags are the contraction's
    (dx): the total is written as it is.  A tile never kept writes zeros;
    one contraction step writes the product straight out.
    """
    kept_any, kept_all = flags
    cases = [(kept_all, False)]
    if sub > 1:
        cases.append((jnp.logical_and(kept_any, jnp.logical_not(kept_all)),
                      True))
    finish = finish or (lambda v, partial: v)

    def on_kept(body, extra=True):
        for when, partial in cases:
            pl.when(jnp.logical_and(extra, when))(
                functools.partial(body, partial))

    def write(v, partial):
        o_ref[...] = finish(v, partial).astype(o_ref.dtype)

    if nr == 1:
        on_kept(lambda partial: write(product(partial), partial))

        @pl.when(jnp.logical_not(kept_any))
        def _zero():
            o_ref[...] = jnp.zeros_like(o_ref)
        return

    acc = scratch[0]
    last = r == nr - 1

    @pl.when(r == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    def mac(partial):
        acc[...] += product(partial)

    on_kept(mac)
    on_kept(lambda partial: write(acc[...], partial), last)

    @pl.when(jnp.logical_and(last, jnp.logical_not(kept_any)))
    def _store_unkept():
        write(acc[...], False)


def _fwd_kernel(mask_ref, x_ref, w_ref, o_ref, *scratch, nk, sub, block_n):
    """Forward: o[i, j] = sum_k x[i, k] @ w[k, j]; grid (M/tm, N/tn, K/tk).
    Column tile j is skipped when all its mask entries are 0, and its
    pruned sub-blocks are written as exact zeros."""
    j, k = pl.program_id(1), pl.program_id(2)
    _emit(o_ref, scratch, k, nk, sub, _kept(mask_ref, j, sub),
          lambda partial: _dot(x_ref[...], w_ref[...], _NN),
          lambda v, partial: (_zero_pruned(v, mask_ref, j, sub, block_n)
                              if partial else v))


def _dx_kernel(mask_ref, dy_ref, w_ref, dx_ref, *scratch, nn, sub, block_n):
    """Backward-x: dx[i, j] = sum_n dy[i, n] @ w[j, n].T; grid (M/tm, K/tk,
    N/tn).  Contraction tile n is skipped when wholly pruned; inside a
    partly kept one, dy's pruned sub-blocks are zeroed before the dot."""
    n = pl.program_id(2)

    def product(partial):
        dy = dy_ref[...]
        if partial:
            dy = _zero_pruned(dy, mask_ref, n, sub, block_n)
        return _dot(dy, w_ref[...], _NT)

    _emit(dx_ref, scratch, n, nn, sub, _kept(mask_ref, n, sub), product,
          None)


def _dw_kernel(mask_ref, x_ref, dy_ref, dw_ref, *scratch, nm, sub, block_n):
    """Backward-w: dw[i, j] = sum_m x[m, i].T @ dy[m, j]; grid (K/tk, N/tn,
    M/tm).  Column tile j is skipped when wholly pruned, and pruned
    sub-blocks are written as EXACT zeros."""
    j, m = pl.program_id(1), pl.program_id(2)
    _emit(dw_ref, scratch, m, nm, sub, _kept(mask_ref, j, sub),
          lambda partial: _dot(x_ref[...], dy_ref[...], _TN),
          lambda v, partial: (_zero_pruned(v, mask_ref, j, sub, block_n)
                              if partial else v))


# ---------------------------------------------------------------------------
# pallas_call wrappers (cfg = (tiles, block_n, interpret))
#
# The block mask is a scalar-prefetch operand: it lands in SMEM whole, and
# each grid step reads its tile's entries by program id.  (A rank-1 (1,)
# VMEM block is refused by the TPU lowering, which tiles rank-1 blocks in
# 128s.)
# ---------------------------------------------------------------------------

def _call(kernel, name, grid, in_specs, out_spec, out_shape, acc_shape,
          interpret, block_mask, *operands):
    keep = (block_mask > 0).astype(jnp.int32)
    scratch = [pltpu.VMEM(acc_shape, jnp.float32)] if grid[2] > 1 else []
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=grid, in_specs=in_specs,
            out_specs=out_spec, scratch_shapes=scratch),
        out_shape=out_shape,
        interpret=interpret,
        name=name,
    )(keep, *operands)


def _statics(cfg, kind):
    """One kernel's (tm, tk, tn), its static keywords, and interpret."""
    tiles, block_n, interpret = cfg
    tile = getattr(tiles, kind)
    return tile, dict(sub=tile[2] // block_n, block_n=block_n), interpret


def _fwd_call(cfg, x, w, block_mask):
    (tm, tk, tn), kw, interpret = _statics(cfg, "fwd")
    m, kdim = x.shape
    n = w.shape[1]
    grid = (m // tm, n // tn, kdim // tk)
    return _call(
        functools.partial(_fwd_kernel, nk=grid[2], **kw), "masked_matmul_fwd",
        grid,
        [pl.BlockSpec((tm, tk), lambda i, j, k, _: (i, k)),
         pl.BlockSpec((tk, tn), lambda i, j, k, _: (k, j))],
        pl.BlockSpec((tm, tn), lambda i, j, k, _: (i, j)),
        jax.ShapeDtypeStruct((m, n), x.dtype), (tm, tn), interpret,
        block_mask, x, w)


def _dx_call(cfg, dy, w, block_mask):
    (tm, tk, tn), kw, interpret = _statics(cfg, "dx")
    m, n = dy.shape
    kdim = w.shape[0]
    grid = (m // tm, kdim // tk, n // tn)
    return _call(
        functools.partial(_dx_kernel, nn=grid[2], **kw), "masked_matmul_dx",
        grid,
        [pl.BlockSpec((tm, tn), lambda i, j, k, _: (i, k)),
         pl.BlockSpec((tk, tn), lambda i, j, k, _: (j, k))],
        pl.BlockSpec((tm, tk), lambda i, j, k, _: (i, j)),
        jax.ShapeDtypeStruct((m, kdim), dy.dtype), (tm, tk), interpret,
        block_mask, dy, w)


def _dw_call(cfg, x, dy, block_mask):
    (tm, tk, tn), kw, interpret = _statics(cfg, "dw")
    m, kdim = x.shape
    n = dy.shape[1]
    grid = (kdim // tk, n // tn, m // tm)
    return _call(
        functools.partial(_dw_kernel, nm=grid[2], **kw), "masked_matmul_dw",
        grid,
        [pl.BlockSpec((tm, tk), lambda i, j, k, _: (k, i)),
         pl.BlockSpec((tm, tn), lambda i, j, k, _: (k, j))],
        pl.BlockSpec((tk, tn), lambda i, j, k, _: (i, j)),
        jax.ShapeDtypeStruct((kdim, n), x.dtype), (tk, tn), interpret,
        block_mask, x, dy)


# ---------------------------------------------------------------------------
# custom VJP
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _masked_matmul(cfg, x, w, block_mask):
    return _fwd_call(cfg, x, w, block_mask)


def _masked_matmul_fwd(cfg, x, w, block_mask):
    return _fwd_call(cfg, x, w, block_mask), (x, w, block_mask)


def _masked_matmul_bwd(cfg, residuals, dy):
    x, w, block_mask = residuals
    dx = _dx_call(cfg, dy, w, block_mask)
    dw = _dw_call(cfg, x, dy, block_mask)
    return dx, dw, jnp.zeros_like(block_mask)


_masked_matmul.defvjp(_masked_matmul_fwd, _masked_matmul_bwd)


# ---------------------------------------------------------------------------
# public entry point
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def masked_matmul(x, w, block_mask, *, block_n: int = 128,
                  interpret: bool = False):
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
    if m % 8 or n % block_n:
        raise ValueError(
            f"masked_matmul shapes must be tile-aligned: x.shape={x.shape} "
            f"w.shape={w.shape} need M % 8 == 0 and N % block_n == 0 "
            f"(block_n={block_n}); pad M (see "
            f"repro.models.layers.masked_dense)")
    block_mask = jnp.asarray(block_mask, jnp.float32)
    if block_mask.shape != (n // block_n,):
        raise ValueError(
            f"masked_matmul block_mask must have shape (N // block_n,) = "
            f"({n // block_n},), got {block_mask.shape} for w.shape={w.shape} "
            f"block_n={block_n}")
    tiles = choose_tiles(m, kdim, n, x.dtype, block_n=block_n)
    return _masked_matmul((tiles, block_n, interpret), x, w, block_mask)
