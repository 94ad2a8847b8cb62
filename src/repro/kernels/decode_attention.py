"""Flash-decode (TPU Pallas): single-token GQA attention vs. a KV cache.

One new query token per sequence attends over a [S, hd] cache per kv head.
Grid (batch, kv_heads, kv_blocks): each kv head processes its G grouped
query heads at once (q block [G, hd] — rows = grouped heads, MXU-friendly),
with the online-softmax state in VMEM scratch persisting over kv blocks.

This is the decode_32k / long_500k hot loop: memory-bound (the whole cache
streams through VMEM once), so block_k is chosen large (512) to amortize
grid overhead against the 819 GB/s HBM stream.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
                   *, sm_scale: float, num_kv_blocks: int, block_k: int):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)             # [G, hd]
    k = k_ref[0, 0].astype(jnp.float32)             # [bk, hd]
    v = v_ref[0, 0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * sm_scale  # [G, bk]

    # Continuous batching: only this sequence's first ``lengths[b]`` cache
    # slots are valid (later slots belong to a PREVIOUS occupant of the
    # decode slot, or were never written).
    pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
    valid = pos < len_ref[pl.program_id(0)]          # [1, bk]
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    # Re-mask after the exp: with m_new == NEG_INF (no valid slot seen yet)
    # exp(NEG_INF - NEG_INF) == 1 would credit masked slots with softmax
    # mass; the where keeps l/acc exactly zero until a valid block arrives.
    p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())))
    m_scr[...] = m_new

    @pl.when(ki == num_kv_blocks - 1)
    def _finish():
        o_ref[0, 0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def decode_attention(q, k, v, lengths=None, *, block_k: int = 512,
                     interpret: bool = False):
    """q [B, 1, H, hd]; k/v cache [B, S, KV, hd] -> [B, 1, H, hd].

    ``lengths`` (optional, int32 [B]) is the per-sequence count of valid
    cache slots: slot ``i`` is attended iff ``i < lengths[b]`` — the
    continuous-batching contract, where each decode slot's cache page
    holds a different request at a different fill level.  Without it all
    S slots are attended (ring-buffer serving, every slot valid).
    Requires S % block_k == 0.
    """
    b, one, h, hd = q.shape
    s_len, kvh = k.shape[1], k.shape[2]
    if one != 1:
        raise ValueError(
            f"decode_attention: q must carry a single decode step, got "
            f"q {q.shape} (expected [B, 1, H, hd])")
    if h % kvh != 0:
        raise ValueError(
            f"decode_attention: query heads H={h} must be a multiple of "
            f"kv heads KV={kvh} (q {q.shape}, k {k.shape})")
    if s_len % block_k != 0:
        raise ValueError(
            f"decode_attention: cache length S={s_len} must be a multiple "
            f"of block_k={block_k} (k {k.shape})")
    if lengths is not None and lengths.shape != (b,):
        raise ValueError(
            f"decode_attention: lengths must be [B]={b} valid-slot counts, "
            f"got {lengths.shape}")
    g = h // kvh
    sm_scale = 1.0 / math.sqrt(hd)
    nk = s_len // block_k

    # head h = g_idx * KV + kv  ->  group by kv head: [B, KV, G, hd]
    qt = q[:, 0].reshape(b, g, kvh, hd).transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)                    # [B, KV, S, hd]
    vt = v.transpose(0, 2, 1, 3)
    # No lengths -> every slot valid; an S-filled vector keeps the kernel
    # single-program (the mask where() is the identity at full length).
    lens = (jnp.full((b,), s_len, jnp.int32) if lengths is None
            else lengths.astype(jnp.int32))

    kernel = functools.partial(_decode_kernel, sm_scale=sm_scale,
                               num_kv_blocks=nk, block_k=block_k)

    # ``lens`` is a scalar-prefetch operand (whole in SMEM, read by program
    # id): the TPU lowering refuses a rank-1 (1,) VMEM block.
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, kvh, nk),
            in_specs=[
                pl.BlockSpec((1, 1, g, hd),
                             lambda b_, j_, k_, _: (b_, j_, 0, 0)),
                pl.BlockSpec((1, 1, block_k, hd),
                             lambda b_, j_, k_, _: (b_, j_, k_, 0)),
                pl.BlockSpec((1, 1, block_k, hd),
                             lambda b_, j_, k_, _: (b_, j_, k_, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, g, hd),
                                   lambda b_, j_, k_, _: (b_, j_, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((g, 1), jnp.float32),
                pltpu.VMEM((g, 1), jnp.float32),
                pltpu.VMEM((g, hd), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((b, kvh, g, hd), q.dtype),
        interpret=interpret,
        name="decode_attention",
    )(lens, qt, kt, vt)
    # [B, KV, G, hd] -> [B, 1, H, hd] with h = g_idx * KV + kv
    return out.transpose(0, 2, 1, 3).reshape(b, 1, h, hd)
