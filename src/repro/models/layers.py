"""Transformer / SSM / MoE building blocks, pure-JAX functional.

Conventions
-----------
* Every ``init_*`` returns ``(params, axes)`` — two pytrees of identical
  structure.  ``axes`` holds *logical* axis-name tuples per tensor
  (e.g. ``("embed", "heads", "head_dim")``); ``repro.sharding.specs`` maps
  them to mesh axes (TP over 'model', optional FSDP over 'data').
* Compute dtype = input dtype (bf16 on TPU); numerics-critical reductions
  (softmax, norms, rope, SSM state) run in f32.
* ``attn_impl``: 'xla' (jnp reference; what the dry-run lowers) or
  'pallas' (the kernels in repro.kernels; validated in interpret mode).
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig


# ---------------------------------------------------------------------------
# initializers
# ---------------------------------------------------------------------------

def _normal(rng, shape, scale, dtype):
    return (jax.random.normal(rng, shape, jnp.float32) * scale).astype(dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def init_norm(cfg: ModelConfig, dtype):
    if cfg.norm == "rmsnorm":
        return {"scale": jnp.ones((cfg.d_model,), dtype)}, {"scale": ("embed",)}
    if cfg.norm == "layernorm":
        return (
            {"scale": jnp.ones((cfg.d_model,), dtype), "bias": jnp.zeros((cfg.d_model,), dtype)},
            {"scale": ("embed",), "bias": ("embed",)},
        )
    if cfg.norm == "nonparam":      # OLMo: no learnable affine
        return {}, {}
    raise ValueError(cfg.norm)


def apply_norm(params, x, kind: str, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    if kind == "rmsnorm":
        y = xf * jax.lax.rsqrt(jnp.mean(jnp.square(xf), -1, keepdims=True) + eps)
        return (y * params["scale"].astype(jnp.float32)).astype(x.dtype)
    mean = jnp.mean(xf, -1, keepdims=True)
    var = jnp.var(xf, -1, keepdims=True)
    y = (xf - mean) * jax.lax.rsqrt(var + eps)
    if kind == "layernorm":
        y = y * params["scale"].astype(jnp.float32) + params["bias"].astype(jnp.float32)
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# rotary embeddings: 1d / GLM-2d / M-RoPE
# ---------------------------------------------------------------------------

def _rope_angles(positions: jnp.ndarray, dim: int, base: float = 10000.0):
    """positions [..., S] -> (sin, cos) [..., S, dim//2] in f32."""
    freqs = 1.0 / (base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.sin(ang), jnp.cos(ang)


def _rotate(x, sin, cos):
    """x [..., dim] with interleaved-pairs rotation (dim even)."""
    x1, x2 = x[..., ::2], x[..., 1::2]
    y1 = x1 * cos - x2 * sin
    y2 = x2 * cos + x1 * sin
    return jnp.stack([y1, y2], axis=-1).reshape(x.shape)


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, kind: str) -> jnp.ndarray:
    """x: [B, S, n, head_dim].  positions: [P, B, S] with
    P=1 (1d), P=2 (GLM 2d), P=3 (M-RoPE temporal/height/width)."""
    if kind == "none":
        return x
    hd = x.shape[-1]
    xf = x.astype(jnp.float32)
    if kind == "1d":
        sin, cos = _rope_angles(positions[0], hd)
        out = _rotate(xf, sin[:, :, None, :], cos[:, :, None, :])
    elif kind == "2d":
        # GLM: first half of head_dim rotated by stream 0, second by stream 1.
        h = hd // 2
        s0, c0 = _rope_angles(positions[0], h)
        s1, c1 = _rope_angles(positions[1], h)
        out = jnp.concatenate([
            _rotate(xf[..., :h], s0[:, :, None, :], c0[:, :, None, :]),
            _rotate(xf[..., h:], s1[:, :, None, :], c1[:, :, None, :]),
        ], axis=-1)
    elif kind == "mrope":
        # Qwen2-VL: head_dim split into 3 sections (t, h, w).
        sec = [hd // 2, hd // 4, hd - hd // 2 - hd // 4]
        parts, off = [], 0
        for i, s in enumerate(sec):
            si, ci = _rope_angles(positions[i], s)
            parts.append(_rotate(xf[..., off:off + s], si[:, :, None, :], ci[:, :, None, :]))
            off += s
        out = jnp.concatenate(parts, axis=-1)
    else:
        raise ValueError(kind)
    return out.astype(x.dtype)


def default_positions(batch: int, seq: int, kind: str, offset: int = 0) -> jnp.ndarray:
    p = {"none": 1, "1d": 1, "2d": 2, "mrope": 3}[kind]
    pos = jnp.arange(seq, dtype=jnp.int32)[None, :] + offset
    return jnp.broadcast_to(pos, (p, batch, seq)).astype(jnp.int32)


# ---------------------------------------------------------------------------
# attention (GQA, causal, optional sliding window) + KV-cache decode
# ---------------------------------------------------------------------------

def init_attention(rng, cfg: ModelConfig, dtype):
    d, h, kv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    # §Perf C1 (MaxText-style head padding): head counts that do not divide
    # the 16-way TP axis (56, 28, 40, 12) force full attention replication.
    # Under REPRO_PAD_HEADS=N, pad H up to a multiple of N with DEAD heads
    # (zero wo rows -> exactly zero contribution; fwd/bwd semantics of live
    # heads unchanged) so every attention tensor shards evenly.
    import os as _o
    env_pad = int(_o.environ.get("REPRO_PAD_HEADS", "0"))
    if env_pad or cfg.pad_heads_to:
        pad = env_pad or cfg.pad_heads_to
        if h % pad:
            h = (h + pad - 1) // pad * pad
        if h % kv:
            kv = h                      # MHA archs pad KV alongside Q
    ks = jax.random.split(rng, 4)
    s = 1.0 / math.sqrt(d)
    h_live = cfg.num_heads
    wo = _normal(ks[3], (h, hd, d), 1.0 / math.sqrt(h_live * hd), dtype)
    if h != h_live:
        wo = wo.at[h_live:].set(0.0)
    params = {
        "wq": _normal(ks[0], (d, h, hd), s, dtype),
        "wk": _normal(ks[1], (d, kv, hd), s, dtype),
        "wv": _normal(ks[2], (d, kv, hd), s, dtype),
        "wo": wo,
    }
    axes = {
        "wq": ("embed", "heads", "head_dim"),
        "wk": ("embed", "kv_heads", "head_dim"),
        "wv": ("embed", "kv_heads", "head_dim"),
        "wo": ("heads", "head_dim", "embed"),
    }
    return params, axes


def _gqa_scores_mask(q_len, kv_len, *, causal, window, q_offset):
    """Additive mask [q_len, kv_len] in f32 (0 or -inf)."""
    qpos = jnp.arange(q_len)[:, None] + q_offset
    kpos = jnp.arange(kv_len)[None, :]
    ok = jnp.ones((q_len, kv_len), bool)
    if causal:
        ok &= kpos <= qpos
    if window is not None:
        ok &= kpos > qpos - window
    return jnp.where(ok, 0.0, -jnp.inf).astype(jnp.float32)


def attention_ref(q, k, v, *, causal=True, window=None, q_offset=0):
    """Reference GQA attention.  q [B,Sq,H,hd], k/v [B,Skv,KV,hd].

    Head grouping uses the [g, kv] order (head h = g * KV + kv): a 16-way
    shard of the H axis then maps EXACTLY onto the g dim after the GQA
    reshape, so GSPMD keeps the scores tensor head-sharded.  The [kv, g]
    order (contiguous shards straddling kv groups) forces GSPMD to
    replicate the group dim — 16x the memory (measured; see EXPERIMENTS.md
    §Perf)."""
    b, sq, h, hd = q.shape
    kvh = k.shape[2]
    g = h // kvh
    qg = q.reshape(b, sq, g, kvh, hd)
    scores = jnp.einsum("bqgkd,bskd->bgkqs", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) / math.sqrt(hd)
    scores = scores + _gqa_scores_mask(sq, k.shape[1], causal=causal,
                                       window=window, q_offset=q_offset)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bgkqs,bskd->bqgkd", w, v.astype(jnp.float32))
    return out.reshape(b, sq, h, hd).astype(q.dtype)


# --- §Perf knobs (defaults = paper-faithful baseline; the hillclimb flips
# them and records before/after in EXPERIMENTS.md §Perf) -------------------
import os as _os

# Query-chunk size above which attention switches to the blocked
# (flash-style, O(S * block) memory) XLA implementation.
_BLOCK_Q = int(_os.environ.get("REPRO_ATTN_BLOCK_Q", "1024"))

# 'blocked'  — scan over q blocks, every block sees ALL kv (baseline)
# 'tree'     — binary-tree causal decomposition: strictly-lower rectangles
#              are computed unmasked and merged via logsumexp, so causal
#              attention does ~S^2/2 work with fully static shapes.
ATTN_MODE = _os.environ.get("REPRO_ATTN_MODE", "tree")
# cast softmax probabilities to bf16 for the P @ V matmul (flash-standard)
P_BF16 = bool(int(_os.environ.get("REPRO_ATTN_P_BF16", "0")))
# repeat K/V to full MHA before attending: heads that do not divide the TP
# axis (56, 28, 40, 12...) can then be PADDED-sharded by GSPMD instead of
# replicated — §Perf iteration C1 (see sharding/specs.py PAD_HEADS).
REPEAT_KV = bool(int(_os.environ.get("REPRO_ATTN_REPEAT_KV", "0")))


def _maybe_repeat_kv(q, k, v):
    if REPEAT_KV and k.shape[2] != q.shape[2]:
        g = q.shape[2] // k.shape[2]
        k = jnp.tile(k, (1, 1, g, 1))    # [g, kv] order: q head h -> kv h %% KV
        v = jnp.tile(v, (1, 1, g, 1))
    return k, v


def _attention_lse(q, k, v, *, causal, window, q_offset):
    """attention_ref that also returns the log-sum-exp [B, H, Sq] needed to
    merge partial attentions over disjoint kv sets."""
    b, sq, h, hd = q.shape
    kvh = k.shape[2]
    g = h // kvh
    qg = q.reshape(b, sq, g, kvh, hd)
    scores = jnp.einsum("bqgkd,bskd->bgkqs", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) / math.sqrt(hd)
    scores = scores + _gqa_scores_mask(sq, k.shape[1], causal=causal,
                                       window=window, q_offset=q_offset)
    m = jnp.max(scores, axis=-1, keepdims=True)
    m = jnp.maximum(m, -1e30)                       # rows with no valid kv
    p = jnp.exp(scores - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    if P_BF16:
        p = p.astype(jnp.bfloat16)
    out = jnp.einsum("bgkqs,bskd->bqgkd", p,
                     v.astype(p.dtype)).astype(jnp.float32)
    denom = jnp.clip(l[..., 0], 1e-30, None).transpose(0, 3, 1, 2)  # [b,sq,g,kv]
    out = out / denom[..., None]
    lse = (m + jnp.log(jnp.clip(l, 1e-30, None)))[..., 0]      # [b,g,kv,sq]
    return (out.reshape(b, sq, h, hd),
            lse.transpose(0, 3, 1, 2).reshape(b, sq, h))


def _attention_lse_any(q, k, v, *, causal, window, q_offset,
                       block_q: int = _BLOCK_Q):
    """(out, lse) with q-block scanning when Sq is large (O(bq * Skv) peak)."""
    sq = q.shape[1]
    if sq <= block_q or sq % block_q:
        return _attention_lse(q, k, v, causal=causal, window=window,
                              q_offset=q_offset)
    b, _, h, hd = q.shape
    nblk = sq // block_q
    qb = q.reshape(b, nblk, block_q, h, hd).transpose(1, 0, 2, 3, 4)

    @partial(jax.checkpoint, prevent_cse=False)
    def one(i, q_blk):
        return _attention_lse(q_blk, k, v, causal=causal, window=window,
                              q_offset=q_offset + i * block_q)

    def body(_, args):
        i, q_blk = args
        return None, one(i, q_blk)

    _, (out, lse) = jax.lax.scan(body, None, (jnp.arange(nblk), qb))
    return (out.transpose(1, 0, 2, 3, 4).reshape(b, sq, h, hd),
            lse.transpose(1, 0, 2, 3).reshape(b, sq, h))


def _merge_partial(parts):
    """Merge [(out [B,S,H,hd] f32, lse [B,S,H])] over disjoint kv sets."""
    outs, lses = zip(*parts)
    lse_tot = lses[0]
    for l in lses[1:]:
        lse_tot = jnp.logaddexp(lse_tot, l)
    acc = jnp.zeros_like(outs[0])
    for o, l in zip(outs, lses):
        acc = acc + o * jnp.exp(l - lse_tot)[..., None]
    return acc, lse_tot


def _attention_tree(q, k, v, *, leaf: int):
    """Binary-tree causal attention: ~S^2/2 FLOPs with static shapes.

    causal(q, kv) = merge( causal(q_hi, kv_hi) + FULL(q_hi, kv_lo),
                           causal(q_lo, kv_lo) )
    The off-diagonal rectangle is UNMASKED (every key is in the past of
    every query), so no wasted masked compute — the XLA-level analogue of
    a triangular kernel grid.  Every causal sub-call sees ALIGNED q/kv
    slices, so the relative offset is always 0.  Returns (out f32, lse).
    """
    s = q.shape[1]
    if s <= leaf or s % 2:
        return _attention_lse_any(q, k, v, causal=True, window=None,
                                  q_offset=0)
    half = s // 2
    lo = _attention_tree(q[:, :half], k[:, :half], v[:, :half], leaf=leaf)
    hi_diag = _attention_tree(q[:, half:], k[:, half:], v[:, half:], leaf=leaf)
    hi_rect = _attention_lse_any(q[:, half:], k[:, :half], v[:, :half],
                                 causal=False, window=None, q_offset=0)
    hi = _merge_partial([hi_diag, hi_rect])
    return (jnp.concatenate([lo[0], hi[0]], axis=1),
            jnp.concatenate([lo[1], hi[1]], axis=1))


def attention_blocked(q, k, v, *, causal=True, window=None, q_offset=0,
                      block_q: int = _BLOCK_Q):
    """Blocked attention: lax.scan over query chunks, scores recomputed in
    the backward pass (jax.checkpoint) — the XLA-level analogue of flash
    attention.  Peak memory O(B * H * block_q * S_kv) instead of O(S^2)."""
    b, sq, h, hd = q.shape
    if sq % block_q != 0:
        return attention_ref(q, k, v, causal=causal, window=window,
                             q_offset=q_offset)
    nblk = sq // block_q
    qb = q.reshape(b, nblk, block_q, h, hd).transpose(1, 0, 2, 3, 4)

    @partial(jax.checkpoint, prevent_cse=False)
    def one_block(i, q_blk):
        return attention_ref(q_blk, k, v, causal=causal, window=window,
                             q_offset=q_offset + i * block_q)

    def body(_, args):
        i, q_blk = args
        return None, one_block(i, q_blk)

    _, out = jax.lax.scan(body, None, (jnp.arange(nblk), qb))
    return out.transpose(1, 0, 2, 3, 4).reshape(b, sq, h, hd)


def attention(q, k, v, *, causal=True, window=None, q_offset=0):
    """Dispatch: tree (§Perf) / blocked for long sequences, direct for
    short ones."""
    k, v = _maybe_repeat_kv(q, k, v)
    if (ATTN_MODE == "tree" and causal and window is None and q_offset == 0
            and q.shape[1] == k.shape[1] and q.shape[1] > _BLOCK_Q):
        out, _ = _attention_tree(q, k, v, leaf=2 * _BLOCK_Q)
        return out.astype(q.dtype)
    if q.shape[1] > _BLOCK_Q:
        return attention_blocked(q, k, v, causal=causal, window=window,
                                 q_offset=q_offset)
    return attention_ref(q, k, v, causal=causal, window=window, q_offset=q_offset)


def attention_block(params, x, positions, cfg: ModelConfig, *,
                    window=None, attn_impl="xla", cross_kv=None):
    """Full attention sub-block: qkv proj, rope, attend, out proj.

    cross_kv: optional (k, v) from an encoder (whisper decoder cross-attn);
    rope and causality are skipped for cross attention.
    """
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    pad_active = bool(cfg.pad_heads_to) or REPEAT_KV
    if cross_kv is None:
        k = jnp.einsum("bsd,dhk->bshk", x, params["wk"])
        v = jnp.einsum("bsd,dhk->bshk", x, params["wv"])
        q = apply_rope(q, positions, cfg.rope)
        k = apply_rope(k, positions, cfg.rope)
        if pad_active and k.shape[2] != q.shape[2]:
            # padded heads: TILE K/V to MHA so the (padded) head axis
            # shards evenly over the TP mesh axis (§Perf C1).  TILE (not
            # repeat): the [g, kv] GQA ordering maps q head h -> kv head
            # h %% KV, which tiling reproduces exactly (decode parity).
            g = q.shape[2] // k.shape[2]
            k = jnp.tile(k, (1, 1, g, 1))
            v = jnp.tile(v, (1, 1, g, 1))
        causal = True
    else:
        k, v = cross_kv
        causal = False
    if attn_impl == "pallas":
        from repro.kernels import ops as kops
        out = kops.flash_attention(q, k, v, causal=causal, window=window)
    else:
        out = attention(q, k, v, causal=causal, window=window)
    return jnp.einsum("bshk,hkd->bsd", out, params["wo"])


def attention_decode(params, x, cache_k, cache_v, cache_index, positions,
                     cfg: ModelConfig, *, window=None, attn_impl="xla"):
    """One-token decode.  x [B,1,d]; cache [B,S,KV,hd] (ring buffer when
    ``window`` is set and S == window).  Returns (out, new_k, new_v).

    ``cache_index`` is either a scalar (lockstep decode: every sequence at
    the same depth) or an int32 [B] vector (continuous batching: each
    decode slot at its own fill level).  In both cases the new K/V land at
    slot ``index mod S`` and slots ``<= index`` are attended — so a
    freshly admitted request (index reset to 0) never sees the previous
    occupant's stale cache rows: they only become "valid" again after
    being overwritten by the new request."""
    b, _, _ = x.shape
    s_cache = cache_k.shape[1]
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    k_new = jnp.einsum("bsd,dhk->bshk", x, params["wk"])
    v_new = jnp.einsum("bsd,dhk->bshk", x, params["wv"])
    q = apply_rope(q, positions, cfg.rope)
    k_new = apply_rope(k_new, positions, cfg.rope)
    if jnp.ndim(cache_index) == 0:  # lint: static-branch (on ndim, not value)
        slot = jnp.mod(cache_index, s_cache)
        cache_k = jax.lax.dynamic_update_slice_in_dim(cache_k, k_new, slot,
                                                      axis=1)
        cache_v = jax.lax.dynamic_update_slice_in_dim(cache_v, v_new, slot,
                                                      axis=1)
    else:
        # Per-slot write: one-hot select along S (k_new [B,1,KV,hd]
        # broadcasts over it) — exact, and batchable with ragged indices.
        oh = jnp.arange(s_cache)[None, :] == \
            jnp.mod(cache_index, s_cache)[:, None]                  # [B, S]
        cache_k = jnp.where(oh[:, :, None, None], k_new, cache_k)
        cache_v = jnp.where(oh[:, :, None, None], v_new, cache_v)

    h, hd = q.shape[2], q.shape[3]          # shape-driven (head padding)
    kvh = cache_k.shape[2]
    g = h // kvh
    qg = q.reshape(b, 1, g, kvh, hd)
    if attn_impl == "pallas":
        from repro.kernels import ops as kops
        if jnp.ndim(cache_index) == 0:  # lint: static-branch (on ndim)
            # lockstep full/ring caches: every slot valid
            out = kops.decode_attention(q, cache_k, cache_v)
        else:
            # continuous batching: the kernel masks each slot's invalid
            # tail (index + 1 valid slots after this step's write)
            out = kops.decode_attention(q, cache_k, cache_v,
                                        cache_index.astype(jnp.int32) + 1)
    else:
        scores = jnp.einsum("bqgkd,bskd->bgkqs", qg.astype(jnp.float32),
                            cache_k.astype(jnp.float32)) / math.sqrt(hd)
        # Mask slots not yet written (cache filling up).  Once the index
        # passes the cache length (ring-buffer regime) every slot is valid.
        if jnp.ndim(cache_index) == 0:  # lint: static-branch (on ndim)
            valid = jnp.arange(s_cache) <= cache_index
            valid = valid[None, :]
        else:
            valid = jnp.arange(s_cache)[None, :] <= cache_index[:, None]
        scores = jnp.where(valid[:, None, None, None, :], scores, -jnp.inf)
        w = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bgkqs,bskd->bqgkd", w, cache_v.astype(jnp.float32))
        out = out.reshape(b, 1, h, hd).astype(x.dtype)
    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"])
    return y, cache_k, cache_v


def attention_decode_cross(params, x, cross_k, cross_v, cfg: ModelConfig):
    """Cross-attention during decode against fixed encoder K/V."""
    b = x.shape[0]
    h, hd = params["wq"].shape[1], params["wq"].shape[2]
    kvh = cross_k.shape[2]
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    g = h // kvh
    qg = q.reshape(b, 1, g, kvh, hd)
    scores = jnp.einsum("bqgkd,bskd->bgkqs", qg.astype(jnp.float32),
                        cross_k.astype(jnp.float32)) / math.sqrt(hd)
    w = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bgkqs,bskd->bqgkd", w, cross_v.astype(jnp.float32))
    out = out.reshape(b, 1, h, hd).astype(x.dtype)
    return jnp.einsum("bshk,hkd->bsd", out, params["wo"])


# ---------------------------------------------------------------------------
# MLP (SwiGLU / GELU)
# ---------------------------------------------------------------------------

def init_mlp(rng, d_model: int, d_ff: int, act: str, dtype):
    ks = jax.random.split(rng, 3)
    s_in, s_out = 1.0 / math.sqrt(d_model), 1.0 / math.sqrt(d_ff)
    if act == "silu":
        params = {
            "wi": _normal(ks[0], (d_model, d_ff), s_in, dtype),
            "wg": _normal(ks[1], (d_model, d_ff), s_in, dtype),
            "wo": _normal(ks[2], (d_ff, d_model), s_out, dtype),
        }
        axes = {"wi": ("embed", "mlp"), "wg": ("embed", "mlp"), "wo": ("mlp", "embed")}
    else:
        params = {
            "wi": _normal(ks[0], (d_model, d_ff), s_in, dtype),
            "wo": _normal(ks[2], (d_ff, d_model), s_out, dtype),
        }
        axes = {"wi": ("embed", "mlp"), "wo": ("mlp", "embed")}
    return params, axes


def apply_mlp(params, x, act: str, mask=None):
    if mask is None:
        h = x @ params["wi"]
        if act == "silu":
            h = jax.nn.silu(x @ params["wg"]) * h
        else:
            h = jax.nn.gelu(h)
        return h @ params["wo"]
    # FedAP masked mode: ``mask`` ([d_ff] 0/1) zeroes pruned hidden units
    # at the PRE-activation, so each pruned unit contributes exactly
    # silu(0) = gelu(0) = 0 through wo — identical logits to structurally
    # shrinking the stack.  The up/gate matmuls route through
    # :func:`masked_dense`: when d_model and d_ff are 128-aligned the
    # Pallas masked_matmul kernel SKIPS fully-pruned column blocks, so the
    # FedAP FLOP savings are realized at static shapes; wo stays dense
    # (its pruned K rows already multiply exact zeros).
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    h = masked_dense(x2, params["wi"], mask)
    if act == "silu":
        h = jax.nn.silu(masked_dense(x2, params["wg"], mask)) * h
    else:
        h = jax.nn.gelu(h)
    return (h @ params["wo"]).reshape(shape)


def masked_dense(x, w, mask, b=None, *, block: int = 128):
    """Dense layer ``x @ w (+ b)`` with an output-filter keep-mask.

    When the feature dimensions K and N are multiples of ``block`` the
    matmul routes through the Pallas ``masked_matmul`` kernel: column
    blocks whose mask is entirely zero are SKIPPED on the MXU, so
    structured pruning's FLOP savings are realized at static shapes
    (partially-kept blocks are computed and re-masked elementwise — exact
    for 0/1 masks).  The batch dimension M does NOT gate the kernel: real
    batch sizes (10, 32) are zero-padded up to the 8-row sublane multiple
    (the kernel chooses its tiles from the padded shape) and the result
    sliced back, so the kernel path is live in training and
    serving alike.  Unaligned K/N fall back to masking the XLA matmul.

    The kernel carries a ``jax.custom_vjp`` whose backward Pallas kernels
    skip the same pruned blocks (and write exact-zero ``dw`` blocks), so
    this routing is differentiable — the training engine uses it via
    ``EngineConfig.masked_compute="kernel"``.  Shared by the CNN dense
    heads (repro.models.cnn) and the LM FFN stacks (:func:`apply_mlp`).
    """
    m, k = x.shape
    n = w.shape[-1]
    if k % block == 0 and n % block == 0:
        from repro.kernels.ops import masked_matmul
        block_mask = jnp.max(mask.reshape(n // block, block), axis=1)
        # Only the LANE dims (K, N) need the mask-granularity block; the
        # sublane dim M pads to the next 8-row multiple (<= 7 wasted rows
        # for ANY batch size, never a full ``block`` rows).  The kernel
        # picks its tiles from the padded shapes.
        m_pad = -m % 8
        xp = jnp.pad(x, ((0, m_pad), (0, 0))) if m_pad else x
        y = masked_matmul(xp, w, block_mask, block_n=block)
        if m_pad:
            y = y[:m]
    else:
        y = x @ w
    if b is not None:
        y = y + b
    # the 0/1 mask is f32; multiplying in y's dtype keeps bf16 activations
    # bf16 (promotion would change the layer scan's carry dtype)
    return y * mask.astype(y.dtype)


# ---------------------------------------------------------------------------
# MoE (token-choice top-k, capacity-bounded, expert-parallel friendly)
# ---------------------------------------------------------------------------

def init_moe(rng, cfg: ModelConfig, dtype):
    m = cfg.moe
    d, e, f = cfg.d_model, m.num_experts, m.expert_d_ff
    ks = jax.random.split(rng, 6)
    s_in, s_out = 1.0 / math.sqrt(d), 1.0 / math.sqrt(f)
    params = {
        "router": _normal(ks[0], (d, e), s_in, jnp.float32),
        "wi": _normal(ks[1], (e, d, f), s_in, dtype),
        "wg": _normal(ks[2], (e, d, f), s_in, dtype),
        "wo": _normal(ks[3], (e, f, d), s_out, dtype),
    }
    axes = {
        "router": ("embed", None),
        "wi": ("experts", "embed", "expert_mlp"),
        "wg": ("experts", "embed", "expert_mlp"),
        "wo": ("experts", "expert_mlp", "embed"),
    }
    if m.dense_d_ff:
        dp, da = init_mlp(ks[4], d, m.dense_d_ff, cfg.act, dtype)
        params["dense"], axes["dense"] = dp, da
    if m.shared_expert:
        sp, sa = init_mlp(ks[5], d, m.expert_d_ff, cfg.act, dtype)
        params["shared"], axes["shared"] = sp, sa
    return params, axes


def apply_moe(params, x, cfg: ModelConfig):
    """Capacity-bounded token-choice routing.

    Per expert, the top-C tokens by gate weight are gathered ([E, C]
    indices), run through the expert FFN, and scatter-added back.  This is
    the memory-feasible dual of the GShard one-hot dispatch: the [T, E, C]
    dispatch tensor is never materialized.  Overflowing tokens are dropped
    (their residual path still carries them — standard token-dropping MoE).

    Returns (y, aux_losses dict).
    """
    m = cfg.moe
    b, s, d = x.shape
    t = b * s
    xt = x.reshape(t, d)
    logits = (xt.astype(jnp.float32) @ params["router"])          # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    topv, topi = jax.lax.top_k(probs, m.top_k)                    # [T, k]
    # gate matrix: prob if expert selected else 0
    gate = jnp.zeros_like(probs).at[jnp.arange(t)[:, None], topi].set(topv)  # [T,E]

    num_experts = params["router"].shape[-1]      # may be FedAP-pruned
    cap = max(1, min(t, int(t * m.top_k * m.capacity_factor / num_experts)))
    # per-expert top-C token selection
    sel_gate, sel_idx = jax.lax.top_k(gate.T, cap)                # [E, C]
    xe = xt[sel_idx]                                              # [E, C, d]
    h = jnp.einsum("ecd,edf->ecf", xe, params["wi"])
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, params["wg"])) * h
    ye = jnp.einsum("ecf,efd->ecd", h, params["wo"])              # [E, C, d]
    ye = ye * sel_gate[..., None].astype(ye.dtype)
    y = jnp.zeros((t, d), ye.dtype).at[sel_idx.reshape(-1)].add(
        ye.reshape(-1, d))

    # aux losses (Switch/GShard style)
    me = jnp.mean(probs, axis=0)                                  # [E]
    ce = jnp.mean((gate > 0).astype(jnp.float32), axis=0)         # fraction routed
    aux = {
        "load_balance": num_experts * jnp.sum(me * ce) * m.load_balance_loss,
        "router_z": jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2) * m.router_z_loss,
    }

    y = y.reshape(b, s, d)
    if "shared" in params:
        y = y + apply_mlp(params["shared"], x, cfg.act)
    if "dense" in params:
        y = y + apply_mlp(params["dense"], x, cfg.act)
    return y.astype(x.dtype), aux


# ---------------------------------------------------------------------------
# Mamba2 (SSD) block
# ---------------------------------------------------------------------------

def init_mamba2(rng, cfg: ModelConfig, dtype):
    m = cfg.ssm
    d = cfg.d_model
    d_in = m.expand * d
    nh = m.num_ssm_heads or max(1, d_in // 64)
    p = d_in // nh                      # head dim
    n = m.state_dim
    ks = jax.random.split(rng, 5)
    params = {
        # fused input projection: [z | x | B | C | dt]
        "in_proj": _normal(ks[0], (d, 2 * d_in + 2 * n + nh), 1.0 / math.sqrt(d), dtype),
        "conv": _normal(ks[1], (m.conv_width, d_in + 2 * n), 0.5, dtype),
        "A_log": jnp.zeros((nh,), jnp.float32),
        "D": jnp.ones((nh,), jnp.float32),
        "dt_bias": jnp.zeros((nh,), jnp.float32),
        "norm_scale": jnp.ones((d_in,), dtype),
        "out_proj": _normal(ks[2], (d_in, d), 1.0 / math.sqrt(d_in), dtype),
    }
    axes = {
        "in_proj": ("embed", "ssm_inner"),
        "conv": (None, "ssm_inner"),
        "A_log": (None,),
        "D": (None,),
        "dt_bias": (None,),
        "norm_scale": ("ssm_inner",),
        "out_proj": ("ssm_inner", "embed"),
    }
    meta = {"d_in": d_in, "nh": nh, "p": p, "n": n}
    return params, axes, meta


def _ssd_chunk_scan(xbc_dt, A_log, D, dt_bias, meta, chunk):
    """Chunked SSD scan (ref).  xbc_dt = (x [B,S,nh,p], Bmat [B,S,N],
    Cmat [B,S,N], dt [B,S,nh]).  Returns y [B,S,nh,p].

    Recurrence per head h:  H_t = a_t * H_{t-1} + (dt_t * x_t) B_t^T
                            y_t = C_t H_t + D * x_t
    with a_t = exp(-dt_t * exp(A_log_h)),  H in R^{p x N}.
    """
    x, bmat, cmat, dt = xbc_dt
    bsz, s, nh, p = x.shape
    n = bmat.shape[-1]
    dt = jax.nn.softplus(dt.astype(jnp.float32) + dt_bias)            # [B,S,nh]
    a = jnp.exp(-dt * jnp.exp(A_log))                                 # [B,S,nh]
    xs = x.astype(jnp.float32) * dt[..., None]                        # dt-scaled input

    nchunk = s // chunk
    xs = xs.reshape(bsz, nchunk, chunk, nh, p)
    bm = bmat.astype(jnp.float32).reshape(bsz, nchunk, chunk, n)
    cm = cmat.astype(jnp.float32).reshape(bsz, nchunk, chunk, n)
    al = jnp.log(jnp.clip(a, 1e-20, None)).reshape(bsz, nchunk, chunk, nh)

    def chunk_body(h0, args):
        xs_c, bm_c, cm_c, al_c = args                                 # [B,chunk,...]
        cum = jnp.cumsum(al_c, axis=1)                                # [B,chunk,nh]
        total = cum[:, -1]                                            # [B,nh]
        # intra-chunk (causal) contribution
        # decay from j to i: exp(cum_i - cum_j) for j <= i
        li = cum[:, :, None, :]                                       # [B,i,1,nh]
        lj = cum[:, None, :, :]                                       # [B,1,j,nh]
        mask = jnp.tril(jnp.ones((xs_c.shape[1], xs_c.shape[1])))[None, :, :, None]
        # mask inside exp: j > i exponents are positive-large (inf * 0 = NaN)
        decay = jnp.exp(jnp.where(mask > 0, li - lj, -1e30))          # [B,i,j,nh]
        inner = jnp.einsum("bin,bjn->bij", cm_c, bm_c)                # [B,i,j]
        y_intra = jnp.einsum("bij,bijh,bjhp->bihp", inner, decay, xs_c)
        # contribution of carried state h0 [B,nh,p,n]
        carried = jnp.exp(cum)[..., None, None] * h0[:, None]         # [B,i,nh,p,n]
        y_carry = jnp.einsum("bin,bihpn->bihp", cm_c, carried)
        # new carried state
        decay_to_end = jnp.exp(total[:, None, :] - cum)               # [B,chunk,nh]
        h_new = h0 * jnp.exp(total)[..., None, None] + jnp.einsum(
            "bjhp,bjn,bjh->bhpn", xs_c, bm_c, decay_to_end)
        return h_new, y_intra + y_carry

    h0 = jnp.zeros((bsz, nh, p, n), jnp.float32)
    _, ys = jax.lax.scan(chunk_body, h0,
                         (xs.transpose(1, 0, 2, 3, 4), bm.transpose(1, 0, 2, 3),
                          cm.transpose(1, 0, 2, 3), al.transpose(1, 0, 2, 3)))
    y = ys.transpose(1, 0, 2, 3, 4).reshape(bsz, s, nh, p)
    return (y + x.astype(jnp.float32) * D[:, None]).astype(x.dtype)


def apply_mamba2(params, x, meta, cfg: ModelConfig, *, impl="xla"):
    """Mamba2/SSD mixer.  x [B,S,d] -> [B,S,d]."""
    m = cfg.ssm
    d_in, nh, p, n = meta["d_in"], meta["nh"], meta["p"], meta["n"]
    proj = x @ params["in_proj"]                                      # [B,S,2di+2n+nh]
    z, xi, bmat, cmat, dt = jnp.split(
        proj, [d_in, 2 * d_in, 2 * d_in + n, 2 * d_in + 2 * n], axis=-1)
    # causal depthwise conv over [x | B | C]
    conv_in = jnp.concatenate([xi, bmat, cmat], axis=-1)              # [B,S,di+2n]
    w = params["conv"]                                                # [W, di+2n]
    pad = jnp.pad(conv_in, ((0, 0), (m.conv_width - 1, 0), (0, 0)))
    conv = sum(pad[:, i:i + x.shape[1]] * w[i] for i in range(m.conv_width))
    conv = jax.nn.silu(conv)
    xi, bmat, cmat = jnp.split(conv, [d_in, d_in + n], axis=-1)
    xi = xi.reshape(x.shape[0], x.shape[1], nh, p)
    chunk = min(m.chunk, x.shape[1])
    if x.shape[1] % chunk:
        chunk = math.gcd(x.shape[1], chunk)
    if impl == "pallas":
        from repro.kernels import ops as kops
        y = kops.ssd_scan(xi, bmat, cmat, dt, params["A_log"], params["D"],
                          params["dt_bias"], chunk=chunk)
    else:
        y = _ssd_chunk_scan((xi, bmat, cmat, dt), params["A_log"], params["D"],
                            params["dt_bias"], meta, chunk)
    y = y.reshape(x.shape[0], x.shape[1], d_in)
    # gated RMSNorm (Mamba2's out norm)
    y = apply_norm({"scale": params["norm_scale"]}, y * jax.nn.silu(z), "rmsnorm")
    return y @ params["out_proj"]


def mamba2_decode(params, x, state, meta, cfg: ModelConfig):
    """Single-token recurrence.  state = (conv_buf [B,W-1,di+2n],
    h [B,nh,p,n]).  x [B,1,d]."""
    m = cfg.ssm
    d_in, nh, p, n = meta["d_in"], meta["nh"], meta["p"], meta["n"]
    conv_buf, h = state
    proj = x @ params["in_proj"]
    z, xi, bmat, cmat, dt = jnp.split(
        proj, [d_in, 2 * d_in, 2 * d_in + n, 2 * d_in + 2 * n], axis=-1)
    conv_in = jnp.concatenate([xi, bmat, cmat], axis=-1)              # [B,1,di+2n]
    window = jnp.concatenate([conv_buf, conv_in], axis=1)             # [B,W,di+2n]
    w = params["conv"]
    conv = jnp.einsum("bwc,wc->bc", window, w)[:, None]
    conv = jax.nn.silu(conv)
    xi, bmat, cmat = jnp.split(conv, [d_in, d_in + n], axis=-1)
    xi = xi.reshape(x.shape[0], nh, p)
    dtv = jax.nn.softplus(dt[:, 0].astype(jnp.float32) + params["dt_bias"])  # [B,nh]
    a = jnp.exp(-dtv * jnp.exp(params["A_log"]))                      # [B,nh]
    h = h * a[..., None, None] + jnp.einsum(
        "bhp,bn,bh->bhpn", xi.astype(jnp.float32), bmat[:, 0].astype(jnp.float32), dtv)
    y = jnp.einsum("bn,bhpn->bhp", cmat[:, 0].astype(jnp.float32), h)
    y = (y + xi.astype(jnp.float32) * params["D"][:, None]).astype(x.dtype)
    y = y.reshape(x.shape[0], 1, d_in)
    y = apply_norm({"scale": params["norm_scale"]}, y * jax.nn.silu(z), "rmsnorm")
    return y @ params["out_proj"], (window[:, 1:], h)


def mamba2_init_state(batch, meta, cfg: ModelConfig, dtype):
    m = cfg.ssm
    d_in, nh, p, n = meta["d_in"], meta["nh"], meta["p"], meta["n"]
    return (jnp.zeros((batch, m.conv_width - 1, d_in + 2 * n), dtype),
            jnp.zeros((batch, nh, p, n), jnp.float32))


# ---------------------------------------------------------------------------
# xLSTM blocks: mLSTM (matrix memory) and sLSTM (scalar memory)
# ---------------------------------------------------------------------------

def init_mlstm(rng, cfg: ModelConfig, dtype):
    d = cfg.d_model
    f = int(cfg.xlstm.proj_factor * d)
    nh = cfg.num_heads
    hd = f // nh
    ks = jax.random.split(rng, 7)
    s = 1.0 / math.sqrt(d)
    params = {
        "up": _normal(ks[0], (d, 2 * f), s, dtype),          # [x_inner | z]
        "wq": _normal(ks[1], (f, nh, hd), 1.0 / math.sqrt(f), dtype),
        "wk": _normal(ks[2], (f, nh, hd), 1.0 / math.sqrt(f), dtype),
        "wv": _normal(ks[3], (f, nh, hd), 1.0 / math.sqrt(f), dtype),
        "w_if": _normal(ks[4], (f, 2 * nh), 1.0 / math.sqrt(f), jnp.float32),
        "norm_scale": jnp.ones((f,), dtype),
        "down": _normal(ks[5], (f, d), 1.0 / math.sqrt(f), dtype),
    }
    axes = {
        "up": ("embed", "mlp"), "wq": ("mlp", "heads", "head_dim"),
        "wk": ("mlp", "heads", "head_dim"), "wv": ("mlp", "heads", "head_dim"),
        "w_if": ("mlp", None), "norm_scale": ("mlp",), "down": ("mlp", "embed"),
    }
    meta = {"f": f, "nh": nh, "hd": hd}
    return params, axes, meta


def _mlstm_scan(q, k, v, i_gate, f_gate, chunk):
    """Chunked mLSTM: C_t = f_t C_{t-1} + i_t k_t v_t^T ; y_t = q_t C_t / nrm.

    Stabilized in log space like the official xLSTM formulation (simplified:
    sigmoid forget gate, exp input gate with per-chunk max-normalization).
    q,k,v: [B,S,nh,hd]; gates [B,S,nh].  Returns [B,S,nh,hd].
    """
    b, s, nh, hd = q.shape
    lf = jax.nn.log_sigmoid(f_gate.astype(jnp.float32))               # [B,S,nh]
    li = i_gate.astype(jnp.float32)
    nchunk = s // chunk

    qc = q.astype(jnp.float32).reshape(b, nchunk, chunk, nh, hd).transpose(1, 0, 2, 3, 4)
    kc = k.astype(jnp.float32).reshape(b, nchunk, chunk, nh, hd).transpose(1, 0, 2, 3, 4)
    vc = v.astype(jnp.float32).reshape(b, nchunk, chunk, nh, hd).transpose(1, 0, 2, 3, 4)
    lfc = lf.reshape(b, nchunk, chunk, nh).transpose(1, 0, 2, 3)
    lic = li.reshape(b, nchunk, chunk, nh).transpose(1, 0, 2, 3)

    def body(carry, args):
        C, N, m_run = carry                                           # [B,nh,hd,hd],[B,nh,hd],[B,nh]
        qx, kx, vx, lfx, lix = args
        cumf = jnp.cumsum(lfx, axis=1)                                # [B,chunk,nh]
        total = cumf[:, -1]
        # log weights of each j's contribution at chunk end / at position i
        log_g = lix + (total[:, None] - cumf)                         # decay j->end
        m_new = jnp.maximum(m_run + total, jnp.max(log_g, axis=1))    # [B,nh]
        # intra-chunk attention-like term
        d_ij = cumf[:, :, None, :] - cumf[:, None, :, :] + lix[:, None, :, :]
        mask = jnp.tril(jnp.ones((chunk, chunk)))[None, :, :, None]
        m_i = jnp.maximum(m_run[:, None] + cumf,                      # carry decayed
                          jnp.max(jnp.where(mask > 0, d_ij, -jnp.inf), axis=2))
        w_ij = jnp.exp(jnp.where(mask > 0, d_ij - m_i[:, :, None, :], -1e30))
        scores = jnp.einsum("bihd,bjhd->bijh", qx, kx) / math.sqrt(hd)
        y_intra = jnp.einsum("bijh,bijh,bjhd->bihd", scores, w_ij, vx)
        carry_scale = jnp.exp(m_run[:, None] + cumf - m_i)            # [B,chunk,nh]
        y_carry = jnp.einsum("bihd,bhde->bihe", qx, C) / math.sqrt(hd)
        y_carry = y_carry * carry_scale[..., None]
        n_i = jnp.einsum("bihd,bhd->bih", qx, N) / math.sqrt(hd) * carry_scale \
            + jnp.einsum("bijh,bijh->bih", scores, w_ij)
        denom = jnp.maximum(jnp.abs(n_i), jnp.exp(-m_i))[..., None]
        y = (y_intra + y_carry) / denom
        # update carried matrix memory
        g = jnp.exp(log_g - m_new[:, None])                           # [B,chunk,nh]
        C = C * jnp.exp(m_run + total - m_new)[..., None, None] \
            + jnp.einsum("bjhd,bjhe,bjh->bhde", kx, vx, g)
        N = N * jnp.exp(m_run + total - m_new)[..., None] \
            + jnp.einsum("bjhd,bjh->bhd", kx, g)
        return (C, N, m_new), y

    C0 = jnp.zeros((b, nh, hd, hd), jnp.float32)
    N0 = jnp.zeros((b, nh, hd), jnp.float32)
    m0 = jnp.full((b, nh), -1e30, jnp.float32)
    _, ys = jax.lax.scan(body, (C0, N0, m0), (qc, kc, vc, lfc, lic))
    return ys.transpose(1, 0, 2, 3, 4).reshape(b, s, nh, hd).astype(q.dtype)


def apply_mlstm(params, x, meta, cfg: ModelConfig, chunk: int = 64):
    f, nh, hd = meta["f"], meta["nh"], meta["hd"]
    up = x @ params["up"]
    xi, z = jnp.split(up, 2, axis=-1)
    q = jnp.einsum("bsf,fhd->bshd", xi, params["wq"])
    k = jnp.einsum("bsf,fhd->bshd", xi, params["wk"])
    v = jnp.einsum("bsf,fhd->bshd", xi, params["wv"])
    gates = xi.astype(jnp.float32) @ params["w_if"]                   # [B,S,2nh]
    i_gate, f_gate = jnp.split(gates, 2, axis=-1)
    y = _mlstm_scan(q, k, v, i_gate, f_gate, min(chunk, x.shape[1]))
    y = y.reshape(x.shape[0], x.shape[1], f)
    y = apply_norm({"scale": params["norm_scale"]}, y * jax.nn.silu(z), "rmsnorm")
    return y @ params["down"]


def mlstm_decode(params, x, state, meta, cfg: ModelConfig):
    """state = (C [B,nh,hd,hd], N [B,nh,hd], m [B,nh])."""
    f, nh, hd = meta["f"], meta["nh"], meta["hd"]
    C, N, m_run = state
    up = x @ params["up"]
    xi, z = jnp.split(up, 2, axis=-1)
    q = jnp.einsum("bsf,fhd->bshd", xi, params["wq"])[:, 0]
    k = jnp.einsum("bsf,fhd->bshd", xi, params["wk"])[:, 0]
    v = jnp.einsum("bsf,fhd->bshd", xi, params["wv"])[:, 0]
    gates = xi.astype(jnp.float32) @ params["w_if"]
    li, lf = jnp.split(gates[:, 0], 2, axis=-1)                       # [B,nh]
    lf = jax.nn.log_sigmoid(lf)
    m_new = jnp.maximum(m_run + lf, li)
    C = C * jnp.exp(m_run + lf - m_new)[..., None, None] \
        + jnp.einsum("bhd,bhe,bh->bhde", k.astype(jnp.float32), v.astype(jnp.float32),
                     jnp.exp(li - m_new))
    N = N * jnp.exp(m_run + lf - m_new)[..., None] \
        + k.astype(jnp.float32) * jnp.exp(li - m_new)[..., None]
    y = jnp.einsum("bhd,bhde->bhe", q.astype(jnp.float32), C) / math.sqrt(hd)
    n = jnp.einsum("bhd,bhd->bh", q.astype(jnp.float32), N) / math.sqrt(hd)
    y = y / jnp.maximum(jnp.abs(n), jnp.exp(-m_new))[..., None]
    y = y.reshape(x.shape[0], 1, f).astype(x.dtype)
    y = apply_norm({"scale": params["norm_scale"]}, y * jax.nn.silu(z), "rmsnorm")
    return y @ params["down"], (C, N, m_new)


def mlstm_init_state(batch, meta, dtype):
    nh, hd = meta["nh"], meta["hd"]
    return (jnp.zeros((batch, nh, hd, hd), jnp.float32),
            jnp.zeros((batch, nh, hd), jnp.float32),
            jnp.full((batch, nh), -1e30, jnp.float32))


def init_slstm(rng, cfg: ModelConfig, dtype):
    d = cfg.d_model
    nh = cfg.num_heads
    ks = jax.random.split(rng, 3)
    s = 1.0 / math.sqrt(d)
    params = {
        # gates: i, f, z(cell input), o — input + recurrent weights
        "w_x": _normal(ks[0], (d, 4 * d), s, dtype),
        "w_h": _normal(ks[1], (d, 4 * d), s, dtype),
        "bias": jnp.zeros((4 * d,), jnp.float32),
        "down": _normal(ks[2], (d, d), s, dtype),
    }
    axes = {"w_x": ("embed", "mlp"), "w_h": ("embed", "mlp"),
            "bias": (None,), "down": ("embed", "embed")}
    meta = {"nh": nh}
    return params, axes, meta


def _slstm_cell(params, x_t, state):
    """One sLSTM step with exponential gating + stabilizer.
    state = (c, n, h, m) each [B, d]."""
    c, n, h, m = state
    pre = (x_t @ params["w_x"] + h.astype(x_t.dtype) @ params["w_h"]).astype(jnp.float32) \
        + params["bias"]
    i_, f_, z_, o_ = jnp.split(pre, 4, axis=-1)
    lf = jax.nn.log_sigmoid(f_)
    m_new = jnp.maximum(lf + m, i_)
    i_g = jnp.exp(i_ - m_new)
    f_g = jnp.exp(lf + m - m_new)
    c = f_g * c + i_g * jnp.tanh(z_)
    n = f_g * n + i_g
    h_new = jax.nn.sigmoid(o_) * c / jnp.maximum(n, 1.0)
    return (c, n, h_new, m_new)


def apply_slstm(params, x, meta, cfg: ModelConfig):
    b, s, d = x.shape
    state = tuple(jnp.zeros((b, d), jnp.float32) for _ in range(4))

    def body(st, x_t):
        st = _slstm_cell(params, x_t, st)
        return st, st[2]

    _, hs = jax.lax.scan(body, state, x.transpose(1, 0, 2))
    y = hs.transpose(1, 0, 2).astype(x.dtype)
    return y @ params["down"]


def slstm_decode(params, x, state, meta, cfg: ModelConfig):
    st = _slstm_cell(params, x[:, 0], state)
    return (st[2][:, None].astype(x.dtype) @ params["down"]), st


def slstm_init_state(batch, d, dtype):
    return tuple(jnp.zeros((batch, d), jnp.float32) for _ in range(4))
