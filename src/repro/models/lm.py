"""Config-driven language-model assembly for all assigned architectures.

One class (:class:`LM`) covers the six families:

  dense   — llama-style decoder (deepseek-67b, chatglm3-6b, olmo-1b,
            llama3-405b) with GQA + RoPE(1d/2d) + SwiGLU.
  moe     — dense attention + token-choice MoE FFN (arctic-480b with dense
            residual, llama4 with shared expert, top-1/2 routing).
  vlm     — qwen2-vl backbone: M-RoPE, input arrives as precomputed
            embeddings (vision frontend stubbed per spec).
  encdec  — whisper: bidirectional encoder over precomputed frame
            embeddings (conv frontend stubbed) + causal decoder with
            cross-attention.
  hybrid  — zamba2: Mamba2 backbone + one SHARED attention block applied
            every k layers (weight reuse via lax.cond inside the scan).
  ssm     — xlstm: mLSTM blocks with periodic sLSTM blocks (unrolled; 12
            small layers).

Deep homogeneous stacks (dense/moe/vlm/hybrid decoders) are executed with
``lax.scan`` over stacked layer parameters (+ optional per-block remat), so
HLO size is O(1) in depth — the production-standard choice.

Batches are dicts:
  tokens [B,S] int32          (dense/moe/encdec-decoder input)
  embeds [B,S,d]              (vlm: replaces tokens)
  enc_embeds [B,F,d]          (encdec: encoder frame embeddings)
  labels [B,S] int32, loss_mask [B,S] f32 (train)
  positions [P,B,S] int32     (optional; defaults to arange)
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.param_dtype)


# ---------------------------------------------------------------------------
# per-family block init / apply
# ---------------------------------------------------------------------------

def init_block(rng, cfg: ModelConfig, *, cross: bool = False):
    """One decoder block.  Returns (params, axes, meta)."""
    dtype = _dtype(cfg)
    ks = jax.random.split(rng, 8)
    params, axes, meta = {}, {}, {}

    if cfg.family == "hybrid":
        params["mamba"], axes["mamba"], meta["mamba"] = L.init_mamba2(ks[0], cfg, dtype)
        params["norm_m"], axes["norm_m"] = L.init_norm(cfg, dtype)
        params["mlp"], axes["mlp"] = L.init_mlp(ks[1], cfg.d_model, cfg.d_ff, cfg.act, dtype)
        params["norm_f"], axes["norm_f"] = L.init_norm(cfg, dtype)
        return params, axes, meta

    params["attn"], axes["attn"] = L.init_attention(ks[0], cfg, dtype)
    params["norm_a"], axes["norm_a"] = L.init_norm(cfg, dtype)
    if cross:
        params["xattn"], axes["xattn"] = L.init_attention(ks[1], cfg, dtype)
        params["norm_x"], axes["norm_x"] = L.init_norm(cfg, dtype)
    if cfg.family == "moe":
        params["moe"], axes["moe"] = L.init_moe(ks[2], cfg, dtype)
    else:
        params["mlp"], axes["mlp"] = L.init_mlp(ks[2], cfg.d_model, cfg.d_ff, cfg.act, dtype)
    params["norm_f"], axes["norm_f"] = L.init_norm(cfg, dtype)
    return params, axes, meta


def apply_block(params, x, positions, cfg: ModelConfig, meta, *,
                window=None, attn_impl="xla", cross_kv=None, causal=True,
                masks=None):
    """Pre-norm residual block.  Returns (x, aux_loss_scalar).

    ``masks`` (optional) carries THIS layer's FedAP filter keep-masks —
    ``{"mlp": [d_ff] 0/1}`` — threaded through the masked FFN
    (:func:`repro.models.layers.apply_mlp`); None is the plain dense path.
    """
    aux = jnp.zeros((), jnp.float32)
    mlp_mask = None if masks is None else masks["mlp"]

    if cfg.family == "hybrid":
        h = L.apply_norm(params["norm_m"], x, cfg.norm)
        x = x + L.apply_mamba2(params["mamba"], h, meta["mamba"], cfg, impl=attn_impl)
        h = L.apply_norm(params["norm_f"], x, cfg.norm)
        x = x + L.apply_mlp(params["mlp"], h, cfg.act, mlp_mask)
        return x, aux

    h = L.apply_norm(params["norm_a"], x, cfg.norm)
    if causal:
        attn_out = L.attention_block(params["attn"], h, positions, cfg,
                                     window=window, attn_impl=attn_impl)
    else:  # encoder self-attention: bidirectional
        q = jnp.einsum("bsd,dhk->bshk", h, params["attn"]["wq"])
        k = jnp.einsum("bsd,dhk->bshk", h, params["attn"]["wk"])
        v = jnp.einsum("bsd,dhk->bshk", h, params["attn"]["wv"])
        out = L.attention(q, k, v, causal=False, window=None)
        attn_out = jnp.einsum("bshk,hkd->bsd", out, params["attn"]["wo"])
    x = x + attn_out

    if cross_kv is not None:
        h = L.apply_norm(params["norm_x"], x, cfg.norm)
        x = x + L.attention_block(params["xattn"], h, positions, cfg,
                                  attn_impl=attn_impl, cross_kv=cross_kv)

    h = L.apply_norm(params["norm_f"], x, cfg.norm)
    if cfg.family == "moe":
        y, moe_aux = L.apply_moe(params["moe"], h, cfg)
        aux = aux + moe_aux["load_balance"] + moe_aux["router_z"]
        x = x + y
    else:
        x = x + L.apply_mlp(params["mlp"], h, cfg.act, mlp_mask)
    return x, aux


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------

class LM:
    """Functional model: ``init``, ``apply`` (full-sequence logits),
    ``loss`` (next-token CE), ``init_cache`` + ``decode_step``."""

    def __init__(self, cfg: ModelConfig, *, attn_impl: str = "xla"):
        self.cfg = cfg
        self.attn_impl = attn_impl
        self._axes = None
        # meta is static per-config (shape bookkeeping for ssm/mlstm blocks)
        self._meta = self._build_meta()

    # -- meta ---------------------------------------------------------------
    def _build_meta(self):
        cfg = self.cfg
        meta = {}
        if cfg.family == "hybrid":
            m = cfg.ssm
            d_in = m.expand * cfg.d_model
            nh = m.num_ssm_heads or max(1, d_in // 64)
            meta["mamba"] = {"d_in": d_in, "nh": nh, "p": d_in // nh, "n": m.state_dim}
        if cfg.family == "ssm":
            f = int(cfg.xlstm.proj_factor * cfg.d_model)
            meta["mlstm"] = {"f": f, "nh": cfg.num_heads, "hd": f // cfg.num_heads}
            meta["slstm"] = {"nh": cfg.num_heads}
        return meta

    def _is_slstm(self, i: int) -> bool:
        return self.cfg.family == "ssm" and (i + 1) % self.cfg.xlstm.slstm_every == 0

    def hybrid_groups(self) -> list:
        """zamba2 layer groups: shared attention fires before each group of
        ``attn_every`` Mamba2 layers."""
        k = self.cfg.hybrid.attn_every
        n = self.cfg.num_layers
        return [(a, min(a + k, n)) for a in range(0, n, k)]

    @property
    def scanned(self) -> bool:
        """Deep homogeneous stacks are scanned; small heterogeneous ones
        (xlstm alternates block types; whisper enc+dec) are unrolled."""
        return self.cfg.family in ("dense", "moe", "vlm", "hybrid")

    # -- init -----------------------------------------------------------------
    def init_with_axes(self, rng):
        cfg = self.cfg
        dtype = _dtype(cfg)
        ks = jax.random.split(rng, 8)
        params, axes = {}, {}

        params["embed"] = L._normal(ks[0], (cfg.vocab_size, cfg.d_model),
                                    1.0 / math.sqrt(cfg.d_model), dtype)
        axes["embed"] = ("vocab", "embed")
        if not cfg.tie_embeddings:
            params["unembed"] = L._normal(ks[1], (cfg.d_model, cfg.vocab_size),
                                          1.0 / math.sqrt(cfg.d_model), dtype)
            axes["unembed"] = ("embed", "vocab")
        params["norm_out"], axes["norm_out"] = L.init_norm(cfg, dtype)

        if cfg.family == "encdec":
            enc = cfg.encoder
            params["enc_pos"] = L._normal(ks[2], (enc.frames, cfg.d_model), 0.02, dtype)
            axes["enc_pos"] = (None, "embed")
            params["norm_enc"], axes["norm_enc"] = L.init_norm(cfg, dtype)
            eb, ea = [], None
            for i, k in enumerate(jax.random.split(ks[3], enc.num_layers)):
                p, a, _ = init_block(k, cfg)
                eb.append(p)
                ea = a
            params["encoder"] = {f"l{i}": p for i, p in enumerate(eb)}
            axes["encoder"] = {f"l{i}": ea for i in range(enc.num_layers)}
            db, da = [], None
            for i, k in enumerate(jax.random.split(ks[4], cfg.num_layers)):
                p, a, _ = init_block(k, cfg, cross=True)
                db.append(p)
                da = a
            params["decoder"] = {f"l{i}": p for i, p in enumerate(db)}
            axes["decoder"] = {f"l{i}": da for i in range(cfg.num_layers)}
            return params, axes

        if cfg.family == "ssm":
            blocks, baxes = {}, {}
            for i, k in enumerate(jax.random.split(ks[3], cfg.num_layers)):
                if self._is_slstm(i):
                    p, a, _ = L.init_slstm(k, cfg, dtype)
                    blocks[f"l{i}"] = {"cell": p}
                    baxes[f"l{i}"] = {"cell": a}
                else:
                    p, a, _ = L.init_mlstm(k, cfg, dtype)
                    blocks[f"l{i}"] = {"cell": p}
                    baxes[f"l{i}"] = {"cell": a}
                np_, na = L.init_norm(cfg, dtype)
                blocks[f"l{i}"]["norm"] = np_
                baxes[f"l{i}"]["norm"] = na
            params["blocks"], axes["blocks"] = blocks, baxes
            return params, axes

        # scanned families: stack layer params along a leading 'layers' axis
        def one(k):
            p, a, _ = init_block(k, cfg)
            return p, a

        layer_keys = jax.random.split(ks[3], cfg.num_layers)
        _, a0 = one(layer_keys[0])
        stacked = jax.vmap(lambda k: one(k)[0])(layer_keys)
        params["layers"] = stacked
        axes["layers"] = jax.tree.map(lambda ax: ("layers",) + ax, a0,
                                      is_leaf=lambda x: isinstance(x, tuple))
        if cfg.family == "hybrid":
            p, a = {}, {}
            p["attn"], a["attn"] = L.init_attention(ks[5], cfg, dtype)
            p["norm"], a["norm"] = L.init_norm(cfg, dtype)
            params["shared_attn"], axes["shared_attn"] = p, a
        return params, axes

    def init(self, rng):
        return self.init_with_axes(rng)[0]

    def axes(self):
        """Logical-axis tree (static); computed via a shape-only trace."""
        if self._axes is None:
            box = {}

            def f(rng):
                p, a = self.init_with_axes(rng)
                box["a"] = a
                return p

            jax.eval_shape(f, jax.random.key(0))
            self._axes = box["a"]
        return self._axes

    def param_shapes(self):
        return jax.eval_shape(self.init, jax.random.key(0))

    # -- forward --------------------------------------------------------------
    def _embed_in(self, params, batch):
        cfg = self.cfg
        if "embeds" in batch:
            x = batch["embeds"].astype(_dtype(cfg))
        else:
            x = params["embed"][batch["tokens"]]
        return x

    def _head(self, params, x):
        cfg = self.cfg
        x = L.apply_norm(params["norm_out"], x, cfg.norm)
        w = params["embed"].T if cfg.tie_embeddings else params["unembed"]
        return x @ w

    def _positions(self, batch, seq, bsz, offset=0):
        if "positions" in batch:
            return batch["positions"]
        return L.default_positions(bsz, seq, self.cfg.rope, offset)

    def _encode(self, params, batch):
        """Whisper encoder over precomputed frame embeddings."""
        cfg = self.cfg
        x = batch["enc_embeds"].astype(_dtype(cfg)) + params["enc_pos"][None]
        pos = L.default_positions(x.shape[0], x.shape[1], "none")
        for i in range(cfg.encoder.num_layers):
            x, _ = apply_block(params["encoder"][f"l{i}"], x, pos, cfg, {},
                               causal=False, attn_impl=self.attn_impl)
        return L.apply_norm(params["norm_enc"], x, cfg.norm)

    def apply(self, params, batch, *, window="auto", masks=None):
        """Full-sequence logits [B,S,V] (+ aux loss).

        ``masks`` (optional) carries the FedAP filter keep-masks of the
        static-shape masked mode — ``{"mlp": [L, d_ff] 0/1}``, one row per
        scanned layer, riding the layer scan as xs alongside that layer's
        params (structure fixed from round 0, zero re-jit).  Masked units
        are zeroed at the FFN pre-activation, which equals the shrunk
        model's logits exactly (silu(0) = gelu(0) = 0 through wo); when
        d_model/d_ff are 128-aligned the masked matmuls run the Pallas
        ``masked_matmul`` kernel, skipping fully-pruned column blocks.
        """
        cfg = self.cfg
        if masks is not None:
            if cfg.family == "moe":
                raise ValueError(
                    "masks= is unsupported for MoE stacks: a zeroed router "
                    "logit is not -inf, so masked experts would still "
                    "receive routed mass — prune experts with "
                    "Prune(mode='shrink') (core.pruning_lm.prune_lm_experts)")
            if not self.scanned:
                raise ValueError(
                    f"masks= requires a scanned stack, not family "
                    f"{cfg.family!r}")
        if window == "auto":
            window = None            # training/prefill default: full attention
        x = self._embed_in(params, batch)
        bsz, seq = x.shape[0], x.shape[1]
        pos = self._positions(batch, seq, bsz)
        aux = jnp.zeros((), jnp.float32)

        if cfg.family == "encdec":
            enc = self._encode(params, batch)
            ek, ev = {}, {}
            for i in range(cfg.num_layers):
                blk = params["decoder"][f"l{i}"]
                k = jnp.einsum("bsd,dhk->bshk", enc, blk["xattn"]["wk"])
                v = jnp.einsum("bsd,dhk->bshk", enc, blk["xattn"]["wv"])
                x, _ = apply_block(blk, x, pos, cfg, {}, window=window,
                                   attn_impl=self.attn_impl, cross_kv=(k, v))
            return self._head(params, x), aux

        if cfg.family == "ssm":
            for i in range(cfg.num_layers):
                blk = params["blocks"][f"l{i}"]
                h = L.apply_norm(blk["norm"], x, cfg.norm)
                if self._is_slstm(i):
                    x = x + L.apply_slstm(blk["cell"], h, self._meta["slstm"], cfg)
                else:
                    x = x + L.apply_mlstm(blk["cell"], h, self._meta["mlstm"], cfg)
            return self._head(params, x), aux

        # scanned stacks (filter masks, when given, ride the scan as extra
        # xs — each step consumes its layer's params AND its mask row)
        def body(carry, scanned):
            x, aux = carry
            layer_params, layer_masks = \
                scanned if masks is not None else (scanned, None)
            x, a = apply_block(layer_params, x, pos, cfg, self._meta,
                               window=window, attn_impl=self.attn_impl,
                               masks=layer_masks)
            return (x, aux + a), None

        if cfg.remat == "block":
            body = jax.checkpoint(body, prevent_cse=False)
        elif cfg.remat == "dots":
            # §Perf knob: save matmul outputs, recompute only elementwise —
            # trades activation memory for ~25% less recompute FLOPs
            body = jax.checkpoint(
                body, prevent_cse=False,
                policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)

        if cfg.family == "hybrid":
            # zamba2: the SHARED attention block runs before each group of
            # ``attn_every`` Mamba2 layers (weights reused; per-application
            # KV caches during decode — see init_cache).
            shared = params["shared_attn"]
            for a, b in self.hybrid_groups():
                h = L.apply_norm(shared["norm"], x, cfg.norm)
                x = x + L.attention_block(shared["attn"], h, pos, cfg,
                                          window=cfg.sliding_window,
                                          attn_impl=self.attn_impl)
                group = jax.tree.map(lambda p: p[a:b], params["layers"])
                if masks is not None:
                    group = (group, jax.tree.map(lambda m: m[a:b], masks))
                (x, aux), _ = jax.lax.scan(body, (x, aux), group)
            return self._head(params, x), aux

        xs = params["layers"] if masks is None else (params["layers"], masks)
        (x, aux), _ = jax.lax.scan(body, (x, aux), xs)
        return self._head(params, x), aux

    # -- loss -------------------------------------------------------------------
    def loss(self, params, batch, *, window="auto", masks=None):
        logits, aux = self.apply(params, batch, window=window, masks=masks)
        labels = batch["labels"]
        mask = batch.get("loss_mask")
        logp = jax.nn.log_softmax(logits.astype(jnp.float32))
        nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
        if mask is not None:
            nll = nll * mask
            denom = jnp.clip(jnp.sum(mask), 1.0, None)
        else:
            denom = nll.size
        return jnp.sum(nll) / denom + aux

    def loss_and_acc(self, params, x, y, *, masks=None):
        """The simulation-driver model contract (mirrors
        ``PaperModel.loss_and_acc``): positional ``(x, y)`` = (tokens
        [B,S], labels [B,S]) int32 arrays -> (loss, token accuracy).

        One forward gives both, so the Formula-7 accuracy gate rides the
        first FedDU server gradient step.  This is the seam that lets
        ``FederatedTrainer``/``PlanExecutor`` drive transformer
        fine-tuning with the same code path as the CNN repro."""
        logits, aux = self.apply(params, {"tokens": x, "labels": y},
                                 masks=masks)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32))
        nll = -jnp.take_along_axis(logp, y[..., None], axis=-1)[..., 0]
        ok = (jnp.argmax(logits, -1) == y).astype(jnp.float32)
        return jnp.sum(nll) / nll.size + aux, jnp.mean(ok)

    # -- FedAP seam (executor Prune events; see repro.core.backend) ----------
    def decide_kept(self, params, p_star, *, align=128):
        """``{"mlp": [L, keep]}`` kept-unit index rows from the Formula-15
        aggregate rate — weight-norm product scores inside the scanned
        stack, uniform ``align``-lane kept count (core.pruning_lm).  A pure
        host function of (params, p_star): the host and mesh FedAP entry
        points make the identical selection."""
        from repro.core import pruning_lm

        return {"mlp": pruning_lm.ffn_kept_indices(
            params, self.cfg, float(p_star), align=align)}

    def filter_masks(self, params, kept):
        """``{"mlp": [L, d_ff] 0/1}`` keep-masks for kernel-mode compute."""
        from repro.core import pruning_lm

        return pruning_lm.ffn_filter_masks(params, kept)

    def param_masks(self, params, kept):
        """Param-structured, factored 0/1 masks (coupling-closed: wi/wg
        cols [L, 1, d_ff] + wo rows [L, d_ff, 1], every other leaf
        ``(1,) * ndim``) for the static-shape masked round state."""
        from repro.core import pruning_lm

        return pruning_lm.ffn_param_masks(params, kept)

    def shrink_params(self, params, kept):
        """Structurally gather the kept FFN units (params or any tree of
        identical structure — momentum buffers, FedDyn corrections)."""
        from repro.core import pruning_lm

        idx = kept.get("mlp") if kept else None
        return params if idx is None else pruning_lm.shrink_ffn_at(params, idx)

    # -- decode -------------------------------------------------------------------
    def init_cache(self, batch_size: int, cache_len: int, *, window=None):
        """Decode cache pytree.  ``cache_len`` is the visible context length
        (S for full attention; min(S, window) for ring-buffer archs)."""
        cfg = self.cfg
        dtype = _dtype(cfg)
        kvh, hd = cfg.padded_num_kv_heads, cfg.resolved_head_dim
        eff = cache_len if window is None else min(cache_len, window)

        def kv(n_layers, length):
            return {
                "k": jnp.zeros((n_layers, batch_size, length, kvh, hd), dtype),
                "v": jnp.zeros((n_layers, batch_size, length, kvh, hd), dtype),
            }

        if cfg.family == "encdec":
            return {
                "self": kv(cfg.num_layers, eff),
                "cross": kv(cfg.num_layers, cfg.encoder.frames),
                "index": jnp.zeros((), jnp.int32),
            }
        if cfg.family == "ssm":
            cache = {"index": jnp.zeros((), jnp.int32)}
            for i in range(cfg.num_layers):
                if self._is_slstm(i):
                    cache[f"l{i}"] = L.slstm_init_state(batch_size, cfg.d_model, dtype)
                else:
                    cache[f"l{i}"] = L.mlstm_init_state(batch_size, self._meta["mlstm"], dtype)
            return cache
        if cfg.family == "hybrid":
            m = self._meta["mamba"]
            lcount = cfg.num_layers
            n_groups = len(self.hybrid_groups())
            attn_len = min(eff, cfg.sliding_window or eff)
            conv = jnp.zeros((lcount, batch_size, cfg.ssm.conv_width - 1,
                              m["d_in"] + 2 * m["n"]), dtype)
            h = jnp.zeros((lcount, batch_size, m["nh"], m["p"], m["n"]), jnp.float32)
            return {
                "mamba": {"conv": conv, "h": h},
                # one KV cache per shared-attention APPLICATION (weights are
                # shared across groups; caches are not)
                "shared_attn": {
                    "k": jnp.zeros((n_groups, batch_size, attn_len, kvh, hd), dtype),
                    "v": jnp.zeros((n_groups, batch_size, attn_len, kvh, hd), dtype),
                },
                "index": jnp.zeros((), jnp.int32),
            }
        return {**kv(cfg.num_layers, eff), "index": jnp.zeros((), jnp.int32)}

    def prefill_cross(self, params, cache, batch):
        """encdec only: compute the fixed cross-attention K/V from the
        encoder output once, before decoding."""
        cfg = self.cfg
        enc = self._encode(params, batch)
        ks, vs = [], []
        for i in range(cfg.num_layers):
            blk = params["decoder"][f"l{i}"]
            ks.append(jnp.einsum("bsd,dhk->bshk", enc, blk["xattn"]["wk"]))
            vs.append(jnp.einsum("bsd,dhk->bshk", enc, blk["xattn"]["wv"]))
        cache["cross"]["k"] = jnp.stack(ks)
        cache["cross"]["v"] = jnp.stack(vs)
        return cache

    def decode_step(self, params, cache, batch, *, masks=None):
        """One-token decode.  batch: tokens [B,1] (or embeds [B,1,d]).
        Returns (logits [B,1,V], new cache).

        ``cache["index"]`` may be a scalar (lockstep decode) or an int32
        [B] vector (continuous batching: per-slot fill levels — rope
        positions, cache writes and attention validity all follow the
        per-slot index; see :func:`layers.attention_decode`).

        ``masks`` (optional) carries the FedAP filter keep-masks exactly
        as in :meth:`apply` — ``{"mlp": [L, d_ff] 0/1}`` riding the layer
        scan as zipped xs — so a mask-mode pruned checkpoint decodes
        through the block-skipping masked FFN matmuls at the dense shapes
        (logits identical to the shrunk model's)."""
        cfg = self.cfg
        if masks is not None:
            if cfg.family == "moe":
                raise ValueError(
                    "masks= is unsupported for MoE stacks: a zeroed router "
                    "logit is not -inf, so masked experts would still "
                    "receive routed mass — prune experts with "
                    "Prune(mode='shrink') (core.pruning_lm.prune_lm_experts)")
            if not self.scanned:
                raise ValueError(
                    f"masks= requires a scanned stack, not family "
                    f"{cfg.family!r}")
        x = self._embed_in(params, batch)
        bsz = x.shape[0]
        idx = cache["index"]
        step_off = idx if jnp.ndim(idx) == 0 else idx[None, :, None]
        pos = self._positions(batch, 1, bsz) if "positions" in batch else \
            L.default_positions(bsz, 1, cfg.rope) + step_off

        if cfg.family == "encdec":
            new_k, new_v = [], []
            for i in range(cfg.num_layers):
                blk = params["decoder"][f"l{i}"]
                h = L.apply_norm(blk["norm_a"], x, cfg.norm)
                y, ck, cv = L.attention_decode(
                    blk["attn"], h, cache["self"]["k"][i], cache["self"]["v"][i],
                    idx, pos, cfg, attn_impl=self.attn_impl)
                new_k.append(ck)
                new_v.append(cv)
                x = x + y
                h = L.apply_norm(blk["norm_x"], x, cfg.norm)
                x = x + L.attention_decode_cross(
                    blk["xattn"], h, cache["cross"]["k"][i], cache["cross"]["v"][i], cfg)
                h = L.apply_norm(blk["norm_f"], x, cfg.norm)
                x = x + L.apply_mlp(blk["mlp"], h, cfg.act)
            cache = {**cache, "self": {"k": jnp.stack(new_k), "v": jnp.stack(new_v)},
                     "index": idx + 1}
            return self._head(params, x), cache

        if cfg.family == "ssm":
            cache = dict(cache)
            for i in range(cfg.num_layers):
                blk = params["blocks"][f"l{i}"]
                h = L.apply_norm(blk["norm"], x, cfg.norm)
                if self._is_slstm(i):
                    y, cache[f"l{i}"] = L.slstm_decode(blk["cell"], h, cache[f"l{i}"],
                                                       self._meta["slstm"], cfg)
                else:
                    y, cache[f"l{i}"] = L.mlstm_decode(blk["cell"], h, cache[f"l{i}"],
                                                       self._meta["mlstm"], cfg)
                x = x + y
            cache["index"] = idx + 1
            return self._head(params, x), cache

        if cfg.family == "hybrid":
            shared = params["shared_attn"]

            def body(carry, scanned):
                x = carry
                layer_params, conv, h = scanned
                hh = L.apply_norm(layer_params["norm_m"], x, cfg.norm)
                y, (conv, h) = L.mamba2_decode(layer_params["mamba"], hh, (conv, h),
                                               self._meta["mamba"], cfg)
                x = x + y
                hh = L.apply_norm(layer_params["norm_f"], x, cfg.norm)
                x = x + L.apply_mlp(layer_params["mlp"], hh, cfg.act)
                return x, (conv, h)

            convs, hs = [], []
            new_sk = list(range(len(self.hybrid_groups())))
            new_sv = list(range(len(self.hybrid_groups())))
            for gi, (a, b) in enumerate(self.hybrid_groups()):
                # shared attention before the group, with ITS OWN kv cache
                hh = L.apply_norm(shared["norm"], x, cfg.norm)
                y, ck, cv = L.attention_decode(
                    shared["attn"], hh, cache["shared_attn"]["k"][gi],
                    cache["shared_attn"]["v"][gi], idx, pos, cfg,
                    window=cfg.sliding_window, attn_impl=self.attn_impl)
                x = x + y
                new_sk[gi], new_sv[gi] = ck, cv
                group = jax.tree.map(lambda p: p[a:b], params["layers"])
                x, (conv, h) = jax.lax.scan(
                    body, x, (group, cache["mamba"]["conv"][a:b],
                              cache["mamba"]["h"][a:b]))
                convs.append(conv)
                hs.append(h)
            cache = {"mamba": {"conv": jnp.concatenate(convs),
                               "h": jnp.concatenate(hs)},
                     "shared_attn": {"k": jnp.stack(new_sk), "v": jnp.stack(new_sv)},
                     "index": idx + 1}
            return self._head(params, x), cache

        # scanned dense/moe/vlm decode (filter masks, when given, ride the
        # layer scan as extra xs — same zip as apply())
        def body(carry, scanned):
            x, li = carry
            if masks is not None:
                layer_params, ck, cv, layer_masks = scanned
            else:
                layer_params, ck, cv = scanned
                layer_masks = None
            h = L.apply_norm(layer_params["norm_a"], x, cfg.norm)
            y, ck, cv = L.attention_decode(layer_params["attn"], h, ck, cv, idx, pos,
                                           cfg, attn_impl=self.attn_impl)
            x = x + y
            h = L.apply_norm(layer_params["norm_f"], x, cfg.norm)
            if cfg.family == "moe":
                y, _ = L.apply_moe(layer_params["moe"], h, cfg)
                x = x + y
            else:
                x = x + L.apply_mlp(layer_params["mlp"], h, cfg.act,
                                    None if layer_masks is None
                                    else layer_masks["mlp"])
            return (x, li + 1), (ck, cv)

        xs = (params["layers"], cache["k"], cache["v"])
        if masks is not None:
            xs = xs + (masks,)
        (x, _), (k_new, v_new) = jax.lax.scan(
            body, (x, jnp.zeros((), jnp.int32)), xs)
        cache = {"k": k_new, "v": v_new, "index": idx + 1}
        return self._head(params, x), cache
