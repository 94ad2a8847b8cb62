"""FedAP for the transformer zoo — structured pruning of scanned stacks.

The paper prunes conv filters with HRank feature-map ranks.  For the
assigned LLM architectures the "filter-like" axes are:

  * FFN hidden units (rows of W_up / W_gate, cols of W_down) — dense archs;
  * whole experts — MoE archs (router mass = the rank analogue);
  * mLSTM projection channels — xlstm.

Two adaptations make this work on TPU with scan-over-layers stacks:

  1. UNIFORM KEPT COUNT across the stack: layer params are stacked
     [L, ...], so every layer must keep the same NUMBER of units (indices
     may differ per layer — a vectorized take_along_axis gather).  The
     count comes from the FedAP per-layer rates via the max-preserved rule
     (p_l <= p*_l, Alg. 3 line 14), then rounds UP to the 128-lane
     boundary (align).

  2. WEIGHT-NORM x WEIGHT-NORM scores (||wi_col|| * ||wo_row||) stand in
     for feature-map ranks inside the scan: activations of interior layers
     are not observable without unrolling, and the product-norm is the
     standard magnitude surrogate with the same keep-the-energetic-units
     semantics.  (On the CNN repro path the true HRank criterion is used —
     see repro.core.pruning.)

Pruning re-materializes a smaller model + config; the framework re-jits
once (the paper prunes once, at round 30).
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig


def _aligned_keep(d: int, rate: float, align: int | None,
                  *, layer: str = "layer") -> int:
    """Uniform kept count for one scanned stack: ``d - floor(rate * d)``,
    rounded UP to the alignment boundary (realized rate <= requested rate).

    Construction-time validation (the FedAPConfig.__post_init__ pattern):
    a rate or alignment that would keep 0 units or overflow the layer
    width fails HERE, naming the rate, the alignment and the layer —
    not as an opaque ``take_along_axis`` shape error downstream.
    """
    rate = float(rate)
    if not 0.0 <= rate < 1.0:
        raise ValueError(
            f"prune rate for {layer} must be in [0, 1), got {rate} "
            f"(rate >= 1 would keep 0 of the {d} units)")
    keep = d - int(np.floor(rate * d))
    if align and d >= align:
        aligned = int(np.ceil(keep / align) * align)
        if aligned > d:
            raise ValueError(
                f"{layer}: the {align}-lane-aligned kept count {aligned} "
                f"exceeds the layer width {d} (width is not a multiple of "
                f"the alignment; rate={rate} keeps {keep} unaligned units)")
        keep = aligned
    if not 1 <= keep <= d:   # unreachable given the guards above
        raise ValueError(
            f"{layer}: kept count {keep} outside [1, {d}] "
            f"(rate={rate}, align={align})")
    return keep


def ffn_unit_scores(layers: Any, act: str) -> jnp.ndarray:
    """[L, d_ff] product-norm scores for stacked dense FFN layers."""
    mlp = layers["mlp"]
    s_in = jnp.linalg.norm(mlp["wi"].astype(jnp.float32), axis=1)      # [L, ff]
    if "wg" in mlp:
        s_in = s_in * jnp.linalg.norm(mlp["wg"].astype(jnp.float32), axis=1)
    s_out = jnp.linalg.norm(mlp["wo"].astype(jnp.float32), axis=2)     # [L, ff]
    return s_in * s_out


def expert_scores(layers: Any) -> jnp.ndarray:
    """[L, E] scores for stacked MoE layers: router column norm (expected
    routing mass under random inputs) x expert weight norms."""
    moe = layers["moe"]
    r = jnp.linalg.norm(moe["router"].astype(jnp.float32), axis=1)     # [L, E]
    wi = jnp.linalg.norm(moe["wi"].astype(jnp.float32), axis=(2, 3))   # [L, E]
    wo = jnp.linalg.norm(moe["wo"].astype(jnp.float32), axis=(2, 3))
    return r * wi * wo


def ffn_kept_indices(params: Any, cfg: ModelConfig, rate: float,
                     *, align: int | None = 128) -> np.ndarray:
    """[L, keep] kept-unit index rows (sorted per layer) for the FFN hidden
    dim of a scanned dense/vlm/hybrid stack — the FedAP decision in index
    form, shared by the shrink (:func:`shrink_ffn_at`) and the static-shape
    mask application (:func:`ffn_param_masks` / :func:`ffn_filter_masks`).

    Host-resident numpy (the decision is static — it drives either a
    re-materialization or constant-folded masks, never a traced value).
    """
    if cfg.family not in ("dense", "vlm", "hybrid"):
        raise ValueError(f"prune_lm_ffn does not apply to family {cfg.family}")
    scores = ffn_unit_scores(params["layers"], cfg.act)                # [L, ff]
    d_ff = scores.shape[1]
    keep = _aligned_keep(d_ff, rate, align,
                         layer=f"mlp stack (d_ff={d_ff})")
    idx = jnp.argsort(scores, axis=1)[:, ::-1][:, :keep]               # [L, keep]
    return np.asarray(jnp.sort(idx, axis=1))


def shrink_ffn_at(params: Any, idx: Any) -> Any:
    """Gather the kept FFN units at the given [L, keep] index rows — wi/wg
    columns and wo rows.  Applies to the param tree AND any tree sharing
    its structure (momentum buffers, FedDyn corrections)."""
    idx = jnp.asarray(idx)
    layers = params["layers"]
    mlp = dict(layers["mlp"])
    mlp["wi"] = jnp.take_along_axis(layers["mlp"]["wi"], idx[:, None, :], axis=2)
    if "wg" in mlp:
        mlp["wg"] = jnp.take_along_axis(layers["mlp"]["wg"], idx[:, None, :], axis=2)
    mlp["wo"] = jnp.take_along_axis(layers["mlp"]["wo"], idx[:, :, None], axis=1)
    new_layers = dict(layers)
    new_layers["mlp"] = mlp
    new_params = dict(params)
    new_params["layers"] = new_layers
    return new_params


def _unit_masks(params: Any, kept: Any) -> np.ndarray | None:
    """[L, d_ff] 0/1 kept-unit masks from a ``{"mlp": [L, keep]}`` kept
    map; None when no decision is in force (all-ones)."""
    idx = kept.get("mlp") if kept else None
    if idx is None:
        return None
    wi = params["layers"]["mlp"]["wi"]
    m = np.zeros((wi.shape[0], wi.shape[2]), np.float32)
    np.put_along_axis(m, np.asarray(idx), 1.0, axis=1)
    return m


def ffn_filter_masks(params: Any, kept: Any) -> dict:
    """``{"mlp": [L, d_ff] 0/1}`` filter keep-masks for kernel-mode masked
    compute — one mask row per scanned layer, riding into the layer scan
    alongside that layer's params."""
    m = _unit_masks(params, kept)
    if m is None:
        wi = params["layers"]["mlp"]["wi"]
        m = np.ones((wi.shape[0], wi.shape[2]), np.float32)
    return {"mlp": jnp.asarray(m)}


def ffn_param_masks(params: Any, kept: Any) -> Any:
    """Param-structured 0/1 masks in FACTORED form, with zeros on exactly
    the coordinates :func:`shrink_ffn_at` would slice away (wi/wg columns
    AND the coupled wo rows) — the scanned-stack analogue of
    ``pruning.param_masks``.  FedAP zeroes whole FFN units, so ``wi``/``wg``
    get [L, 1, d_ff] masks, ``wo`` [L, d_ff, 1], and every other leaf
    ``(1,) * ndim``: ``p * m`` broadcasts.  The shapes do not depend on
    ``kept`` (all-ones masks already have a decision's shapes).  The
    zeroed set is closed under the FFN coupling, so the masked forward
    equals the shrunk forward exactly: a zero pre-activation unit
    contributes silu(0) = gelu(0) = 0 through wo."""
    masks = jax.tree.map(lambda p: jnp.ones((1,) * p.ndim, jnp.float32),
                         params)
    if "mlp" not in params.get("layers", {}):
        return masks        # no dense FFN stack: nothing FedAP can zero
    m = _unit_masks(params, kept)
    if m is None:
        wi = params["layers"]["mlp"]["wi"]
        m = np.ones((wi.shape[0], wi.shape[2]), np.float32)
    unit = jnp.asarray(m)                                              # [L, ff]
    mlp = dict(masks["layers"]["mlp"])
    mlp["wi"] = unit[:, None, :]
    if "wg" in mlp:
        mlp["wg"] = unit[:, None, :]
    mlp["wo"] = unit[:, :, None]
    new_layers = dict(masks["layers"])
    new_layers["mlp"] = mlp
    masks = dict(masks)
    masks["layers"] = new_layers
    return masks


def prune_lm_ffn(params: Any, cfg: ModelConfig, rate: float,
                 *, align: int | None = 128) -> tuple[Any, ModelConfig, dict]:
    """Structurally shrink the FFN hidden dim of a scanned dense/vlm/hybrid
    stack.  Returns (new params, new config, info)."""
    idx = ffn_kept_indices(params, cfg, rate, align=align)
    d_ff = int(params["layers"]["mlp"]["wi"].shape[2])
    keep = int(idx.shape[1])
    new_params = shrink_ffn_at(params, idx)
    new_cfg = dataclasses.replace(cfg, d_ff=keep)
    return new_params, new_cfg, {"kept": keep, "of": d_ff,
                                 "realized_rate": 1.0 - keep / d_ff}


def prune_lm_experts(params: Any, cfg: ModelConfig, rate: float,
                     *, align: int | None = None,
                     min_keep: int | None = None) -> tuple[Any, ModelConfig, dict]:
    """Remove whole experts from a scanned MoE stack (expert-parallel-aware:
    keep counts stay divisible by the TP axis when align is set)."""
    if not cfg.moe:
        raise ValueError("not a MoE config")
    layers = params["layers"]
    scores = expert_scores(layers)                                     # [L, E]
    e = scores.shape[1]
    keep = _aligned_keep(e, rate, align, layer=f"moe expert stack (E={e})")
    if min_keep:
        keep = max(keep, min_keep)
    keep = min(max(keep, cfg.moe.top_k), e)
    idx = jnp.sort(jnp.argsort(scores, axis=1)[:, ::-1][:, :keep], axis=1)

    moe = dict(layers["moe"])
    moe["router"] = jnp.take_along_axis(layers["moe"]["router"], idx[:, None, :], axis=2)
    for name, ax in [("wi", 1), ("wg", 1), ("wo", 1)]:
        shaped = idx.reshape(idx.shape[0], keep, 1, 1)
        moe[name] = jnp.take_along_axis(layers["moe"][name], shaped, axis=ax)
    new_layers = dict(layers)
    new_layers["moe"] = moe
    new_params = dict(params)
    new_params["layers"] = new_layers
    new_cfg = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, num_experts=keep))
    return new_params, new_cfg, {"kept": keep, "of": e,
                                 "realized_rate": 1.0 - keep / e}


def fedap_lm(params: Any, cfg: ModelConfig, p_star: float,
             *, align: int | None = 128) -> tuple[Any, ModelConfig, dict]:
    """FedAP entry point for the LLM zoo: dispatch per family."""
    if cfg.moe:
        return prune_lm_experts(params, cfg, p_star, align=None,
                                min_keep=max(8, cfg.moe.top_k * 4))
    return prune_lm_ffn(params, cfg, p_star, align=align)
