"""Public model-building API + input specs for every (arch x shape).

``input_specs`` is the single source of truth for what each input shape
means per family — concrete arrays for smoke tests, ShapeDtypeStruct
stand-ins (no allocation) for compile rehearsals.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import InputShape, ModelConfig
from repro.models.lm import LM


def build_model(cfg: ModelConfig, *, attn_impl: str = "xla") -> LM:
    return LM(cfg, attn_impl=attn_impl)


def _pos_streams(cfg: ModelConfig) -> int:
    return {"none": 1, "1d": 1, "2d": 2, "mrope": 3}[cfg.rope]


def decode_cache_len(cfg: ModelConfig, shape: InputShape) -> int:
    """Visible context during decode.  long_500k uses the sliding window
    (ring-buffer) for attention archs; SSM/hybrid have O(1) state anyway."""
    if shape.name == "long_500k" and cfg.sliding_window:
        return min(shape.seq_len, cfg.sliding_window)
    return shape.seq_len


def input_specs(cfg: ModelConfig, shape: InputShape, *, abstract: bool = True,
                seed: int = 0) -> dict[str, Any]:
    """Batch pytree for (cfg, shape).

    abstract=True  -> jax.ShapeDtypeStruct leaves (lowering only)
    abstract=False -> concrete random arrays (smoke tests / benchmarks)
    """
    b, s = shape.global_batch, shape.seq_len
    dtype = jnp.dtype(cfg.param_dtype)
    rng = np.random.default_rng(seed)

    def arr(shp, dt, high=None):
        if abstract:
            return jax.ShapeDtypeStruct(shp, dt)
        if jnp.issubdtype(dt, jnp.integer):
            return jnp.asarray(rng.integers(0, high or cfg.vocab_size, shp), dt)
        return jnp.asarray(rng.standard_normal(shp), dt)

    if shape.kind == "decode":
        s_tok = 1
    else:
        s_tok = s

    batch: dict[str, Any] = {}
    if cfg.family == "vlm":
        batch["embeds"] = arr((b, s_tok, cfg.d_model), dtype)
        batch["positions"] = arr((_pos_streams(cfg), b, s_tok), jnp.int32, high=s)
    elif cfg.family == "encdec":
        batch["enc_embeds"] = arr((b, cfg.encoder.frames, cfg.d_model), dtype)
        batch["tokens"] = arr((b, s_tok), jnp.int32)
    else:
        batch["tokens"] = arr((b, s_tok), jnp.int32)

    if shape.kind == "train":
        batch["labels"] = arr((b, s_tok), jnp.int32)
        if cfg.family == "vlm":
            if abstract:
                batch["loss_mask"] = jax.ShapeDtypeStruct((b, s_tok), jnp.float32)
            else:  # vision-token positions excluded from the LM loss
                batch["loss_mask"] = jnp.asarray(
                    rng.random((b, s_tok)) > 0.25, jnp.float32)
    return batch
