"""OLMo-1B at its published widths, at the depth its configuration file
gives: the builder of its weights from the seed, its adapter to the
program, and its plain reference.  Each OLMo-1B configuration under
``bench/configs/`` loads this module.

The reference is written from the published architecture (arXiv:2402.00838):
token embedding tied to the output head, pre-norm blocks with LayerNorm
without scale or bias, multi-head causal attention with rotary embeddings
(interleaved pairs, base 10000), SwiGLU feed-forward.  It is plain
``jax.numpy``, one layer after the other, with no kernel, cache or
batching, and imports nothing of the program.  ``precision="bf16"``, the
configuration's, multiplies bfloat16 operands with float32 accumulation
and keeps everything else in float32 (the training reference);
``precision="f32"`` computes in float32 at ``Precision.HIGHEST``.  ``fmask`` rows ([L, d_ff] 0/1) zero the pruned FFN units at the
pre-activation, which is what FedAP's mask mode means.

``precision="fp8"`` is the control, the step below the configuration's
bfloat16, as float8 training computes: every matmul's operands rounded to
e4m3 and the gradient of its output to e5m2, each with one scale per
tensor (its largest magnitude mapped to the type's largest).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
E4M3 = jnp.float8_e4m3fn
EPS = 1e-5
# the precision the configuration states, in which the training reference
# computes (the control computes one step below it, in float8)
REFERENCE_PRECISION = "bf16"


def dims(sizes: dict) -> dict:
    return {"L": sizes["num_hidden_layers"], "d": sizes["hidden_size"],
            "h": sizes["num_attention_heads"],
            "kv": sizes["num_key_value_heads"], "hd": sizes["head_dim"],
            "ff": sizes["intermediate_size"], "V": sizes["vocab_size"]}


# ---------------------------------------------------------------------------
# the program's side
# ---------------------------------------------------------------------------

def program_config(sizes: dict):
    """The program's own OLMo-1B config at this file's sizes; refuses a
    program config that resolves to other sizes or another architecture."""
    from repro.configs import get_config

    dm = dims(sizes)
    cfg = dataclasses.replace(
        get_config("olmo-1b"), num_layers=dm["L"], d_model=dm["d"],
        num_heads=dm["h"], num_kv_heads=dm["kv"], head_dim=dm["hd"],
        d_ff=dm["ff"], vocab_size=dm["V"])
    got = {"d": cfg.d_model, "h": cfg.num_heads, "kv": cfg.num_kv_heads,
           "hd": cfg.resolved_head_dim, "ff": cfg.d_ff, "V": cfg.vocab_size,
           "L": cfg.num_layers}
    want = dims(sizes)
    if got != want or cfg.norm != "nonparam" or not cfg.tie_embeddings \
            or cfg.param_dtype != "bfloat16" or cfg.act != "silu":
        raise ValueError(f"program config {got} differs from {want}")
    return dataclasses.replace(cfg, param_dtype=param_dtype(sizes))


def train_model(sizes: dict):
    """The model the federated trainer drives (training attention in XLA,
    the masked FFN on the Pallas kernel)."""
    from repro.models.lm import LM

    return LM(program_config(sizes))


def servable(sizes: dict, params, kept: dict):
    """A mask-mode FedAP checkpoint served the way ``load_servable(...,
    "auto")`` serves it: masked FFN and the flash-decode kernel."""
    from repro.serving import load_servable

    art = {"params": params, "kept": kept, "filter_masks": None,
           "mode": "mask", "model_config": program_config(sizes)}
    return load_servable(art, "auto")


def param_dtype(sizes: dict) -> str:
    """The dtype the program keeps the weights in: the configuration's
    ``param_dtype`` (float32 master weights in training), else the
    published checkpoint's bfloat16."""
    return sizes.get("param_dtype", "bfloat16")


@functools.lru_cache(maxsize=None)
def _builder(L, d, h, kv, hd, ff, V, dtype):
    def build(key):
        ks = jax.random.split(key, 8)

        def n(k, shape, scale):
            # bfloat16 values, as a checkpoint holds them, kept in ``dtype``
            return (jax.random.normal(k, shape, jnp.float32) * scale
                    ).astype(jnp.bfloat16).astype(dtype)

        return {
            "embed": n(ks[0], (V, d), d ** -0.5),
            "norm_out": {},
            "layers": {
                "attn": {"wq": n(ks[1], (L, d, h, hd), d ** -0.5),
                         "wk": n(ks[2], (L, d, kv, hd), d ** -0.5),
                         "wv": n(ks[3], (L, d, kv, hd), d ** -0.5),
                         "wo": n(ks[4], (L, h, hd, d), (h * hd) ** -0.5)},
                "norm_a": {}, "norm_f": {},
                "mlp": {"wi": n(ks[5], (L, d, ff), d ** -0.5),
                        "wg": n(ks[6], (L, d, ff), d ** -0.5),
                        "wo": n(ks[7], (L, ff, d), ff ** -0.5)},
            },
        }

    return jax.jit(build)


def init_params(sizes: dict, key):
    """Weights of bfloat16 values, in ``param_dtype(sizes)``, made on the
    device in one jitted call from ``key``, in the program's parameter
    tree."""
    return _builder(**dims(sizes), dtype=param_dtype(sizes))(key)


def kept_units(sizes: dict, rate: float, align: int,
               rng: np.random.Generator) -> dict:
    """FedAP's decision in index form, drawn from the seed: per layer the
    same aligned count of FFN units, scattered over the layer."""
    dm = dims(sizes)
    keep = dm["ff"] - int(np.floor(rate * dm["ff"]))
    keep = int(np.ceil(keep / align) * align)
    rows = [np.sort(rng.choice(dm["ff"], keep, replace=False))
            for _ in range(dm["L"])]
    return {"mlp": np.stack(rows).astype(np.int32)}


def mask_params(tree, fmask):
    """Zero the pruned units' weights in a parameter-shaped tree: their
    columns of the up and gate projections, their rows of the down one."""
    mlp = tree["layers"]["mlp"]
    masked = {"wi": mlp["wi"] * fmask[:, None, :],
              "wg": mlp["wg"] * fmask[:, None, :],
              "wo": mlp["wo"] * fmask[:, :, None]}
    return {**tree, "layers": {**tree["layers"], "mlp": masked}}


def train_flops(sizes: dict, hp: dict, kept) -> dict:
    """FLOPs of one federated round and of one eval (``bench/lm_math``),
    the kept FFN units only."""
    from bench import lm_math

    dm = dims(sizes)
    ff = dm["ff"] if kept is None else int(kept["mlp"].shape[1])
    return {"round": lm_math.train_round_flops(dm, hp, ff),
            "eval": lm_math.eval_flops(dm, hp, ff)}


def filter_rows(sizes: dict, kept: dict) -> np.ndarray:
    """[L, d_ff] 0/1 rows of the kept units."""
    dm = dims(sizes)
    rows = np.zeros((dm["L"], dm["ff"]), np.float32)
    for layer, idx in enumerate(kept["mlp"]):
        rows[layer, idx] = 1.0
    return rows


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------

def _on_grid8(x, dtype):
    """``x`` on a float8 type's grid with one scale per tensor (its largest
    magnitude mapped to the type's largest): (grid values, scale)."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / float(jnp.finfo(dtype).max)
    return (x / s).astype(dtype).astype(jnp.float32), s


def _dot(spec, a, b):
    # float8 grid values are exact in bfloat16, so one bfloat16 pass with
    # float32 accumulation multiplies them exactly
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.DEFAULT,
                      preferred_element_type=jnp.float32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _mm8(spec, a, b):
    """A float8 matmul: operands in e4m3, the gradient of its output in
    e5m2, float32 accumulation."""
    (qa, sa), (qb, sb) = _on_grid8(a, E4M3), _on_grid8(b, E4M3)
    return _dot(spec, qa, qb) * (sa * sb)


def _mm8_fwd(spec, a, b):
    (qa, sa), (qb, sb) = _on_grid8(a, E4M3), _on_grid8(b, E4M3)
    return _dot(spec, qa, qb) * (sa * sb), (qa, sa, qb, sb)


def _mm8_bwd(spec, res, ct):
    qa, sa, qb, sb = res
    qg, sg = _on_grid8(ct, jnp.float8_e5m2)
    _, vjp = jax.vjp(lambda x, y: _dot(spec, x, y), qa, qb)
    da, db = vjp(qg)
    return da * (sb * sg), db * (sa * sg)


_mm8.defvjp(_mm8_fwd, _mm8_bwd)


def _mm(spec, a, b, precision):
    if precision == "fp8":
        return _mm8(spec, a, b)
    if precision == "bf16":
        return jnp.einsum(spec, a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _layernorm(x):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + EPS)


def _rope(x, positions):
    hd = x.shape[-1]
    freqs = 1.0 / (10000.0 ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = positions.astype(jnp.float32)[:, None] * freqs         # [S, hd/2]
    sin, cos = jnp.sin(ang)[None, :, None], jnp.cos(ang)[None, :, None]
    x1, x2 = x[..., ::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def logits(params, tokens, fmask, precision: str = "f32"):
    """[B, S, V] float32 logits of the whole sequence."""
    p = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    lay = p["layers"]
    n_layers = lay["attn"]["wq"].shape[0]
    b, s = tokens.shape
    x = p["embed"][tokens]
    pos = jnp.arange(s)
    causal = jnp.tril(jnp.ones((s, s), bool))
    for i in range(n_layers):
        at, ml = ({k: v[i] for k, v in lay[g].items()} for g in ("attn", "mlp"))
        h = _layernorm(x)
        q = _rope(_mm("bsd,dhk->bshk", h, at["wq"], precision), pos)
        k = _rope(_mm("bsd,dhk->bshk", h, at["wk"], precision), pos)
        v = _mm("bsd,dhk->bshk", h, at["wv"], precision)
        sc = _mm("bqhd,bkhd->bhqk", q, k, precision) / np.sqrt(q.shape[-1])
        w = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        o = _mm("bhqk,bkhd->bqhd", w, v, precision)
        x = x + _mm("bshk,hkd->bsd", o, at["wo"], precision)
        h = _layernorm(x)
        m = fmask[i]
        up = _mm("bsd,df->bsf", h, ml["wi"], precision) * m
        gate = _mm("bsd,df->bsf", h, ml["wg"], precision) * m
        x = x + _mm("bsf,fd->bsd", jax.nn.silu(gate) * up, ml["wo"],
                    precision)
    return _mm("bsd,vd->bsv", _layernorm(x), p["embed"], precision)


def loss_and_acc(params, x, y, fmask, precision: str = "f32"):
    """Mean next-token cross-entropy and token accuracy."""
    lg = logits(params, x, fmask, precision)
    logp = jax.nn.log_softmax(lg, axis=-1)
    nll = -jnp.take_along_axis(logp, y[..., None], axis=-1)[..., 0]
    return jnp.mean(nll), jnp.mean((jnp.argmax(lg, -1) == y)
                                   .astype(jnp.float32))
