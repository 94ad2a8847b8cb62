"""ResNet-18 (CIFAR variant) with GroupNorm, at its published widths: the
builder of its weights from the seed, its adapter to the program, its
operation count, and its plain reference.

The reference is written from the published architecture
(arXiv:1512.03385, CIFAR form: a 3x3 stem of 64 filters and no max-pool,
four stages of two basic blocks at 64-128-256-512 filters, the first
block of stages 2-4 strided by 2 with a 1x1 projection shortcut, global
average pooling, one dense layer), with GroupNorm of 8 groups in place of
BatchNorm (arXiv:1910.00189).  It is plain ``jax.numpy`` and
``lax.conv_general_dilated`` in float32 at ``Precision.HIGHEST`` and
imports nothing of the program.  Two departures from the paper, both the
program's and noted: every convolution carries a bias (zero at
initialisation), and a strided 3x3 convolution pads TensorFlow's "SAME"
way (0 rows before, 1 after) where the paper pads 1 on each side.

``precision="fp8"`` is the control: every convolution's and matmul's
operands rounded to float8 e4m3 with one scale per tensor, the step below
the bfloat16 operands that the configuration computes with.
"""
from __future__ import annotations

import functools
import math
import re

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
GROUPS = 8
EPS = 1e-5


def dims(sizes: dict) -> dict:
    return {"widths": tuple(sizes["stage_widths"]),
            "blocks": tuple(sizes["blocks_per_stage"]),
            "stem": sizes["stem_width"], "classes": sizes["num_classes"],
            "image": tuple(sizes["image_shape"])}


def _blocks(dm: dict):
    """(name, cin, cout, stride) of every basic block, in order."""
    cin = dm["stem"]
    for s, (cout, n) in enumerate(zip(dm["widths"], dm["blocks"])):
        for b in range(n):
            stride = 2 if (b == 0 and s > 0) else 1
            yield f"s{s}b{b}", cin, cout, stride
            cin = cout


# ---------------------------------------------------------------------------
# the program's side
# ---------------------------------------------------------------------------

def train_model(sizes: dict):
    """The program's ResNet18; refuses one of other widths."""
    from repro.models.cnn import ResNet18

    dm = dims(sizes)
    model = ResNet18(num_classes=dm["classes"], image_shape=dm["image"],
                     width=dm["stem"])
    widths = tuple(dm["stem"] * 2 ** s for s in range(len(model._stages)))
    if widths != dm["widths"] or tuple(model._stages) != dm["blocks"]:
        raise ValueError(f"program ResNet18 has widths {widths} and blocks "
                         f"{model._stages}, not {dm['widths']} {dm['blocks']}")
    return model


@functools.lru_cache(maxsize=None)
def _builder(widths, blocks, stem, classes, image):
    dm = {"widths": widths, "blocks": blocks, "stem": stem,
          "classes": classes, "image": image}

    def build(key):
        keys = iter(jax.random.split(key, 64))

        def conv(kh, cin, cout):
            fan = kh * kh * cin
            return {"w": jax.random.normal(next(keys), (kh, kh, cin, cout),
                                           jnp.float32) * math.sqrt(2 / fan),
                    "b": jnp.zeros((cout,), jnp.float32)}

        def gn(c):
            return {"scale": jnp.ones((c,), jnp.float32),
                    "bias": jnp.zeros((c,), jnp.float32)}

        p = {"stem": conv(3, image[-1], stem), "stem_gn": gn(stem)}
        for name, cin, cout, stride in _blocks(dm):
            blk = {"conv1": conv(3, cin, cout), "gn1": gn(cout),
                   "conv2": conv(3, cout, cout), "gn2": gn(cout)}
            if stride != 1 or cin != cout:
                blk["proj"] = conv(1, cin, cout)
            p[name] = blk
        fin = widths[-1]
        p["out"] = {"w": jax.random.normal(next(keys), (fin, classes),
                                           jnp.float32) * math.sqrt(2 / fin),
                    "b": jnp.zeros((classes,), jnp.float32)}
        return p

    return jax.jit(build)


def init_params(sizes: dict, key):
    """float32 weights made on the device in one jitted call from ``key``,
    in the program's parameter tree (He-normal convolutions and head,
    zero biases, unit GroupNorm scales)."""
    return _builder(**dims(sizes))(key)


# ---------------------------------------------------------------------------
# operations the model requires
# ---------------------------------------------------------------------------

def image_flops(sizes: dict) -> float:
    """Forward FLOPs of one image: every convolution at its output size
    and the dense head (norms and activations are not counted)."""
    dm = dims(sizes)
    h, w, c = dm["image"]
    f = 2 * 9 * c * dm["stem"] * h * w
    for _, cin, cout, stride in _blocks(dm):
        h, w = -(-h // stride), -(-w // stride)
        f += 2 * 9 * cin * cout * h * w + 2 * 9 * cout * cout * h * w
        if stride != 1 or cin != cout:
            f += 2 * cin * cout * h * w
    return f + 2 * dm["widths"][-1] * dm["classes"]


def train_flops(sizes: dict, hp: dict, kept) -> dict:
    """FLOPs of one federated round (forward and backward, 3x forward, of
    every client and server image) and of one eval."""
    imgs = (hp["clients_per_round"] * hp["local_steps"] * hp["batch_size"]
            + hp["tau"] * hp["server_batch_size"])
    per = image_flops(sizes)
    return {"round": 3 * imgs * per, "eval": hp["test_rows"] * per}


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------

def _fp8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _conv(x, p, stride, precision):
    w = p["w"]
    if precision == "fp8":
        x, w = _fp8(x), _fp8(w)
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=HIGHEST) + p["b"]


def _gn(x, p):
    b, h, w, c = x.shape
    g = math.gcd(GROUPS, c)
    xg = x.reshape(b, h, w, g, c // g)
    mean = jnp.mean(xg, axis=(1, 2, 4), keepdims=True)
    var = jnp.mean(jnp.square(xg - mean), axis=(1, 2, 4), keepdims=True)
    xg = (xg - mean) / jnp.sqrt(var + EPS)
    return xg.reshape(b, h, w, c) * p["scale"] + p["bias"]


def logits(params, x, precision: str = "f32"):
    """[B, classes] float32 logits of images ``x`` [B, H, W, C]."""
    h = jax.nn.relu(_gn(_conv(x, params["stem"], 1, precision),
                        params["stem_gn"]))
    blocks = sorted((tuple(map(int, m.groups())), k) for k in params
                    if (m := re.fullmatch(r"s(\d+)b(\d+)", k)))
    for (stage, block), name in blocks:
        blk = params[name]
        stride = 2 if (block == 0 and stage > 0) else 1
        y = jax.nn.relu(_gn(_conv(h, blk["conv1"], stride, precision),
                            blk["gn1"]))
        y = _gn(_conv(y, blk["conv2"], 1, precision), blk["gn2"])
        sc = _conv(h, blk["proj"], stride, precision) if "proj" in blk else h
        h = jax.nn.relu(y + sc)
    h = jnp.mean(h, axis=(1, 2))
    w = params["out"]["w"]
    if precision == "fp8":
        h, w = _fp8(h), _fp8(w)
    return jnp.dot(h, w, precision=HIGHEST) + params["out"]["b"]


def loss_and_acc(params, x, y, fmask=None, precision: str = "f32"):
    """Mean cross-entropy and accuracy over the batch (no masks: this
    configuration is trained without FedAP)."""
    lg = logits(params, x, precision)
    logp = jax.nn.log_softmax(lg, axis=-1)
    nll = -jnp.take_along_axis(logp, y[:, None], axis=-1)[:, 0]
    return jnp.mean(nll), jnp.mean((jnp.argmax(lg, -1) == y)
                                   .astype(jnp.float32))
