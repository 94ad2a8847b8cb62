#!/usr/bin/env python3
"""Chip smoke test: FedDUMAP training and pruned serving at OLMo-1B widths.

    python chip_smoke.py               # one TPU chip
    python chip_smoke.py --four-chips  # four TPU chips of one host

One chip, in one process (no child processes):

1. device check: a TPU, or a non-zero exit naming the platform found;
2. JAX's persistent compile cache (``repro.utils.compile_cache``);
3. FedDUMAP fine-tuning of OLMo-1B (published widths, depth cut) through
   ``FederatedTrainer`` and ``PlanExecutor``, with a FedAP
   ``Prune(mode="mask")`` event and ``masked_compute="kernel"``, so the FFN
   matmuls run the Pallas ``masked_matmul`` kernel;
4. the pruned checkpoint saved, loaded ``masked`` and ``shrunk``, and
   served through ``DecodeEngine`` over the flash-decode kernel; the
   logits of one decode step of the two servables are compared;
5. the Mosaic kernels (``tpu_custom_call``) in the compiled training chunk
   and decode waves are counted, and zero fails the run.

``--four-chips`` runs only the paths that span chips: the same plan on
``MeshBackend`` (clients sharded over 4 chips) against ``LocalScanBackend``
on one chip, per round, and slot-sharded decoding against one-chip
decoding.

Every number is printed before the last line, which is one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Any failed phase raises, so the exit code is non-zero and that line is
never printed.
"""
from __future__ import annotations

import argparse
import collections
import dataclasses
import json
import pathlib
import sys
import tempfile
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core.plan import fedap_plan  # noqa: E402
from repro.core.pruning import FedAPConfig  # noqa: E402
from repro.core.rounds import FederatedTrainer, feddumap_config  # noqa: E402
from repro.data.pipeline import build_lm_federated_data  # noqa: E402
from repro.data.synthetic import TokenSpec  # noqa: E402
from repro.models.lm import LM  # noqa: E402
from repro.serving import DecodeEngine, ServeConfig, load_servable  # noqa: E402

ARCH = "olmo-1b"
# The most layers whose training chunk fits one v5e chip (16 GB HBM) with
# 2 clients per round: memory_analysis() of the chunk compiled for v5e
# gives 12.1 GiB at 4 layers and 14.0 GiB at 5, before the resident copy
# of the initial params and the FedAP probe gradients.
LAYERS = 4
# --four-chips: the one-chip reference leg holds all 4 clients of a round
# on one chip (the chunk needs 14.1 GiB at 2 layers), so it runs 1 layer.
FOUR_CHIP_LAYERS = 1
SEQ_LEN = 512
ROUNDS, PRUNE_ROUND = 4, 2

# Tolerances, as a share of the largest reference value.  The compared
# programs do the same bf16 arithmetic in a different order (masked vs
# shrunk: the FFN K-reduction runs over zero rows or without them; mesh vs
# one chip: partial sums plus an all-reduce).  Each path rounds every
# matmul output to bf16 once, so values may differ by a bf16 step (2**-7
# relative) per rounding, a few of which stack up through the layers.
LOGIT_TOL = 4 * 2.0 ** -7      # decode logits: 4 bf16 steps of max|logit|
PARAM_TOL = 4 * 2.0 ** -7      # trained params: 4 bf16 steps of max|leaf|
LOSS_TOL = 2.0 ** -7           # eval loss: one bf16 step of the loss


class SmokeFailure(RuntimeError):
    """A phase produced a wrong result."""


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def device_check(count: int) -> dict:
    """The device record of the last line; refuses anything but a TPU."""
    devs = jax.devices()
    d = devs[0]
    print(f"device: platform={d.platform} kind={d.device_kind} "
          f"count={len(devs)}")
    if d.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, JAX found platform "
                         f"{d.platform!r} ({d.device_kind})")
    if len(devs) < count:
        raise SystemExit(f"chip_smoke: needs {count} TPU chips, JAX found "
                         f"{len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def olmo_config(layers: int):
    """OLMo-1B at its published widths, depth cut to ``layers``."""
    return dataclasses.replace(get_config(ARCH), num_layers=layers)


def describe(cfg) -> None:
    print(f"model: {cfg.name} d_model={cfg.d_model} heads={cfg.num_heads} "
          f"kv_heads={cfg.num_kv_heads} head_dim={cfg.resolved_head_dim} "
          f"d_ff={cfg.d_ff} vocab={cfg.vocab_size} "
          f"tie_embeddings={cfg.tie_embeddings} norm={cfg.norm} "
          f"param_dtype={cfg.param_dtype} layers={cfg.num_layers} of 16")


def federated_data(cfg, *, seq_len: int = SEQ_LEN, num_sequences: int = 160,
                   seed: int = 0):
    """Seeded synthetic tokens: 8 topic-skewed clients, a server pool and
    a test split of ``seq_len``-token sequences."""
    return build_lm_federated_data(
        num_clients=8, test_fraction=0.05, seed=seed,
        spec=TokenSpec(vocab_size=cfg.vocab_size, seq_len=seq_len + 1,
                       num_sequences=num_sequences, seed=seed))


def fl_config(clients_per_round: int, *, seed: int = 0):
    """FedDUMAP with kernel-mode masked compute and 128-lane FedAP.  The
    probe size bounds the per-sample gradients FedAP holds at once."""
    return feddumap_config(
        num_clients=8, clients_per_round=clients_per_round, local_epochs=1,
        batch_size=2, server_batch_size=2, lr=1e-2, lr_decay=1.0, seed=seed,
        masked_compute="kernel",
        fedap=FedAPConfig(align=128, min_rate=0.5, probe_size=4,
                          participants=2))


def train(model, data, fl, *, backend: str = "local", mesh=None,
          label: str = ""):
    """``fedap_plan(ROUNDS, prune_round=PRUNE_ROUND, mode="mask")`` with an
    Eval after every round; prints and checks each round."""
    trainer = FederatedTrainer(model, data, fl, backend=backend, mesh=mesh)
    res = trainer.run(fedap_plan(ROUNDS, prune_round=PRUNE_ROUND,
                                 mode="mask", eval_every=1))
    h, prev = res.history, 0.0
    for r, loss, acc, tau, t in zip(h["round"], h["loss"], h["acc"],
                                    h["tau_eff"], h["time"]):
        extra = ", and the FedAP decision" if r == PRUNE_ROUND + 1 else ""
        print(f"{label}round {r}: loss {loss:.6f} acc {acc:.6f} "
              f"tau_eff {tau:.6f} wall {t - prev:.3f}s (includes "
              f"compilation{extra})")
        prev = t
    art = res.artifacts["prune"]
    print(f"{label}FedAP: p_star {art['p_star']:.6f} kept_counts "
          f"{art['kept_counts']} layer_rates {art['layer_rates']} "
          f"probe_size {fl.fedap.probe_size}")
    if not np.all(np.isfinite(h["loss"])):
        raise SmokeFailure(f"{label}non-finite eval loss: {h['loss']}")
    misaligned = {k: v for k, v in art["kept_counts"].items()
                  if v % fl.fedap.align}
    if misaligned:
        raise SmokeFailure(f"{label}FedAP kept counts not multiples of "
                           f"{fl.fedap.align}: {misaligned}")
    return trainer, res


def mosaic_kernels(compiled) -> int:
    """Pallas TPU kernels in a compiled program (0 in interpret mode)."""
    return compiled.as_text().count("tpu_custom_call")


def training_chunk(trainer, res):
    """The compiled one-round scan chunk the run used (masked engine)."""
    backend = trainer.backend(use_masks=True)
    spec = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,  # noqa: E731
                                          sharding=x.sharding)
    key = jax.random.key(0)
    return backend.chunk.lower(
        jax.tree.map(spec, res.state), spec(key),
        jax.tree.map(spec, backend.device_data()), length=1).compile()


def prompts_for(cfg, *, count: int = 16, lo: int = 64, hi: int = 256,
                seed: int = 0) -> list:
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, int(rng.integers(lo, hi + 1)))
            .astype(np.int32) for _ in range(count)]


def serve(servable, scfg: ServeConfig, prompts, *, mesh=None,
          label: str = ""):
    """All prompts through one ``DecodeEngine``; every completion must be
    ``ok``.  Returns the logits of one decode step taken after the first
    wave, while every slot is still prefilling its prompt (so the state is
    the same whatever the servable or placement), and the Mosaic kernel
    count of the compiled decode wave."""
    first = prompts[:scfg.slots]
    if scfg.steps_per_wave >= min(len(p) for p in first):
        raise ValueError("the first wave must end inside every prompt")
    engine = DecodeEngine(servable.model, servable.params, scfg,
                          masks=servable.masks, mesh=mesh)
    t0 = time.perf_counter()
    for p in first:
        engine.submit(p)
    engine.step_wave()
    logits = engine.next_logits()
    done = engine.run(prompts[scfg.slots:])
    dt = time.perf_counter() - t0
    status = collections.Counter(c.status for c in done)
    tokens = sum(len(c.tokens) for c in done)
    print(f"{label}: d_ff {servable.model.cfg.d_ff}, {len(done)} completions "
          f"{dict(status)}, {tokens} tokens in {dt:.3f}s (includes "
          f"compilation)")
    if len(done) != len(prompts) or status["ok"] != len(prompts):
        raise SmokeFailure(f"{label}: expected {len(prompts)} ok "
                           f"completions, got {dict(status)}")
    kernels = mosaic_kernels(engine.lower_wave().compile())
    print(f"{label}: decode wave tpu_custom_call count {kernels}")
    return logits, kernels


def compare(name: str, got, want, tol: float) -> float:
    """max|got - want| against ``tol`` x max|want|; raises past it."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    err = float(np.max(np.abs(got - want)))
    bound = tol * max(float(np.max(np.abs(want))), 1e-30)
    print(f"{name}: max abs diff {err:.6g} (tolerance {bound:.6g})")
    if not err <= bound:
        raise SmokeFailure(f"{name}: max abs diff {err} exceeds {bound}")
    return err


# ---------------------------------------------------------------------------
# the two paths
# ---------------------------------------------------------------------------

SERVE = ServeConfig(slots=8, cache_len=1024, max_prompt=256,
                    max_new_tokens=32, steps_per_wave=32)


def one_chip(cfg, *, seq_len: int = SEQ_LEN, num_sequences: int = 160,
             scfg: ServeConfig = SERVE, prompt_lens=(64, 256),
             n_prompts: int = 16, seed: int = 0) -> None:
    """Train, prune, save, serve masked and shrunk, count kernels."""
    describe(cfg)
    data = federated_data(cfg, seq_len=seq_len, num_sequences=num_sequences,
                          seed=seed)
    print(f"data: clients {data.client_x.shape} server {data.server_x.shape} "
          f"test {data.test_x.shape} (synthetic tokens, seed {seed})")
    trainer, res = train(LM(cfg), data, fl_config(2, seed=seed))

    chunk = training_chunk(trainer, res)
    train_kernels = mosaic_kernels(chunk)
    ma = chunk.memory_analysis()
    print(f"training chunk: tpu_custom_call count {train_kernels}; "
          f"memory_analysis args {ma.argument_size_in_bytes} temp "
          f"{ma.temp_size_in_bytes} bytes")

    prompts = prompts_for(cfg, count=n_prompts, lo=prompt_lens[0],
                          hi=prompt_lens[1], seed=seed)
    with tempfile.TemporaryDirectory() as ckpt:
        res.save(ckpt, model_config=cfg)
        del trainer, res, chunk        # free the training state's HBM
        logits, waves = {}, {}
        for mode in ("masked", "shrunk"):
            logits[mode], waves[mode] = serve(load_servable(ckpt, mode), scfg,
                                              prompts, label=f"serve {mode}")
    compare("masked vs shrunk decode_step logits", logits["masked"],
            logits["shrunk"], LOGIT_TOL)
    if train_kernels == 0 or 0 in waves.values():
        raise SmokeFailure(f"no Mosaic kernel ran: training chunk "
                           f"{train_kernels}, decode waves {waves}")


def four_chips(cfg, *, seed: int = 0) -> None:
    """The mesh backend and slot-sharded decoding, each against one chip."""
    from repro.launch.mesh import make_host_mesh

    describe(cfg)
    data = federated_data(cfg, seed=seed)
    fl = fl_config(4, seed=seed)
    mesh = make_host_mesh(model=1)
    print(f"mesh: {dict(mesh.shape)} over devices "
          f"{[d.id for d in mesh.devices.flat]}")

    _, local = train(LM(cfg), data, fl, label="local 1 chip ")
    want_hist = dict(local.history)
    want_kept = jax.tree.map(np.asarray, local.artifacts["prune"]["kept"])
    want_params = jax.device_get(local.params)
    del local
    _, meshed = train(LM(cfg), data, fl, backend="mesh", mesh=mesh,
                      label="mesh 4 chips ")
    leaf = jax.tree.leaves(meshed.params)[0]
    print(f"mesh params on devices {sorted(d.id for d in leaf.devices())}")
    got_kept = jax.tree.map(np.asarray, meshed.artifacts["prune"]["kept"])
    same = all(np.array_equal(got_kept[k], want_kept[k]) for k in want_kept)
    print(f"FedAP kept units identical on mesh and one chip: {same}")
    if not same:
        raise SmokeFailure("mesh FedAP decision differs from one chip")
    for r, (a, b) in enumerate(zip(meshed.history["loss"],
                                   want_hist["loss"]), 1):
        compare(f"round {r} eval loss, mesh vs one chip", [a], [b],
                LOSS_TOL)
    flat = jax.tree_util.tree_flatten_with_path(jax.device_get(meshed.params))
    for (path, got), want in zip(flat[0], jax.tree.leaves(want_params)):
        compare(f"final params {jax.tree_util.keystr(path)}, mesh vs one "
                f"chip", got, want, PARAM_TOL)

    s = load_servable(meshed, "masked", model_config=cfg)
    host = dataclasses.replace(s, params=jax.device_get(s.params),
                               masks=jax.device_get(s.masks))
    del meshed, s
    prompts = prompts_for(cfg, seed=seed)
    one, _ = serve(host, SERVE, prompts, label="serve masked, one chip")
    sharded, kernels = serve(host, SERVE, prompts, mesh=mesh,
                             label="serve masked, slots over 4 chips")
    compare("slot-sharded vs one-chip decode_step logits", sharded, one,
            LOGIT_TOL)
    if kernels == 0:
        raise SmokeFailure("no Mosaic kernel in the slot-sharded wave")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the mesh and slot-sharded paths, each "
                         "against one chip")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    device = device_check(4 if args.four_chips else 1)
    from repro.utils.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}")
    if args.four_chips:
        print(f"layers: {FOUR_CHIP_LAYERS} of 16 - the one-chip reference "
              f"leg holds all 4 clients of a round on one chip")
        four_chips(olmo_config(FOUR_CHIP_LAYERS), seed=args.seed)
    else:
        print(f"layers: {LAYERS} of 16 - the most whose training chunk "
              f"fits one chip's 16 GB with 2 clients per round (compiled "
              f"for v5e: 12.1 GiB at 4 layers, 14.0 GiB at 5)")
        one_chip(olmo_config(LAYERS), seed=args.seed)
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
