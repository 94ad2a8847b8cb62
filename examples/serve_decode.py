"""Serving demo: continuous-batching decode of (pruned) checkpoints.

For the scanned-KV families (dense / moe) this drives
``repro.serving.DecodeEngine``: a fixed pool of decode slots, requests
admitted as slots free up, prompts chunk-prefilled through the same
lockstep step, finished sequences retired via the on-device done-mask.
``--prune-rate`` serves a FedAP-style pruned model either ``masked``
(block-skipping masked_matmul at dense shapes) or ``shrunk`` (compacted
d_ff) — the FLOP cut the paper claims, measured at the tokens/s level.

  PYTHONPATH=src python examples/serve_decode.py --arch llama3-405b \\
      --requests 8 --slots 4 --tokens 16 --prune-rate 0.5 --serve-mode shrunk

Other families (encdec / ssm / hybrid / vlm) fall back to the plain
lockstep batch-decode loop through ``decode_step``:

  PYTHONPATH=src python examples/serve_decode.py --arch zamba2-1.2b --tokens 32
"""
import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCH_NAMES, get_config
from repro.models.api import build_model
from repro.utils.compile_cache import enable_compile_cache


def serve_continuous(cfg, args):
    """Engine path: continuous batching, optional pruned serving."""
    from repro.core import pruning_lm
    from repro.models.lm import LM
    from repro.serving import DecodeEngine, ServeConfig

    rng = np.random.default_rng(args.seed)
    model = LM(cfg)
    params = model.init(jax.random.key(args.seed))
    masks = None
    tag = "dense"
    if args.prune_rate > 0 and cfg.family != "dense":
        raise SystemExit("--prune-rate prunes the scanned FFN stack; use a "
                         "dense-family --arch")
    if args.prune_rate > 0:
        kept = pruning_lm.ffn_kept_indices(params, cfg, args.prune_rate,
                                           align=128)
        if args.serve_mode == "masked":
            masks = model.filter_masks(params, {"mlp": kept})
            # zero the pruned coordinates as mask-mode training would have
            params = jax.tree.map(
                lambda p, m: p * m, params,
                model.param_masks(params, {"mlp": kept}))
            tag = f"masked@{args.prune_rate}"
        else:
            params = pruning_lm.shrink_ffn_at(params, kept)
            cfg = dataclasses.replace(cfg, d_ff=int(np.asarray(kept).shape[-1]))
            model = LM(cfg)
            tag = f"shrunk@{args.prune_rate} (d_ff={cfg.d_ff})"

    scfg = ServeConfig(slots=args.slots,
                       cache_len=args.prompt + args.tokens,
                       max_prompt=args.prompt, max_new_tokens=args.tokens,
                       steps_per_wave=args.steps_per_wave)
    engine = DecodeEngine(model, params, scfg, masks=masks)
    prompts = [rng.integers(0, cfg.vocab_size,
                            size=rng.integers(1, args.prompt + 1))
               .astype(np.int32) for _ in range(args.requests)]

    # warm-up wave compiles the two programs outside the timed region
    engine.submit(prompts[0])
    while engine.pending:
        engine.step_wave()

    t0 = time.perf_counter()
    completions = engine.run(prompts)
    # engine.run host-syncs every wave (np.asarray on the done-mask), so
    # the clock reads AFTER the final wave's device work completed
    elapsed = time.perf_counter() - t0

    generated = sum(len(c.tokens) for c in completions)
    print(f"arch={cfg.name} (reduced, {tag}) slots={args.slots} "
          f"requests={args.requests} programs={engine.program_counts()}")
    print(f"{generated} tokens in {elapsed:.2f}s "
          f"({generated / elapsed:.1f} tok/s continuous batching)")
    print("sample:", completions[0].tokens[:16].tolist())


def serve_lockstep(cfg, args):
    """Legacy path for families without per-slot cache indices: every
    sequence at the same depth, one jitted decode_step in a host loop."""
    model = build_model(cfg)
    params = model.init(jax.random.key(args.seed))
    rng = np.random.default_rng(args.seed)

    cache_len = args.prompt + args.tokens
    cache = model.init_cache(args.slots, cache_len)
    batch_extra = {}
    if cfg.family == "encdec":
        batch_extra["enc_embeds"] = jnp.asarray(
            rng.standard_normal((args.slots, cfg.encoder.frames, cfg.d_model)),
            jnp.float32)
        cache = model.prefill_cross(params, cache, batch_extra)

    decode = jax.jit(model.decode_step)
    prompt = rng.integers(0, cfg.vocab_size, (args.slots, args.prompt))

    def step_input(tok):
        if cfg.family == "vlm":
            return {"embeds": jax.nn.one_hot(tok[:, 0], cfg.d_model,
                                             dtype=jnp.float32)[:, None]}
        return {"tokens": tok.astype(jnp.int32), **batch_extra}

    # prefill by stepping the prompt through the cache
    t0 = time.perf_counter()
    for t in range(args.prompt):
        logits, cache = decode(params, cache,
                               step_input(jnp.asarray(prompt[:, t:t + 1])))
    jax.block_until_ready(logits)       # time execution, not dispatch
    prefill_s = time.perf_counter() - t0

    # greedy decode
    out = []
    t0 = time.perf_counter()
    tok = jnp.argmax(logits[:, -1], -1)[:, None]
    for _ in range(args.tokens):
        logits, cache = decode(params, cache, step_input(tok))
        tok = jnp.argmax(logits[:, -1], -1)[:, None]
        out.append(tok[:, 0])
    gen = np.stack(jax.block_until_ready(out), 1)
    decode_s = time.perf_counter() - t0

    print(f"arch={cfg.name} (reduced) batch={args.slots}")
    print(f"prefill {args.prompt} tok: {prefill_s:.2f}s; "
          f"decode {args.tokens} tok: {decode_s:.2f}s "
          f"({args.slots * args.tokens / decode_s:.1f} tok/s)")
    print("sample:", gen[0][:16].tolist())


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3-405b", choices=list(ARCH_NAMES))
    ap.add_argument("--requests", type=int, default=8,
                    help="queued requests (engine path)")
    ap.add_argument("--slots", type=int, default=4,
                    help="decode-slot pool (engine) / batch (lockstep)")
    ap.add_argument("--prompt", type=int, default=16,
                    help="max prompt length")
    ap.add_argument("--tokens", type=int, default=32,
                    help="max new tokens per request")
    ap.add_argument("--steps-per-wave", type=int, default=8)
    ap.add_argument("--prune-rate", type=float, default=0.0,
                    help="FedAP-style FFN prune rate (engine path)")
    ap.add_argument("--serve-mode", default="shrunk",
                    choices=("masked", "shrunk"),
                    help="how to serve the pruned model")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    cfg = get_config(args.arch).reduced()
    if cfg.family in ("dense", "moe"):
        serve_continuous(cfg, args)
    else:
        serve_lockstep(cfg, args)


if __name__ == "__main__":
    main()
