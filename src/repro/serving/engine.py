"""Continuous-batching decode engine over the flash-decode kernel.

The inference leg of FedDUMAP: a trained (optionally FedAP-pruned)
checkpoint is served from a FIXED pool of decode slots, so the pruned
model's FLOP cut is realized where the paper's efficiency claim matters —
tokens/s under load.

Design:

* **Slot pool.**  ``ServeConfig.slots`` decode slots form the batch axis
  of ONE model decode cache; each slot owns a KV-cache page (its row of
  ``cache["k"]/["v"]``) and a fill level (``cache["index"]`` is an int32
  ``[slots]`` vector — the continuous-batching extension of
  ``LM.decode_step``).  Attention over a slot's page is masked to its own
  valid prefix (``kernels.decode_attention`` ``lengths``), so slots at
  different depths — and stale rows from a page's previous occupant —
  never leak across requests.

* **Lockstep waves.**  The device runs ``steps_per_wave`` decode steps
  per launch as one ``lax.scan``.  Prompts prefill THROUGH the same step
  (one prompt token per step — chunked prefill), then generation
  continues seamlessly: the step input switches from the prompt buffer to
  the previous argmax on device.

* **On-device done-mask.**  A slot that reaches ``max_new_tokens`` (or
  ``eos_id``) flips its ``active`` bit in the carry and freezes — its
  cache index, output count and last token stop advancing.  There is NO
  per-token host sync: the host reads ``active`` once per wave to retire
  finished requests and admit queued ones into the freed slots.

* **Zero re-traces.**  All slot state lives in fixed-structure,
  fixed-shape device arrays, so the whole serving session compiles
  exactly TWO programs — ``_admit`` (one slot write) and ``_wave`` (the
  step scan) — no matter how many requests are admitted or retired
  (locked by the ``serving/*`` compile-budget scenarios).

* **Fault tolerance.**  An in-wave health guard retires any slot whose
  logits go non-finite (``error`` bit in the carry; the request completes
  with ``status="error"`` instead of poisoning the shared batch), and
  ``ServeConfig.max_queue``/``on_full`` bound the host admission queue
  (raise :class:`QueueFull` or count-and-drop).

* **Pruned checkpoints** serve either *masked* (dense shapes, FFN matmuls
  through the block-skipping ``masked_matmul`` kernel via
  ``decode_step(..., masks=)``) or *shrunk* (compacted shapes); see
  :mod:`repro.serving.checkpoint`.

* **Mesh throughput** (optional): pass ``mesh=`` to shard the slot axis
  over the mesh's data axis — slot state, KV pages and the decode batch
  all partition; the host protocol is unchanged.
"""
from __future__ import annotations

import collections
import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Engine shape knobs (all static: they size the two compiled programs).

    slots           decode-slot pool == device batch of the step
    cache_len       per-slot KV page length (max prompt+generated context)
    max_prompt      admission pads prompts to this many tokens
    max_new_tokens  per-request generation budget
    eos_id          stop token (-1: never stop early)
    steps_per_wave  decode steps per device launch — the host-sync cadence
                    (admission latency vs. launch overhead trade-off)
    max_queue       backpressure bound on the host admission queue
                    (None: unbounded — the pre-backpressure behaviour)
    on_full         what ``submit`` does at the bound: "raise" a
                    :class:`QueueFull`, or "reject" (drop the request,
                    count it in ``DecodeEngine.rejected``, return None)
    """

    slots: int = 8
    cache_len: int = 64
    max_prompt: int = 16
    max_new_tokens: int = 16
    eos_id: int = -1
    steps_per_wave: int = 8
    max_queue: Optional[int] = None
    on_full: str = "raise"

    def __post_init__(self):
        if self.slots < 1:
            raise ValueError(f"slots must be >= 1, got {self.slots}")
        if self.max_queue is not None and self.max_queue < 1:
            raise ValueError(
                f"max_queue must be None or >= 1, got {self.max_queue}")
        if self.on_full not in ("raise", "reject"):
            raise ValueError(
                f"on_full must be 'raise' or 'reject', got {self.on_full!r}")
        if not 1 <= self.max_prompt <= self.cache_len:
            raise ValueError(
                f"max_prompt must be in [1, cache_len={self.cache_len}], "
                f"got {self.max_prompt}")
        if self.max_prompt + self.max_new_tokens - 1 > self.cache_len:
            raise ValueError(
                f"cache_len={self.cache_len} cannot hold max_prompt="
                f"{self.max_prompt} + max_new_tokens={self.max_new_tokens} "
                f"- 1 context tokens")
        if self.max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {self.max_new_tokens}")
        if self.steps_per_wave < 1:
            raise ValueError(
                f"steps_per_wave must be >= 1, got {self.steps_per_wave}")


class QueueFull(RuntimeError):
    """``submit`` hit ``ServeConfig.max_queue`` with ``on_full="raise"``."""


@dataclasses.dataclass(frozen=True)
class Completion:
    """One finished request: ``tokens`` are the generated ids (prompt
    excluded), in generation order.  ``status`` is ``"ok"`` for a normal
    finish, ``"error"`` when the slot was retired by the in-wave health
    guard (non-finite logits); an error completion carries the tokens
    generated before the fault."""

    uid: int
    prompt: np.ndarray
    tokens: np.ndarray
    status: str = "ok"


# Families whose decode cache is the scanned [L, B, S, KV, hd] KV stack —
# the per-slot index/validity semantics the engine relies on.
_SERVABLE_FAMILIES = ("dense", "moe", "vlm")


class DecodeEngine:
    """Continuous-batching argmax decoding over ``model.decode_step``.

    ``masks`` (optional) is the FedAP filter keep-mask tree
    (``{"mlp": [L, d_ff]}``) — when given, every step routes the FFN
    matmuls through the block-skipping masked kernel (masked serving of a
    mask-mode pruned checkpoint).  ``mesh`` (optional) shards the slot
    axis over ``mesh_axis``.
    """

    def __init__(self, model, params, cfg: ServeConfig | None = None, *,
                 masks=None, mesh=None, mesh_axis: str = "data",
                 faults: tuple = ()):
        if model.cfg.family not in _SERVABLE_FAMILIES:
            raise ValueError(
                f"DecodeEngine serves the scanned-KV families "
                f"{_SERVABLE_FAMILIES}, not {model.cfg.family!r} (ssm/"
                f"hybrid/encdec decode state has no per-slot cache index)")
        self.model = model
        self.cfg = cfg or ServeConfig()
        self._faults = tuple(f for f in faults if hasattr(f, "apply_logits"))
        self._masks = masks
        self._mesh = mesh
        self._mesh_axis = mesh_axis
        if mesh is not None:
            n = mesh.shape[mesh_axis]
            if self.cfg.slots % n:
                raise ValueError(
                    f"slots={self.cfg.slots} must divide over the "
                    f"{n}-way mesh axis {mesh_axis!r}")
        self._params = self._place(params, batched=False)
        if masks is not None:
            self._masks = self._place(masks, batched=False)
        wave, logits = self._wave_fn, self._logits_fn
        if mesh is not None:
            # GSPMD cannot partition the Mosaic kernels (flash-decode, the
            # masked FFN): each device steps its own slots in a shard_map
            # over the whole mesh — the step is slot-parallel throughout
            from jax.sharding import PartitionSpec as P

            specs = (P(), self._state_specs(self._init_state()))
            wave = jax.shard_map(wave, mesh=mesh, in_specs=specs,
                                 out_specs=specs[1], check_vma=False)
            logits = jax.shard_map(logits, mesh=mesh, in_specs=specs,
                                   out_specs=P(mesh_axis), check_vma=False)
        self._admit = jax.jit(self._admit_fn, donate_argnums=(0,))
        self._wave = jax.jit(wave, donate_argnums=(1,))
        self._logits = jax.jit(logits)
        self._state = self._place_state(self._init_state())
        self._occupants: list[Optional[tuple[int, np.ndarray]]] = \
            [None] * self.cfg.slots
        self._queue: collections.deque = collections.deque()
        self._next_uid = 0
        self.rejected = 0  # requests dropped by on_full="reject" backpressure

    # -- state ------------------------------------------------------------
    def _init_state(self) -> dict:
        c = self.cfg
        cache = self.model.init_cache(c.slots, c.cache_len)
        cache["index"] = jnp.zeros((c.slots,), jnp.int32)
        return {
            "cache": cache,
            "active": jnp.zeros((c.slots,), bool),
            "last_tok": jnp.zeros((c.slots,), jnp.int32),
            "prompt": jnp.zeros((c.slots, c.max_prompt), jnp.int32),
            "prompt_len": jnp.ones((c.slots,), jnp.int32),
            "n_out": jnp.zeros((c.slots,), jnp.int32),
            "out": jnp.zeros((c.slots, c.max_new_tokens), jnp.int32),
            "error": jnp.zeros((c.slots,), bool),
            # global slot ids: under a mesh each device sees only its own
            "slot": jnp.arange(c.slots, dtype=jnp.int32),
        }

    def _specs(self, tree, *, batched: bool, cache: bool = False):
        """PartitionSpecs over the mesh: replicated when ``batched=False``,
        else the slot axis over ``mesh_axis``."""
        from jax.sharding import PartitionSpec as P

        ax = self._mesh_axis

        def spec(leaf):
            if not batched:
                return P()
            if cache and jnp.ndim(leaf) > 1:
                # scanned KV stacks [L, slots, S, KV, hd]: batch is axis 1
                return P(None, ax)
            return P(ax)

        return jax.tree.map(spec, tree)

    def _state_specs(self, state: dict) -> dict:
        specs = {k: self._specs(v, batched=True)
                 for k, v in state.items() if k != "cache"}
        specs["cache"] = self._specs(state["cache"], batched=True,
                                     cache=True)
        return specs

    def _place(self, tree, *, batched: bool):
        """device_put with the mesh sharding (replicated when
        ``batched=False``); the default device on a mesh-less engine, so
        host params (a loaded checkpoint) are uploaded once, not per
        wave."""
        if self._mesh is None:
            return jax.device_put(tree)
        from jax.sharding import NamedSharding

        return jax.tree.map(
            lambda leaf, spec: jax.device_put(leaf,
                                              NamedSharding(self._mesh, spec)),
            tree, self._specs(tree, batched=batched))

    def _place_state(self, state: dict) -> dict:
        if self._mesh is None:
            return state
        from jax.sharding import NamedSharding

        return jax.tree.map(
            lambda leaf, spec: jax.device_put(leaf,
                                              NamedSharding(self._mesh, spec)),
            state, self._state_specs(state))

    # -- the two compiled programs ---------------------------------------
    def _admit_fn(self, state, slot, prompt, plen):
        """Write one queued request into a freed slot.  Fixed shapes (the
        prompt arrives padded to max_prompt) and a traced slot index: ONE
        program for every admission.  The slot's cache page is NOT
        cleared — index=0 re-grows the valid prefix, so the previous
        occupant's rows are only ever attended after being overwritten."""
        st = dict(state)
        cache = dict(st["cache"])
        cache["index"] = cache["index"].at[slot].set(0)
        st["cache"] = cache
        st["active"] = st["active"].at[slot].set(True)
        st["prompt"] = st["prompt"].at[slot].set(prompt)
        st["prompt_len"] = st["prompt_len"].at[slot].set(plen)
        st["last_tok"] = st["last_tok"].at[slot].set(prompt[0])
        st["n_out"] = st["n_out"].at[slot].set(0)
        st["error"] = st["error"].at[slot].set(False)
        return st

    def _step(self, params, state):
        """One lockstep decode step for every slot (done slots frozen)."""
        c = self.cfg
        cache = state["cache"]
        idx = cache["index"]                         # [B] pre-step fill
        active = state["active"]
        logits, cache = self.model.decode_step(
            params, cache, {"tokens": state["last_tok"][:, None]},
            masks=self._masks)
        for f in self._faults:  # lint: static-branch (test-only injection)
            logits = f.apply_logits(logits, state)
        # in-wave health guard: a slot whose logits go non-finite is
        # retired on device (error bit set, slot frozen) instead of
        # emitting garbage tokens.  Same fixed state structure and no
        # host sync — the session still compiles exactly two programs.
        ok = jnp.all(jnp.isfinite(logits[:, 0]), axis=-1)
        bad = active & ~ok
        live = active & ok
        cache = dict(cache)
        # done-mask: frozen (and newly-errored) slots keep their fill
        # level (their page write lands on a slot that stays invalid —
        # never attended)
        cache["index"] = jnp.where(live, cache["index"], idx)
        sampled = jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)

        consumed = idx + 1                           # tokens seen after step
        in_prefill = consumed < state["prompt_len"]  # next input from prompt
        nxt_prompt = jnp.take_along_axis(
            state["prompt"],
            jnp.minimum(consumed, c.max_prompt - 1)[:, None], axis=1)[:, 0]
        # a step that consumed the prompt's last token (or any later one)
        # emits a generated token
        emitted = live & (consumed >= state["prompt_len"])
        row = jnp.arange(active.shape[0])      # this device's slots
        pos = jnp.clip(state["n_out"], 0, c.max_new_tokens - 1)
        out = state["out"].at[row, pos].set(
            jnp.where(emitted, sampled, state["out"][row, pos]))
        n_out = state["n_out"] + emitted.astype(jnp.int32)
        finished = emitted & ((n_out >= c.max_new_tokens) |
                              (sampled == c.eos_id))
        last_tok = jnp.where(
            live, jnp.where(in_prefill, nxt_prompt, sampled),
            state["last_tok"])
        return {
            "cache": cache,
            "active": active & ~finished & ~bad,
            "last_tok": last_tok,
            "prompt": state["prompt"],
            "prompt_len": state["prompt_len"],
            "n_out": n_out,
            "out": out,
            "error": state["error"] | bad,
            "slot": state["slot"],
        }

    def _logits_fn(self, params, state):
        logits, _ = self.model.decode_step(
            params, state["cache"], {"tokens": state["last_tok"][:, None]},
            masks=self._masks)
        return logits[:, 0]

    def _wave_fn(self, params, state):
        def body(st, _):
            return self._step(params, st), None

        st, _ = jax.lax.scan(body, state, None,
                             length=self.cfg.steps_per_wave)
        return st

    # -- host protocol ----------------------------------------------------
    def submit(self, prompt) -> Optional[int]:
        """Queue a request; returns its uid (completion order may differ
        from submission order — slots free up raggedly).  With a
        ``max_queue`` bound and the host queue full, either raises
        :class:`QueueFull` (``on_full="raise"``) or drops the request and
        returns None (``on_full="reject"``, counted in ``rejected``)."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if not 1 <= prompt.shape[0] <= self.cfg.max_prompt:
            raise ValueError(
                f"prompt length {prompt.shape[0]} outside [1, "
                f"max_prompt={self.cfg.max_prompt}]")
        if (self.cfg.max_queue is not None
                and len(self._queue) >= self.cfg.max_queue):
            if self.cfg.on_full == "raise":
                raise QueueFull(
                    f"admission queue at max_queue={self.cfg.max_queue} "
                    f"(drain with step_wave/run, or use on_full='reject')")
            self.rejected += 1
            return None
        uid = self._next_uid
        self._next_uid += 1
        self._queue.append((uid, prompt))
        return uid

    @property
    def pending(self) -> int:
        """Queued + in-flight request count."""
        return len(self._queue) + sum(o is not None for o in self._occupants)

    def step_wave(self) -> list[Completion]:
        """Admit into free slots, run one wave, retire finished requests.
        The building block of :meth:`run` — exposed for callers that
        interleave submission with decoding."""
        for slot in range(self.cfg.slots):
            if self._occupants[slot] is None and self._queue:
                uid, prompt = self._queue.popleft()
                padded = np.zeros((self.cfg.max_prompt,), np.int32)
                padded[:prompt.shape[0]] = prompt
                self._state = self._admit(
                    self._state, slot, self._place(jnp.asarray(padded),
                                                   batched=False),
                    prompt.shape[0])
                self._occupants[slot] = (uid, prompt)
        self._state = self._wave(self._params, self._state)
        # the wave's ONLY host sync: the done-mask (and, for slots that
        # finished, their token counts and output rows)
        active = np.asarray(self._state["active"])
        done = [slot for slot, occ in enumerate(self._occupants)
                if occ is not None and not active[slot]]
        if not done:
            return []
        n_out = np.asarray(self._state["n_out"])
        out = np.asarray(self._state["out"])
        error = np.asarray(self._state["error"])
        completions = []
        for slot in done:
            uid, prompt = self._occupants[slot]
            completions.append(
                Completion(uid, prompt, out[slot, :n_out[slot]].copy(),
                           status="error" if error[slot] else "ok"))
            self._occupants[slot] = None
        return completions

    def run(self, prompts=None) -> list[Completion]:
        """Serve until the queue and every slot drain; returns completions
        sorted by uid.  ``prompts`` (optional) are submitted first."""
        for p in (prompts or []):
            self.submit(p)
        done: list[Completion] = []
        while self.pending:
            done.extend(self.step_wave())
        return sorted(done, key=lambda comp: comp.uid)

    # -- introspection -----------------------------------------------------
    def next_logits(self) -> np.ndarray:
        """[slots, vocab] f32 logits of the next decode step of every slot,
        without advancing the state — for checking one servable or
        placement against another on identical (e.g. mid-prefill) state."""
        return np.asarray(self._logits(self._params, self._state)
                          .astype(jnp.float32))

    def lower_wave(self):
        """AOT-lower the wave program against the current state — the
        analysis hook :mod:`repro.analysis.hlo_lint` uses to inspect the
        optimized HLO (f64 leaks, host callbacks, collectives)."""
        return self._wave.lower(self._params, self._state)

    def program_counts(self) -> dict:
        """Lowered-program counts of the session's two jitted entry points
        (the compile-budget serving scenarios lock admit=1, wave=1 across
        arbitrarily many admissions)."""
        return {"admit": int(self._admit._cache_size()),
                "wave": int(self._wave._cache_size())}
