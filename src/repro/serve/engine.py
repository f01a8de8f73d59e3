"""Device-resident continuous batching: the serving analogue of
``runner.run(resident=True)``.

The host :class:`~repro.serve.scheduler.ContinuousBatcher` round-trips every
token through Python — per step it syncs ``int(next_token[slot])`` for each
slot and pulls the full ``(slots, vocab)`` logits to host to pick the next
token.  This engine applies the residency discipline the training side uses
(PRs 4–7) to decode:

* **Slot state lives on device** as one donated pytree
  (:class:`SlotState`: active mask, next-token vector, remaining-token
  budgets) next to the shared KV/recurrent cache with its per-slot
  position vector.
* **Decode runs as compiled multi-token chunks**: one ``lax.scan`` over
  ``chunk`` decode steps per dispatch.  Each step emits the pending token
  for every *active* slot, decrements its budget, retires slots that hit
  EOS or their budget by clearing the mask (no host sync — retired slots
  keep decoding garbage that the emission mask hides, exactly like the
  host batcher's idle slots), and samples the next token on device.
* **Admission splices prefilled rows with a traced slot index**: prompts
  prefill as batch-1 rows against the engine's fixed ``max_len`` (uniform
  row-cache shapes), and one jitted ``_admit`` executable — slot index and
  budget are traced scalars — splices the row into the shared cache and
  seeds the slot state.  One executable total, not one per slot.
* **Generated tokens accumulate on device** in the chunk's preallocated
  ``(chunk, slots)`` emission buffer (the scan ys) and are pulled ONCE per
  chunk together with the emission mask and the post-chunk active mask —
  O(1) host<->device transfers per chunk instead of O(tokens x slots).
  ``engine.transfers`` reports the ledger ({h2d, d2h, chunks}):
  h2d = one prompt upload per admission, d2h = one pull per chunk.

Semantics are EXACTLY the host batcher's (greedy by default): per-request
outputs are bit-identical to ``ContinuousBatcher.run_until_done`` and to
standalone prefill+decode, because each cache row's computation is
independent of its batch neighbours.  A custom ``sampler`` must be
traceable ``(logits (B, V)) -> (B,) int32`` (it runs inside the compiled
chunk; the host batcher's may be arbitrary Python).
"""

from __future__ import annotations

import functools
from collections import deque
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import transformer
from repro.models.api import ModelConfig

from .scheduler import Request, cache_insert

__all__ = ["ResidentEngine", "SlotState"]


class SlotState(NamedTuple):
    """Per-slot decode state, resident on device (leading axis = slots)."""
    active: jax.Array      # (S,) bool — slot is mid-generation
    next_tok: jax.Array    # (S,) int32 — pending emission / next decode input
    remaining: jax.Array   # (S,) int32 — tokens still to emit (incl. pending)


def _greedy(logits):
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


@functools.lru_cache(maxsize=None)
def _build_executables(cfg: ModelConfig, max_len: int, eos: int | None,
                       pick: Callable, n_chunk: int):
    """Per-(config, shape) compiled prefill/admit/chunk executables.

    Cached at module level so a freshly constructed engine (the bench and
    sweep shape) reuses the compiled programs instead of re-tracing —
    the serving analogue of ``runner``'s persistent executable cache.
    ``pick`` must be hashable (module functions are; ad-hoc lambdas get
    their own cache entries)."""
    @jax.jit
    def prefill(params, tokens, **kw):
        return transformer.prefill(cfg, params, tokens, max_len=max_len,
                                   **kw)

    decode = functools.partial(transformer.decode_step, cfg)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def admit(state: SlotState, cache, row_cache, logits, budget, slot):
        # slot and budget are TRACED scalars: one compiled executable
        # serves every slot and every max_new_tokens
        cache = cache_insert(cache, row_cache, slot)
        tok = pick(logits)[0].astype(jnp.int32)
        return SlotState(
            active=state.active.at[slot].set(True),
            next_tok=state.next_tok.at[slot].set(tok),
            remaining=state.remaining.at[slot].set(budget)), cache

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def run_chunk(state: SlotState, cache, params):
        def body(carry, _):
            st, c = carry
            emit = st.next_tok
            emitted = st.active
            rem = st.remaining - emitted.astype(jnp.int32)
            done = emitted & (rem <= 0)
            if eos is not None:
                done = done | (emitted & (emit == eos))
            # decode ALL slots (retired/idle rows produce garbage the
            # emission mask hides) — same batched step as the host loop
            logits, c = decode(params, c, emit)
            picked = pick(logits)
            st = SlotState(
                active=st.active & ~done,
                next_tok=jnp.where(st.active & ~done, picked,
                                   st.next_tok),
                remaining=rem)
            return (st, c), (emit, emitted)

        (state, cache), (toks, mask) = jax.lax.scan(
            body, (state, cache), None, length=n_chunk)
        return state, cache, (toks, mask, state.active)

    return prefill, admit, run_chunk


class ResidentEngine:
    """Drop-in continuous batcher with a device-resident hot path.

    Same client API as :class:`~repro.serve.scheduler.ContinuousBatcher`
    (``submit`` / ``busy`` / ``step`` / ``run_until_done`` / ``outputs``)
    with ``step()`` advancing one *chunk* of decode steps instead of one
    token.
    """

    def __init__(self, cfg: ModelConfig, params, max_slots: int,
                 max_len: int, eos_id: int | None = None,
                 sampler: Callable | None = None, chunk: int = 16):
        if chunk < 1:
            raise ValueError(f"chunk must be >= 1, got {chunk}")
        self.cfg = cfg
        self.params = params
        self.max_slots = max_slots
        self.max_len = max_len
        self.eos_id = eos_id
        self.chunk = chunk
        self._pick = sampler if sampler is not None else _greedy

        self.queue: deque[Request] = deque()
        self.slot_req: list[Request | None] = [None] * max_slots
        self.slot_generated: list[list[int]] = [[] for _ in range(max_slots)]
        self.outputs: dict[int, np.ndarray] = {}
        self.transfers = {"h2d": 0, "d2h": 0, "chunks": 0}

        self.cache = transformer.init_cache(cfg, max_slots, max_len)
        self.state = SlotState(
            active=jnp.zeros((max_slots,), bool),
            next_tok=jnp.zeros((max_slots,), jnp.int32),
            remaining=jnp.zeros((max_slots,), jnp.int32))

        # batch-1 prefill against the engine's fixed max_len: row caches get
        # uniform shapes, so the admission splice is ONE executable.
        # prefill itself compiles once per distinct prompt length (bucket
        # your workload's prompt lengths — serve/stream.py does).
        self._prefill, self._admit, self._chunk = _build_executables(
            cfg, max_len, eos_id, self._pick, chunk)

    # -- client API ---------------------------------------------------------

    def submit(self, req: Request):
        self.queue.append(req)

    @property
    def busy(self) -> bool:
        return bool(self.queue) or any(r is not None for r in self.slot_req)

    def run_until_done(self, max_steps: int = 10000) -> dict:
        steps = 0
        while self.busy and steps < max_steps:
            self.step()
            steps += 1
        return dict(self.outputs)

    # -- engine -------------------------------------------------------------

    def _admit_all(self):
        for slot in range(self.max_slots):
            if self.slot_req[slot] is not None or not self.queue:
                continue
            req = self.queue.popleft()
            if len(req.tokens) >= self.max_len:
                raise ValueError(
                    f"request {req.uid}: prompt length {len(req.tokens)} "
                    f"does not fit the engine's max_len={self.max_len} cache")
            kw = {}
            if req.image_embeds is not None:
                kw["image_embeds"] = jnp.asarray(req.image_embeds)[None]
            if req.audio_frames is not None:
                kw["audio_frames"] = jnp.asarray(req.audio_frames)[None]
            toks = jnp.asarray(np.asarray(req.tokens, np.int32))[None]
            self.transfers["h2d"] += 1          # the prompt upload
            logits, row_cache = self._prefill(self.params, toks, **kw)
            self.state, self.cache = self._admit(
                self.state, self.cache, row_cache, logits,
                req.max_new_tokens, slot)
            self.slot_req[slot] = req
            self.slot_generated[slot] = []

    def step(self) -> dict[int, int]:
        """Admit queued requests, run ONE compiled decode chunk, pull the
        emission buffer once.  Returns {uid: n_new_tokens} for this chunk."""
        self._admit_all()
        if not any(r is not None for r in self.slot_req):
            return {}
        self.state, self.cache, ys = self._chunk(self.state, self.cache,
                                                 self.params)
        toks, mask, active = jax.device_get(ys)   # ONE pull per chunk
        self.transfers["d2h"] += 1
        self.transfers["chunks"] += 1
        events: dict[int, int] = {}
        for slot, req in enumerate(self.slot_req):
            if req is None:
                continue
            new = toks[mask[:, slot], slot].tolist()
            if new:
                self.slot_generated[slot].extend(new)
                events[req.uid] = len(new)
            if not active[slot]:
                self.outputs[req.uid] = np.asarray(self.slot_generated[slot],
                                                   np.int32)
                self.slot_req[slot] = None
        return events
