"""Batched serving engine: slot-based continuous batching.

The engine owns ``num_slots`` cache slots.  Each engine tick:
  1. admit — free slots are filled from the request queue; the prompt is
     prefilled (``Model.prefill``, padded to a fixed bucket so the compiled
     prefill is reused; a recurrent model prefills at the exact length) and
     its cache scattered into the slot;
  2. decode — ONE fused decode step advances *all* live slots together,
     each at its own depth (vector ``cache_pos``);
  3. retire — slots that hit EOS/max_tokens emit a finished response.

Admission, the decode dispatch and the token sync each run inside a program
span (``spans.NAMES``), so a profiler trace shows what the host did while
the device idled.  Every request is stamped when it is submitted, when its
admission starts and when its first token is on the host.

Everything jitted is shape-stable: (num_slots, 1) decode, a fixed set of
prefill buckets — no recompiles in steady state.

Given the serve entry's sharding ``plan``, the cache is created straight
into the plan's cache shardings and every jitted body traces under the plan,
so a model spread over several chips stays spread.
"""
from __future__ import annotations

import dataclasses
import math
import time
from collections import deque
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import spans
from .models.common import P
from .models.sharding import use_plan


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray                   # (prompt_len,) int32
    max_new_tokens: int = 32
    temperature: float = 0.0             # 0 = greedy
    submitted_at: float = 0.0            # time.perf_counter() stamps
    admitted_at: float = math.nan        # admission starts
    first_token_at: float = math.nan     # first token on the host


@dataclasses.dataclass
class Response:
    """A finished request.  ``queued_s`` (submit to admission), ``prefill_s``
    (admission to the first token on the host: prefill, slot insert, first
    token sync) and ``decode_s`` (first token to finish) add up to the
    request's time in the engine."""
    rid: int
    tokens: List[int]
    prompt_len: int
    queued_s: float
    prefill_s: float
    decode_s: float
    first_token_at: float


class ServingEngine:
    def __init__(self, model, params, *, num_slots: int = 8,
                 max_seq: int = 1024,
                 prefill_buckets: Sequence[int] = (64, 256),
                 eos_id: int = -1, rng_seed: int = 0, plan=None):
        self.model = model
        self.params = params
        self.num_slots = num_slots
        self.max_seq = max_seq
        self.prefill_buckets = sorted(prefill_buckets)
        self.eos_id = eos_id
        self.cfg = model.cfg
        self.plan = plan

        def cache_shardings(batch: int):
            if plan is None:
                return None
            return jax.tree.map(lambda p: plan.sharding(p.axes, p.shape),
                                model.cache_specs(batch, max_seq),
                                is_leaf=lambda x: isinstance(x, P))

        cache_sh = cache_shardings(num_slots)
        self.cache = jax.jit(
            lambda: model.init_cache(num_slots, max_seq),
            out_shardings=cache_sh)()
        self.queue: deque[Request] = deque()
        self.slot_req: List[Optional[Request]] = [None] * num_slots
        self.slot_pos = np.zeros(num_slots, np.int32)       # next write pos
        self.slot_out: List[List[int]] = [[] for _ in range(num_slots)]
        self.finished: List[Response] = []
        self._next_tokens = np.zeros(num_slots, np.int32)
        self._key = jax.random.PRNGKey(rng_seed)
        self._ticks = 0

        # jitted single-slot prefill (per bucket), slot insert and fused
        # decode; the cache keeps the plan's shardings across all three
        self._prefill = jax.jit(self._prefill_impl,
                                out_shardings=(None, cache_shardings(1)))
        self._insert = jax.jit(self._insert_impl, out_shardings=cache_sh,
                               donate_argnums=(0,))
        self._decode = jax.jit(self._decode_impl,
                               out_shardings=(None, cache_sh),
                               donate_argnums=(1,))

    # -- jitted bodies ------------------------------------------------------
    def _prefill_impl(self, params, tokens, length):
        """tokens: (1, bucket); length: scalar prompt length.
        Returns (next_token_logits (1, v), cache_b1)."""
        m = self.model
        with use_plan(self.plan):
            cache = m.init_cache(1, self.max_seq)
            pos = jnp.arange(tokens.shape[1], dtype=jnp.int32)[None]
            return m.prefill(params, {"tokens": tokens, "positions": pos},
                             cache, length)

    @staticmethod
    def _insert_impl(full_cache, cache1, slot):
        """Scatter a prefilled batch-1 cache into ``slot`` (batch axis = 1,
        because stacked cache leaves are (layers, batch, ...))."""
        return jax.tree.map(
            lambda full, one: jax.lax.dynamic_update_slice_in_dim(
                full, one.astype(full.dtype), slot, axis=1),
            full_cache, cache1)

    def _decode_impl(self, params, cache, tokens, positions, key, temps):
        """tokens: (slots,); positions: (slots,)."""
        with use_plan(self.plan):
            return self._decode_body(params, cache, tokens, positions, key,
                                     temps)

    def _decode_body(self, params, cache, tokens, positions, key, temps):
        logits, new_cache = self.model.decode_step(
            params, tokens[:, None], positions[:, None], cache, positions)
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        gumbel = -jnp.log(-jnp.log(
            jax.random.uniform(key, logits.shape, minval=1e-9, maxval=1.0)))
        sampled = jnp.argmax(
            logits / jnp.maximum(temps[:, None], 1e-6) + gumbel,
            axis=-1).astype(jnp.int32)
        next_tok = jnp.where(temps > 0, sampled, greedy)
        # dead slots must not corrupt their cache position: they decode into
        # position max_seq-1 and their token is ignored on the host.
        return next_tok, new_cache

    # -- public API -----------------------------------------------------------
    def submit(self, prompt: Sequence[int], max_new_tokens: int = 32,
               temperature: float = 0.0) -> int:
        rid = len(self.finished) + len(self.queue) + sum(
            r is not None for r in self.slot_req)
        self.queue.append(Request(
            rid=rid, prompt=np.asarray(prompt, np.int32),
            max_new_tokens=max_new_tokens, temperature=temperature,
            submitted_at=time.perf_counter()))
        return rid

    def _bucket_for(self, n: int) -> int:
        if self.model.recurrent:    # one compile per distinct length
            return n
        for b in self.prefill_buckets:
            if n <= b:
                return b
        return self.prefill_buckets[-1]

    def _prefill_args(self, prompt: Sequence[int]) -> tuple:
        """The arguments the jitted prefill takes for ``prompt``: its
        bucket-padded tokens and its length (clipped to the bucket)."""
        n = len(prompt)
        bucket = self._bucket_for(n)
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :n] = np.asarray(prompt, np.int32)[:bucket]
        return (self.params, jnp.asarray(toks),
                jnp.asarray(min(n, bucket), jnp.int32))

    def prefill(self, prompt: Sequence[int]):
        """Prefill ``prompt`` alone: (next-token logits (1, vocab), the
        batch-1 cache, the number of prompt tokens kept)."""
        args = self._prefill_args(prompt)
        logits, cache1 = self._prefill(*args)
        return logits, cache1, int(args[2])

    def lower_prefill(self, prompt: Sequence[int]) -> jax.stages.Lowered:
        """The prefill program ``prompt`` runs, lowered (not run)."""
        return self._prefill.lower(*self._prefill_args(prompt))

    def lower_decode(self) -> jax.stages.Lowered:
        """The fused decode step over all slots, lowered (not run)."""
        n = self.num_slots
        return self._decode.lower(
            self.params, self.cache, jnp.zeros(n, jnp.int32),
            jnp.zeros(n, jnp.int32), self._key, jnp.zeros(n, jnp.float32))

    def _admit(self) -> None:
        for slot in range(self.num_slots):
            if self.slot_req[slot] is not None or not self.queue:
                continue
            with spans.span(spans.ADMIT):
                req = self.queue.popleft()
                req.admitted_at = time.perf_counter()
                logits, cache1, n_kept = self.prefill(req.prompt)
                self.cache = self._insert(self.cache, cache1,
                                          jnp.asarray(slot, jnp.int32))
                first = int(jax.device_get(jnp.argmax(logits[0])))
                req.first_token_at = time.perf_counter()
                self.slot_req[slot] = req
                self.slot_pos[slot] = n_kept
                self.slot_out[slot] = [first]
                self._next_tokens[slot] = first

    def _retire(self) -> None:
        now = time.perf_counter()
        for slot in range(self.num_slots):
            req = self.slot_req[slot]
            if req is None:
                continue
            out = self.slot_out[slot]
            done = len(out) >= req.max_new_tokens or (
                self.eos_id >= 0 and out and out[-1] == self.eos_id)
            if done or int(self.slot_pos[slot]) >= self.max_seq - 1:
                self.finished.append(Response(
                    rid=req.rid, tokens=list(out),
                    prompt_len=len(req.prompt),
                    queued_s=req.admitted_at - req.submitted_at,
                    prefill_s=req.first_token_at - req.admitted_at,
                    decode_s=now - req.first_token_at,
                    first_token_at=req.first_token_at))
                self.slot_req[slot] = None
                self.slot_out[slot] = []

    def tick(self) -> int:
        """One engine iteration; returns number of live slots decoded."""
        self._admit()
        self._retire()          # a 1-token request is done after prefill
        self._admit()
        live = np.array([r is not None for r in self.slot_req])
        if not live.any():
            return 0
        with spans.span(spans.DECODE):
            positions = np.where(live, self.slot_pos, self.max_seq - 1) \
                .astype(np.int32)
            temps = np.array([
                (r.temperature if r is not None else 0.0)
                for r in self.slot_req], np.float32)
            self._key, sub = jax.random.split(self._key)
            next_tok, self.cache = self._decode(
                self.params, self.cache, jnp.asarray(self._next_tokens),
                jnp.asarray(positions), sub, jnp.asarray(temps))
        with spans.span(spans.TOKEN_SYNC):
            next_tok = np.asarray(jax.device_get(next_tok))
            for slot in range(self.num_slots):
                if live[slot]:
                    self.slot_out[slot].append(int(next_tok[slot]))
                    self.slot_pos[slot] += 1
                    self._next_tokens[slot] = next_tok[slot]
        self._ticks += 1
        self._retire()
        return int(live.sum())

    def run_until_drained(self, max_ticks: int = 10_000) -> List[Response]:
        while (self.queue or any(r is not None for r in self.slot_req)) \
                and max_ticks > 0:
            self.tick()
            max_ticks -= 1
        return self.finished
