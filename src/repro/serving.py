"""Batched serving engine: slot-based continuous batching.

The engine owns ``num_slots`` cache slots.  Each engine tick:
  1. admit — free slots are filled from the request queue; the prompt is
     prefilled (padded to a fixed bucket so the compiled prefill is reused)
     and its cache scattered into the slot;
  2. decode — ONE fused decode step advances *all* live slots together,
     each at its own depth (vector ``cache_pos``);
  3. retire — slots that hit EOS/max_tokens emit a finished response.

Everything jitted is shape-stable: (num_slots, 1) decode, a fixed set of
prefill buckets — no recompiles in steady state.

Given the serve entry's sharding ``plan``, the cache is created straight
into the plan's cache shardings and every jitted body traces under the plan,
so a model spread over several chips stays spread.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .models.common import P
from .models.sharding import use_plan


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray                   # (prompt_len,) int32
    max_new_tokens: int = 32
    temperature: float = 0.0             # 0 = greedy
    submitted_at: float = 0.0


@dataclasses.dataclass
class Response:
    rid: int
    tokens: List[int]
    prompt_len: int
    queued_s: float
    prefill_s: float
    decode_s: float


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
        self.slot_t0 = np.zeros(num_slots, np.float64)
        self.slot_tprefill = np.zeros(num_slots, np.float64)
        self.finished: List[Response] = []
        self._next_tokens = np.zeros(num_slots, np.int32)
        self._key = jax.random.PRNGKey(rng_seed)
        self._ticks = 0

        # jitted single-slot prefill (per bucket), slot insert and fused
        # decode; the cache keeps the plan's shardings across all three
        self._prefill = jax.jit(self._prefill_impl,
                                static_argnames=("bucket",),
                                out_shardings=(None, cache_shardings(1)))
        self._insert = jax.jit(self._insert_impl, out_shardings=cache_sh,
                               donate_argnums=(0,))
        self._decode = jax.jit(self._decode_impl,
                               out_shardings=(None, cache_sh),
                               donate_argnums=(1,))

    # -- jitted bodies ------------------------------------------------------
    def _prefill_impl(self, params, tokens, length, bucket: int):
        """tokens: (1, bucket); length: scalar prompt length.
        Returns (next_token_logits (1, v), cache_b1)."""
        with use_plan(self.plan):
            return self._prefill_body(params, tokens, length, bucket)

    def _prefill_body(self, params, tokens, length, bucket: int):
        m = self.model
        cache = m.init_cache(1, self.max_seq)
        pos = jnp.arange(bucket, dtype=jnp.int32)[None]
        if self.cfg.mrope_sections:
            pos3 = jnp.broadcast_to(pos, (3, 1, bucket))
            batch = {"tokens": tokens, "positions": pos3}
        else:
            batch = {"tokens": tokens, "positions": pos}
        if self.cfg.family == "audio-lm":
            # serve path embeds codebook tokens via the embedding table
            from .models.common import sinusoidal_pos
            e = params["embed"]["tok"][tokens]
            e = e + sinusoidal_pos(pos, self.cfg.d_model).astype(e.dtype)
        else:
            e = m.embed(params, batch)
        logits, _, cache, _ = m.logits_fn(params, e, batch["positions"],
                                          cache, 0)
        last = jnp.take_along_axis(
            logits, (length - 1)[None, None, None].astype(jnp.int32)
            if jnp.ndim(length) == 0 else length[:, None, None], axis=1)
        return last[:, 0, :], cache

    @staticmethod
    def _insert_impl(full_cache, cache1, slot):
        """Scatter a prefilled batch-1 cache into ``slot`` (batch axis = 1,
        because stacked cache leaves are (layers, batch, ...))."""
        return jax.tree.map(
            lambda full, one: jax.lax.dynamic_update_slice_in_dim(
                full, one.astype(full.dtype), slot, axis=1),
            full_cache, cache1)

    def _decode_impl(self, params, cache, tokens, positions, live, key,
                     temps):
        """tokens: (slots,); positions: (slots,); live: (slots,) bool."""
        with use_plan(self.plan):
            return self._decode_body(params, cache, tokens, positions, key,
                                     temps)

    def _decode_body(self, params, cache, tokens, positions, key, temps):
        m = self.model
        toks = tokens[:, None]
        pos = positions[:, None]
        if self.cfg.mrope_sections:
            pos_in = jnp.broadcast_to(pos, (3,) + pos.shape)
        else:
            pos_in = pos
        logits, new_cache = m.decode_step(params, toks, pos_in, cache,
                                          positions)
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
        if self.cfg.family in ("ssm-lm", "hybrid-lm"):
            # recurrent state must not integrate padding junk: exact-length
            # prefill (one compile per distinct prompt length)
            return n
        for b in self.prefill_buckets:
            if n <= b:
                return b
        return self.prefill_buckets[-1]

    def _prefill_args(self, prompt: Sequence[int]) -> Tuple[tuple, dict]:
        """The (args, kwargs) the jitted prefill takes for ``prompt``: its
        bucket-padded tokens and its length (clipped to the bucket)."""
        n = len(prompt)
        bucket = self._bucket_for(n)
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :n] = np.asarray(prompt, np.int32)[:bucket]
        return ((self.params, jnp.asarray(toks),
                 jnp.asarray(min(n, bucket), jnp.int32)),
                {"bucket": bucket})

    def prefill(self, prompt: Sequence[int]):
        """Prefill ``prompt`` alone: (next-token logits (1, vocab), the
        batch-1 cache, the number of prompt tokens kept)."""
        args, kw = self._prefill_args(prompt)
        logits, cache1 = self._prefill(*args, **kw)
        return logits, cache1, int(args[2])

    def lower_prefill(self, prompt: Sequence[int]) -> jax.stages.Lowered:
        """The prefill program ``prompt`` runs, lowered (not run)."""
        args, kw = self._prefill_args(prompt)
        return self._prefill.lower(*args, **kw)

    def lower_decode(self) -> jax.stages.Lowered:
        """The fused decode step over all slots, lowered (not run)."""
        n = self.num_slots
        return self._decode.lower(
            self.params, self.cache, jnp.zeros(n, jnp.int32),
            jnp.zeros(n, jnp.int32), jnp.ones(n, bool), self._key,
            jnp.zeros(n, jnp.float32))

    def _admit(self) -> None:
        for slot in range(self.num_slots):
            if self.slot_req[slot] is not None or not self.queue:
                continue
            req = self.queue.popleft()
            t0 = time.perf_counter()
            logits, cache1, n_kept = self.prefill(req.prompt)
            self.cache = self._insert(self.cache, cache1,
                                      jnp.asarray(slot, jnp.int32))
            first = int(jax.device_get(jnp.argmax(logits[0])))
            self.slot_req[slot] = req
            self.slot_pos[slot] = n_kept
            self.slot_out[slot] = [first]
            self._next_tokens[slot] = first
            self.slot_t0[slot] = req.submitted_at
            self.slot_tprefill[slot] = time.perf_counter() - t0

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
                    queued_s=now - req.submitted_at,
                    prefill_s=float(self.slot_tprefill[slot]),
                    decode_s=now - self.slot_t0[slot]))
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
        positions = np.where(live, self.slot_pos, self.max_seq - 1) \
            .astype(np.int32)
        temps = np.array([
            (r.temperature if r is not None else 0.0)
            for r in self.slot_req], np.float32)
        self._key, sub = jax.random.split(self._key)
        next_tok, self.cache = self._decode(
            self.params, self.cache, jnp.asarray(self._next_tokens),
            jnp.asarray(positions), jnp.asarray(live), sub,
            jnp.asarray(temps))
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
