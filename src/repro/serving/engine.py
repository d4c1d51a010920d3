"""Serving engine: batched prefill + continuous-batching decode.

Slot model (vLLM-style, static shapes for XLA):
  * the engine owns `batch_size` slots and one cache pytree; slot admission,
    budgets and refill-on-completion live in `core.scheduler.SlotScheduler`
    — the SAME policy object the analytical simulator (core/simulator.py)
    replays, so simulated schedules are about this exact code;
  * prefill runs per admission wave (right-padded prompts, per-sequence
    prompt_lens); finished slots are refilled by single-prompt prefill into
    a fresh batch-1 cache that is scattered into the slot (jitted);
  * decode advances all live slots every step (dead slots masked), sampling
    every slot with its own request's SamplingParams.

Recurrent/hybrid archs (state pollution from right pads) are admitted in
equal-length buckets — the scheduler handles that transparently.

Each call's pieces run inside `jax.profiler.TraceAnnotation` spans named
`engine.*` (plan, wave, refill, decode; inside them cache, feed, prefill,
step, insert, sample, wait, commit). They land in the profiler's own trace,
on the device planes' clock, and record nothing while no trace runs.
`engine.wait` holds only the host blocking on the device.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation as span

from ..configs.base import ModelConfig
from ..core.scheduler import SlotScheduler
from .. import models
from .sampler import SamplingParams, sample_per_request


@dataclass
class Request:
    uid: int
    prompt: List[int]
    max_new_tokens: int = 32
    eos_id: int = -1
    sampling: SamplingParams = field(default_factory=SamplingParams)
    output: List[int] = field(default_factory=list)
    done: bool = False


def jit_steps(cfg: ModelConfig):
    """The engine's two device programs, jitted: prefill(params, tokens,
    cache, prompt_lens, frontend) and decode(params, token, cache). Named
    functions, so the device trace names them `jit_prefill_step` and
    `jit_decode_step`. Decode donates its cache: the step writes the new
    K/V into it in place, and the caller's cache is gone after the call."""
    def prefill_step(params, tokens, cache, prompt_lens, frontend):
        return models.prefill(cfg, params, tokens, cache, frontend=frontend,
                              prompt_lens=prompt_lens)

    def decode_step(params, token, cache):
        return models.decode_step(cfg, params, token, cache)

    return jax.jit(prefill_step), jax.jit(decode_step, donate_argnums=(2,))


class Engine:
    def __init__(self, cfg: ModelConfig, params, batch_size: int,
                 max_len: int, seed: int = 0, policy: str = "continuous"):
        self.cfg = cfg
        self.params = params
        self.B = batch_size
        self.max_len = max_len
        self.key = jax.random.PRNGKey(seed)
        self.cache = models.init_cache(cfg, batch_size, max_len)
        self.sched = SlotScheduler(batch_size, policy=policy)
        self._prefill, self._decode = jit_steps(cfg)
        self._insert = jax.jit(self._insert_impl, static_argnames=("slot",))
        # waves: whole-batch prefills; refills: per-slot prefill + insert
        self.stats = {"tokens_out": 0, "steps": 0, "waves": 0,
                      "refills": 0}

    @property
    def slot_req(self) -> List[Optional[Request]]:
        return self.sched.slot_req

    @property
    def slot_budget(self) -> List[int]:
        return self.sched.slot_budget

    # ------------------------------------------------------------------
    def _insert_impl(self, cache, one_cache, slot: int):
        """Scatter a batch-1 cache into `slot` of the engine cache."""
        def put(big, small):
            if big.ndim == 0:
                return big
            # find the batch axis: the dim where shapes differ (B vs 1)
            for ax in range(big.ndim):
                if big.shape[ax] != small.shape[ax] and small.shape[ax] == 1:
                    idx = [slice(None)] * big.ndim
                    idx[ax] = slice(slot, slot + 1)
                    return big.at[tuple(idx)].set(small)
            return big
        return jax.tree.map(put, cache, one_cache)

    # ------------------------------------------------------------------
    def admit_wave(self, requests: List[Request]):
        """Prefill a wave of requests into free slots (right-padded)."""
        with span("engine.plan"):
            pairs = self.sched.plan_wave(requests)
        if not pairs:
            return []
        wave = [r for _, r in pairs]
        if self.sched.idle:
            # whole-batch prefill path
            with span("engine.wave", n=len(wave)):
                with span("engine.feed"):
                    S = max(max(len(r.prompt) for r in wave), 1)
                    toks = np.zeros((self.B, S), np.int32)
                    lens = np.zeros((self.B,), np.int32)
                    for i, r in enumerate(wave):
                        toks[i, :len(r.prompt)] = r.prompt
                        lens[i] = len(r.prompt)
                    lens = np.maximum(lens, 1)
                    toks, lens = jnp.asarray(toks), jnp.asarray(lens)
                with span("engine.prefill"):
                    logits, self.cache = self._prefill(
                        self.params, toks, self.cache, lens, None)
                with span("engine.sample"):
                    self.key, sub = jax.random.split(self.key)
                    first = sample_per_request(
                        logits[:len(wave)], sub, [r.sampling for r in wave])
                with span("engine.wait"):
                    first = np.asarray(first, np.int32)
                for i, r in enumerate(wave):
                    self._admit_slot(i, r, int(first[i]))
                self.stats["waves"] += 1
        else:
            # per-slot insertion
            for slot, r in pairs:
                with span("engine.refill", uid=r.uid, slot=slot,
                          prompt_len=len(r.prompt)):
                    with span("engine.cache"):
                        one = models.init_cache(self.cfg, 1, self.max_len)
                    with span("engine.feed"):
                        toks = jnp.asarray([r.prompt], jnp.int32)
                        lens = jnp.asarray([len(r.prompt)], jnp.int32)
                    with span("engine.prefill"):
                        logits, one = self._prefill(self.params, toks, one,
                                                    lens, None)
                    with span("engine.insert"):
                        self.cache = self._insert(self.cache, one, slot=slot)
                    with span("engine.sample"):
                        self.key, sub = jax.random.split(self.key)
                        first = sample_per_request(logits[:1], sub,
                                                   [r.sampling])[0]
                    with span("engine.wait"):
                        first = int(np.asarray(first))
                    self._admit_slot(slot, r, first)
                    self.stats["refills"] += 1
        return wave

    # ------------------------------------------------------------------
    def _admit_slot(self, slot: int, r: Request, first_token: int):
        """The prefill's first sampled token counts against the budget."""
        r.output.append(first_token)
        self.stats["tokens_out"] += 1
        if (r.max_new_tokens <= 1
                or (r.eos_id >= 0 and first_token == r.eos_id)):
            r.done = True
            return
        self.sched.admit(slot, r, r.max_new_tokens - 1)

    # ------------------------------------------------------------------
    def decode_round(self):
        """One decode step for all live slots (dead slots stay masked;
        each live slot samples with its own request's SamplingParams)."""
        live = self.sched.live_slots()
        if not live:
            return
        with span("engine.decode", live=len(live)):
            with span("engine.feed"):
                tok = np.zeros((self.B,), np.int32)
                for i in live:
                    tok[i] = self.sched.slot_req[i].output[-1]
                tok = jnp.asarray(tok)
            with span("engine.step"):
                logits, self.cache = self._decode(self.params, tok,
                                                  self.cache)
            with span("engine.sample"):
                self.key, sub = jax.random.split(self.key)
                nxt = sample_per_request(
                    logits[jnp.asarray(live)], sub,
                    [self.sched.slot_req[i].sampling for i in live])
            with span("engine.wait"):
                nxt = np.asarray(nxt, np.int32)
            with span("engine.commit"):
                self.stats["steps"] += 1
                for j, i in enumerate(live):
                    r = self.sched.slot_req[i]
                    r.output.append(int(nxt[j]))
                    self.stats["tokens_out"] += 1
                    hit_eos = r.eos_id >= 0 and r.output[-1] == r.eos_id
                    if self.sched.step(i, hit_eos=hit_eos):
                        r.done = True

    # ------------------------------------------------------------------
    def run(self, requests: List[Request]) -> List[Request]:
        """Offline serve: continuous batching until all requests finish."""
        pending = list(requests)
        submitted: List[Request] = []
        while pending or not self.sched.idle:
            if pending:
                wave = self.admit_wave(pending)
                submitted += wave
                pending = pending[len(wave):]
            self.decode_round()
        return submitted
