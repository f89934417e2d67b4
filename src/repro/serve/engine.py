"""Batched serving engine with slot-based continuous batching.

The engine owns a fixed-shape (max_batch, max_seq) KV/state cache. Requests
occupy slots; new requests are prefetched with a single-row prefill whose
cache rows are spliced into the live batch cache, so decoding never stalls
the whole batch for one admission (continuous batching). Finished slots free
immediately. Greedy or temperature sampling.

This is the ``jax_serve`` runtime the TACC execution layer provisions for
inference tasks.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models.transformer import (RunFlags, decode_step, init_cache,
                                      prefill)


@dataclass
class GenerationResult:
    request_id: int
    prompt: List[int]
    tokens: List[int] = field(default_factory=list)
    done: bool = False
    # host perf_counter stamps, the last two taken once the device produced
    # the token: arrival (admission, or the start of ``run`` for the
    # requests it queues), first token, last token
    arrived_s: float = 0.0
    first_token_s: float = 0.0
    finished_s: float = 0.0


@dataclass
class _Slot:
    request: Optional[GenerationResult] = None
    remaining: int = 0
    last_token: int = 0


class ServeEngine:
    def __init__(self, cfg: ModelConfig, params, *, max_batch: int = 8,
                 max_seq: int = 256, flags: RunFlags = RunFlags(),
                 eos_id: Optional[int] = None, seed: int = 0):
        if cfg.input_mode != "tokens":
            raise ValueError("ServeEngine drives token models; modality-stub "
                             "archs are exercised via prefill/decode directly")
        self.cfg = cfg
        self.params = params
        self.max_batch = max_batch
        self.max_seq = max_seq
        self.flags = flags
        self.eos_id = eos_id
        self._next_id = 0
        self._slots = [_Slot() for _ in range(max_batch)]
        self.cache = init_cache(cfg, max_batch, max_seq)
        self._rng = np.random.RandomState(seed)
        self._prefill1 = jax.jit(
            lambda p, b, n: prefill(cfg, p, b, n, flags=flags))
        self._decode = jax.jit(
            lambda p, c, t: decode_step(cfg, p, c, t, flags=flags))
        self._steps = 0

    # -- admission ---------------------------------------------------------

    def _free_slot(self) -> Optional[int]:
        for i, s in enumerate(self._slots):
            if s.request is None:
                return i
        return None

    def add_request(self, prompt: List[int], max_new: int = 32
                    ) -> Optional[GenerationResult]:
        """Prefill one row and splice it into the live cache. Returns None if
        no slot is free (caller queues)."""
        slot = self._free_slot()
        if slot is None:
            return None
        arrived_s = time.perf_counter()
        prompt = list(prompt)[: self.max_seq - max_new - 1]
        toks = np.zeros((1, self.max_seq), np.int32)
        toks[0, :len(prompt)] = prompt
        lengths = jnp.asarray([len(prompt)], jnp.int32)
        logits, row_cache = self._prefill1(
            self.params, {"tokens": jnp.asarray(toks)}, lengths)
        self._splice(slot, row_cache)
        req = GenerationResult(self._next_id, prompt, arrived_s=arrived_s)
        self._next_id += 1
        first = self._pick(np.asarray(logits)[0])
        req.tokens.append(int(first))
        req.first_token_s = time.perf_counter()
        self._slots[slot] = _Slot(req, max_new - 1, int(first))
        return req

    def _splice(self, slot: int, row_cache) -> None:
        def put(dst, src):          # prelayer caches: batch is axis 0
            return jax.lax.dynamic_update_slice_in_dim(
                dst, src.astype(dst.dtype), slot, axis=0)

        def put1(dst, src):         # stacked period caches: batch is axis 1
            return jax.lax.dynamic_update_slice_in_dim(
                dst, src.astype(dst.dtype), slot, axis=1)

        new = {}
        new["prelayers"] = jax.tree.map(put, self.cache["prelayers"],
                                        row_cache["prelayers"])
        new["period"] = jax.tree.map(put1, self.cache["period"],
                                     row_cache["period"])
        # cache holds exactly len(prompt) entries; the first generated token
        # is written at position lengths on its first decode step
        new["lengths"] = self.cache["lengths"].at[slot].set(
            row_cache["lengths"][0])
        self.cache = new

    def _pick(self, logits: np.ndarray, temperature: float = 0.0) -> int:
        if temperature <= 0:
            return int(logits.argmax())
        z = logits / temperature
        z = z - z.max()
        p = np.exp(z) / np.exp(z).sum()
        return int(self._rng.choice(len(p), p=p))

    # -- decode loop -------------------------------------------------------

    def active(self) -> int:
        return sum(s.request is not None for s in self._slots)

    def step(self) -> List[GenerationResult]:
        """One decode step for every occupied slot. Returns newly finished."""
        occupied = np.asarray([s.request is not None for s in self._slots])
        if not occupied.any():
            return []
        tokens = jnp.asarray([s.last_token for s in self._slots], jnp.int32)
        prev_lengths = self.cache["lengths"]
        logits, self.cache = self._decode(self.params, self.cache, tokens)
        # the dense decode advances every row's length; freed slots must not
        # keep walking (they would eventually run past max_seq and corrupt
        # the position a future splice resumes from), so pin them in place
        self.cache["lengths"] = jnp.where(jnp.asarray(occupied),
                                          self.cache["lengths"], prev_lengths)
        logits = np.asarray(logits)
        now = time.perf_counter()
        finished = []
        self._steps += 1
        for i, s in enumerate(self._slots):
            if s.request is None:
                continue
            nxt = self._pick(logits[i])
            s.request.tokens.append(nxt)
            s.last_token = nxt
            s.remaining -= 1
            hit_eos = self.eos_id is not None and nxt == self.eos_id
            if s.remaining <= 0 or hit_eos:
                s.request.done = True
                s.request.finished_s = now
                finished.append(s.request)
                self._slots[i] = _Slot()
                self.cache["lengths"] = self.cache["lengths"].at[i].set(0)
        return finished

    def run(self, requests: List[List[int]], max_new: int = 16
            ) -> List[GenerationResult]:
        """Serve a workload of prompts to completion (continuous batching)."""
        queue = list(requests)
        results: List[GenerationResult] = []
        t0 = time.perf_counter()
        while queue or self.active():
            while queue:
                r = self.add_request(queue[0], max_new=max_new)
                if r is None:
                    break
                r.arrived_s = t0             # waited in this queue since t0
                results.append(r)
                queue.pop(0)
            if self.active():
                self.step()
        return results
