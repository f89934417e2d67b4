"""Batched serving engine with slot-based continuous batching.

The engine owns a fixed-shape (max_batch, max_seq) KV/state cache. Requests
occupy slots; new requests are prefetched with a single-row prefill whose
cache rows are spliced into the live batch cache, so decoding never stalls
the whole batch for one admission (continuous batching). Finished slots free
immediately. Greedy or temperature sampling.

The engine's work is named for a trace. Its two compiled programs are
``jit_serve_prefill`` and ``jit_serve_decode``. With ``repro.obs`` on,
each admission is a ``serve.admit`` span (request id) around
``serve.prefill``, ``serve.splice`` and ``serve.first_token``, and each
decode step a ``serve.step`` span (step number) around ``serve.decode``,
``serve.pin``, ``serve.fetch``, ``serve.sample`` and ``serve.retire``.
``counters`` counts the work as plain ints, whether tracing is on or not;
for an MoE model both programs also return their expert layers' counts
(``transformer.moe_counts``), which the counters add up.

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

from repro import obs
from repro.configs.base import ModelConfig
from repro.models.transformer import (RunFlags, carried_layers, decode_step,
                                      init_cache, prefill)


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


def split_cache(cfg: ModelConfig, cache, flags: RunFlags = RunFlags()):
    """``(owned, kept)``: the period caches ``decode_step`` carries through
    its layer loop and writes in place (``carried_layers``), and the rest
    of ``cache`` with those entries None."""
    carried = carried_layers(cfg, flags)
    owned = tuple(c if k else None for c, k in zip(cache["period"], carried))
    kept = dict(cache, period=tuple(None if k else c for c, k in
                                    zip(cache["period"], carried)))
    return owned, kept


def decode_program(cfg: ModelConfig, flags: RunFlags = RunFlags(),
                   with_counts: bool = False):
    """The engine's decode step, jitted as ``jit_serve_decode``:
    ``(params, owned, kept, tokens) -> (logits, cache)``, the cache split
    by ``split_cache``; ``with_counts`` adds the MoE counts after the
    cache. ``owned`` is donated: the program writes each row's
    new token into those buffers in place and hands them back in
    ``cache``, so the arrays passed are invalid after the call. ``kept`` is
    not: the caller may keep reading its ``lengths``, and the caches the
    program reads and replaces whole (recurrent state, the prelayers')
    would only be copied whole if donated."""
    def serve_decode(p, owned, kept, t):
        period = tuple(k if o is None else o
                       for o, k in zip(owned, kept["period"]))
        return decode_step(cfg, p, dict(kept, period=period), t, flags=flags,
                           with_counts=with_counts)

    return jax.jit(serve_decode, donate_argnums=1)


class ServeEngine:
    """Continuous batching over a fixed (max_batch, max_seq) cache.

    The decode program owns the caches it writes in place
    (``decode_program``): each ``step`` donates ``self.cache``'s period
    attention keys and values and MLA latents and replaces them with the
    program's outputs, so an array taken from ``self.cache`` before a
    ``step`` is invalid after it. Read the cache through ``self.cache``
    only. ``lengths`` stays out of the donation, since ``step`` reads the
    lengths from before the call to pin freed slots; so do recurrent state
    and the unscanned prelayers' caches, which the program replaces whole.
    """

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
        moe = cfg.moe is not None

        def serve_prefill(p, b, n):
            return prefill(cfg, p, b, n, flags=flags, with_counts=moe)

        self._prefill1 = jax.jit(serve_prefill)
        self._decode = decode_program(cfg, flags, with_counts=moe)
        # decode_rows: occupied rows summed over steps; decode_kv_tokens:
        # the cache entries those rows' steps read (prompt and tokens so
        # far, the one each step writes included); prefill_padded_tokens:
        # max_seq per admission, the prefill's one shape
        names = ["decode_steps", "decode_rows", "decode_kv_tokens",
                 "admitted", "prefill_tokens", "prefill_padded_tokens"]
        if moe:
            # summed over MoE layers: decode assignments that landed on
            # held experts and held experts that got a token (free slots
            # route nowhere), and the prompt tokens' assignments here
            names += ["moe_assignments_here", "moe_experts_touched",
                      "moe_prefill_assignments_here"]
        self.counters = dict.fromkeys(names, 0)

    def _count_moe(self, counts, *names) -> None:
        """Add the programs' per-layer MoE counts to ``counters``."""
        got = jax.device_get(counts)
        for name, key in zip(names, ("assignments_here", "experts_touched")):
            if key in got:
                self.counters[name] += int(np.sum(got[key]))

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
        with obs.span("serve.admit", request=self._next_id):
            arrived_s = time.perf_counter()
            with obs.span("serve.prefill"):
                prompt = list(prompt)[: self.max_seq - max_new - 1]
                toks = np.zeros((1, self.max_seq), np.int32)
                toks[0, :len(prompt)] = prompt
                lengths = jnp.asarray([len(prompt)], jnp.int32)
                logits, row_cache, *counts = self._prefill1(
                    self.params, {"tokens": jnp.asarray(toks)}, lengths)
            with obs.span("serve.splice"):
                self._splice(slot, row_cache)
            req = GenerationResult(self._next_id, prompt, arrived_s=arrived_s)
            self._next_id += 1
            with obs.span("serve.first_token"):
                first = self._pick(np.asarray(logits)[0])
            req.tokens.append(int(first))
            req.first_token_s = time.perf_counter()
            self._slots[slot] = _Slot(req, max_new - 1, int(first))
            c = self.counters
            c["admitted"] += 1
            c["prefill_tokens"] += len(prompt)
            c["prefill_padded_tokens"] += self.max_seq
            if counts:
                self._count_moe(counts[0], "moe_prefill_assignments_here")
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
        c = self.counters
        with obs.span("serve.step", step=c["decode_steps"]):
            with obs.span("serve.decode"):
                tokens = jnp.asarray([s.last_token for s in self._slots],
                                     jnp.int32)
                prev_lengths = self.cache["lengths"]
                owned, kept = split_cache(self.cfg, self.cache, self.flags)
                logits, self.cache, *counts = self._decode(
                    self.params, owned, kept, tokens)
            # the dense decode advances every row's length; freed slots
            # must not keep walking (they would eventually run past max_seq
            # and corrupt the position a future splice resumes from), so
            # pin them in place
            with obs.span("serve.pin"):
                self.cache["lengths"] = jnp.where(
                    jnp.asarray(occupied), self.cache["lengths"],
                    prev_lengths)
            with obs.span("serve.fetch"):
                logits = np.asarray(logits)
                if counts:
                    self._count_moe(counts[0], "moe_assignments_here",
                                    "moe_experts_touched")
            now = time.perf_counter()
            finished, freed = [], []
            c["decode_steps"] += 1
            with obs.span("serve.sample"):
                for i, s in enumerate(self._slots):
                    if s.request is None:
                        continue
                    c["decode_rows"] += 1
                    c["decode_kv_tokens"] += len(s.request.prompt) + len(
                        s.request.tokens)
                    nxt = self._pick(logits[i])
                    s.request.tokens.append(nxt)
                    s.last_token = nxt
                    s.remaining -= 1
                    hit_eos = self.eos_id is not None and nxt == self.eos_id
                    if s.remaining <= 0 or hit_eos:
                        s.request.done = True
                        s.request.finished_s = now
                        finished.append(s.request)
                        freed.append(i)
                        self._slots[i] = _Slot()
            with obs.span("serve.retire"):
                for i in freed:
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
