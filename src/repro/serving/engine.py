"""Batched serving engine: admission queue + prefill + decode slots.

Continuous-batching-lite: a fixed number of decode slots; finished
sequences free their slot and the next queued request is prefilled into it.
The decode step itself is the jit'd model decode_step (KV caches live in
device memory, sharded per launch/specs.py on real meshes).
"""
from __future__ import annotations

import contextlib
from dataclasses import dataclass, field
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np


def drain_microbatched(queue: list, window: int, eval_batch: Callable,
                       max_items: int | None = None, lock=None) -> list:
    """Generic admission-queue drain for batched serving: pop up to
    `window` queued items at a time, evaluate each micro-batch with ONE
    `eval_batch(batch) -> results` call, and collect the results in
    submission order (at most `max_items` items total).

    `lock`, when given, guards only the queue mutation — never the
    evaluation — so `eval_batch` may itself serialize on the same lock
    (the `DesignTwin.run` shape) and concurrent producers may keep
    submitting while a batch is in flight."""
    guard = lock if lock is not None else contextlib.nullcontext()
    finished: list = []
    budget = float("inf") if max_items is None else max_items
    while budget > 0:
        with guard:
            batch = queue[: int(min(window, budget))]
            del queue[: len(batch)]
        if not batch:
            break
        finished.extend(eval_batch(batch))
        budget -= len(batch)
    return finished


@dataclass
class Request:
    rid: int
    prompt: np.ndarray                 # (S,) int32
    max_new_tokens: int = 16
    out_tokens: list = field(default_factory=list)
    done: bool = False


@dataclass
class ServeStats:
    prefills: int = 0
    decode_steps: int = 0
    tokens_out: int = 0
    # distinct experts the router selected, summed over routed layers and
    # model steps; read from the cache's counter once a batch, where the
    # model's cache carries one
    experts_routed: int = 0


class Server:
    """Single-host reference server (smoke scale); the same decode_step is
    what the dry-run lowers for the 256/512-chip meshes."""

    def __init__(self, cfg, model, params, *, batch_slots: int = 4,
                 max_len: int = 256, env=None, eos: int = 1):
        self.cfg, self.model, self.params = cfg, model, params
        self.max_len = max_len
        self.slots = batch_slots
        self.env = env
        self.eos = eos
        self.queue: list[Request] = []
        self.stats = ServeStats()

        # repro: ignore[R001]: one jit per Server instance (one Server
        # per process); cfg/env are deliberately baked into the closure
        self._decode = jax.jit(
            lambda p, t, c, l: model.decode_step(p, cfg, t, c, l, env=env))

    def submit(self, req: Request):
        self.queue.append(req)

    def _prefill_batch(self, reqs: list[Request]):
        B = len(reqs)
        S = max(len(r.prompt) for r in reqs)
        toks = np.zeros((B, S), np.int32)
        for i, r in enumerate(reqs):
            toks[i, S - len(r.prompt):] = r.prompt   # left-pad
        cache = self.model.init_cache(self.cfg, B, self.max_len,
                                      jnp.float32)
        # teacher-forced prompt pass token by token (families share this
        # path; transformer families could use model.prefill instead)
        cur = jnp.zeros((B,), jnp.int32)
        logits = None
        for t in range(S):
            logits, cache = self._decode(self.params,
                                         jnp.asarray(toks[:, t]), cache,
                                         jnp.asarray(t))
        self.stats.prefills += B
        return logits, cache, S

    def run(self, max_steps: int = 512) -> list[Request]:
        finished: list[Request] = []
        while self.queue and max_steps > 0:
            batch = self.queue[: self.slots]
            self.queue = self.queue[self.slots:]
            logits, cache, pos = self._prefill_batch(batch)
            next_tok = jnp.argmax(logits, axis=-1)
            for _ in range(max(r.max_new_tokens for r in batch)):
                max_steps -= 1
                for i, r in enumerate(batch):
                    if not r.done and len(r.out_tokens) < r.max_new_tokens:
                        tok = int(next_tok[i])
                        r.out_tokens.append(tok)
                        self.stats.tokens_out += 1
                        if tok == self.eos:
                            r.done = True
                if all(r.done or len(r.out_tokens) >= r.max_new_tokens
                       for r in batch) or pos + 1 >= self.max_len:
                    break
                logits, cache = self._decode(self.params, next_tok, cache,
                                             jnp.asarray(pos))
                self.stats.decode_steps += 1
                pos += 1
                next_tok = jnp.argmax(logits, axis=-1)
            if "experts_routed" in cache:
                self.stats.experts_routed += int(cache["experts_routed"])
            finished.extend(batch)
        return finished
