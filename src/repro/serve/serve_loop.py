"""Batched serving loop (continuous batching, slot-based).

A fixed number of decode slots share one jit'd decode step (the same
`serve_step` the dry-run lowers).  Requests are admitted into free
slots via a (vectorized) prefill; finished sequences (EOS or max len)
free their slot immediately — the decode step never waits for the
slowest request in the batch (slot-level continuous batching, the
vLLM-style scheduling idea mapped onto fixed-shape jit).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional

import numpy as np
import jax
import jax.numpy as jnp


@dataclasses.dataclass
class ServeConfig:
    max_slots: int = 4
    max_seq: int = 256
    eos_id: int = 0


@dataclasses.dataclass
class Request:
    rid: int
    prompt: List[int]
    max_new: int = 32
    out: List[int] = dataclasses.field(default_factory=list)
    done: bool = False


class BatchedServer:
    def __init__(self, model, params, cfg: ServeConfig):
        self.model = model
        self.params = params
        self.cfg = cfg
        self.cache = model.init_cache(cfg.max_slots, cfg.max_seq)
        self.pos = np.zeros(cfg.max_slots, np.int32)
        self.active: List[Optional[Request]] = [None] * cfg.max_slots
        self._decode = jax.jit(model.decode_step)
        self._queue: List[Request] = []

    def submit(self, req: Request):
        self._queue.append(req)

    def _admit(self):
        for slot in range(self.cfg.max_slots):
            if self.active[slot] is None and self._queue:
                req = self._queue.pop(0)
                self.active[slot] = req
                # prefill: feed prompt tokens one by one through the
                # decode step (correct for every cache/state family;
                # a batched prefill kernel is a serving optimization
                # exercised by the prefill_32k dry-run cells)
                for t, tok in enumerate(req.prompt):
                    toks = np.zeros((self.cfg.max_slots, 1), np.int32)
                    toks[slot, 0] = tok
                    pos = jnp.asarray(self.pos)
                    logits, self.cache = self._decode(
                        self.params, self.cache, jnp.asarray(toks), pos)
                    # wait for the step before the next one: with the
                    # CPU runtime's async dispatch (jax 0.9) unawaited
                    # prefill steps gave run-to-run different tokens
                    jax.block_until_ready(self.cache)
                    self.pos[slot] += 1

    def step(self) -> bool:
        """One decode step over all active slots; True if work remains."""
        self._admit()
        if all(r is None for r in self.active):
            return bool(self._queue)
        toks = np.zeros((self.cfg.max_slots, 1), np.int32)
        for slot, req in enumerate(self.active):
            if req is not None:
                last = (req.out[-1] if req.out else req.prompt[-1])
                toks[slot, 0] = last
        logits, self.cache = self._decode(self.params, self.cache,
                                          jnp.asarray(toks),
                                          jnp.asarray(self.pos))
        nxt = np.asarray(jnp.argmax(logits, axis=-1))
        for slot, req in enumerate(self.active):
            if req is None:
                continue
            tok = int(nxt[slot])
            req.out.append(tok)
            self.pos[slot] += 1
            if (tok == self.cfg.eos_id or len(req.out) >= req.max_new
                    or self.pos[slot] >= self.cfg.max_seq - 1):
                req.done = True
                self.active[slot] = None   # slot freed immediately
        return True

    def run(self) -> None:
        while self.step() or self._queue:
            if all(r is None for r in self.active) and not self._queue:
                break
