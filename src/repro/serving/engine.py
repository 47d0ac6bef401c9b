"""Batched serving engine: continuous batching over a fixed-slot KV cache.

The paper's composite-workload idea at serving granularity: B cache slots
are the "harts"; heterogeneous requests (different lengths/phases) share
the same compute engine. Scheduler policy:

  * new requests are admitted into free slots (prefill one sequence at a
    time through the shared prefill step — TPU-friendly static shapes),
  * every engine step decodes ALL active slots in one batched decode_step,
  * finished sequences (EOS or max_tokens) free their slot immediately
    (continuous batching — no head-of-line blocking on long generations).

Runs on CPU with small models in examples/serve_lm.py; the same engine
drives the decode_32k serving cells on the production mesh.
"""
from __future__ import annotations

import itertools
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

import numpy as np
import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeConfig
from repro.models import steps as steps_lib


@dataclass
class Request:
    rid: int
    prompt: np.ndarray                 # [len] int32
    max_new_tokens: int = 32
    eos_id: int = -1                   # -1 => never
    out_tokens: List[int] = field(default_factory=list)
    submitted_at: float = field(default_factory=time.monotonic)
    first_token_at: Optional[float] = None
    done_at: Optional[float] = None


class ServingEngine:
    def __init__(self, cfg: ModelConfig, params, *, slots: int = 4,
                 max_seq: int = 512, rules=None, par=None):
        from repro.configs.base import Parallelism
        from repro.models.sharding import make_rules
        self.cfg = cfg
        self.par = par or Parallelism(remat="none")
        self.rules = rules or make_rules(None, cfg, self.par)
        self.params = params
        self.slots = slots
        self.max_seq = max_seq
        shape = ShapeConfig("serve", "decode", max_seq, slots)
        self.shape = shape

        self._decode = jax.jit(steps_lib.make_decode_step(
            cfg, self.rules, self.par, shape), donate_argnums=(1,))
        # per-slot prefill uses batch=1 cache then scatters into slot caches;
        # for simplicity and static shapes we re-embed prompts token-by-token
        # through the decode step (prefill == teacher-forced decode), which
        # keeps ONE compiled executable for the whole engine.
        self.cache = self._init_cache()
        self.active: Dict[int, Request] = {}       # slot -> request
        self.queue: List[Request] = []
        self.slot_pos = np.zeros(slots, np.int64)  # per-slot write position
        self.slot_prompt_left: Dict[int, List[int]] = {}
        self._finished: List[Request] = []

    # ------------------------------------------------------------------
    def _init_cache(self):
        from repro.models import params as params_lib
        t = steps_lib.cache_template(self.cfg, self.shape)
        return params_lib.initialize(t, jax.random.PRNGKey(0))

    def submit(self, req: Request):
        self.queue.append(req)

    # ------------------------------------------------------------------
    def _reset_slot(self, s: int):
        """Invalidate a slot's cache lines before reuse (continuous
        batching: new request must not attend to stale entries)."""
        lc = self.cache["layers"]
        for key in ("cpos",):
            if key in lc:
                lc[key] = lc[key].at[:, s, :].set(-1)
        for key in ("conv", "state"):
            if key in lc:
                lc[key] = lc[key].at[:, s].set(0)
        self.cache["pos"] = self.cache["pos"].at[s].set(0)
        self.cache["layers"] = lc

    def _admit(self):
        free = [s for s in range(self.slots) if s not in self.active]
        while free and self.queue:
            s = free.pop(0)
            req = self.queue.pop(0)
            self._reset_slot(s)
            self.active[s] = req
            self.slot_prompt_left[s] = list(req.prompt)
        return

    def step(self):
        """One engine step: feed each active slot its next token (prompt
        token during prefill phase, last sampled token during decode)."""
        self._admit()
        if not self.active:
            return False
        tokens = np.zeros((self.slots, 1), np.int32)
        for s, req in self.active.items():
            left = self.slot_prompt_left[s]
            if left:
                tokens[s, 0] = left.pop(0)
            else:
                tokens[s, 0] = req.out_tokens[-1] if req.out_tokens else 0
        logits, self.cache = self._decode(self.params, self.cache,
                                          {"tokens": jnp.asarray(tokens)})
        last = logits[:, -1]
        next_tok, finite = jax.device_get(
            (jnp.argmax(last, axis=-1), jnp.isfinite(last).all()))
        if not finite:
            raise FloatingPointError("decode step produced non-finite "
                                     "logits")
        now = time.monotonic()
        done_slots = []
        for s, req in self.active.items():
            if self.slot_prompt_left[s]:
                continue                       # still prefill phase
            tok = int(next_tok[s])
            if req.first_token_at is None:
                req.first_token_at = now
            req.out_tokens.append(tok)
            if tok == req.eos_id or len(req.out_tokens) >= req.max_new_tokens:
                req.done_at = now
                done_slots.append(s)
        for s in done_slots:
            self._finished.append(self.active.pop(s))
            self.slot_prompt_left.pop(s, None)
        return True

    def run_until_drained(self, max_steps: int = 10_000) -> List[Request]:
        for _ in range(max_steps):
            if not self.step() and not self.queue:
                break
        return self._finished

    @property
    def finished(self) -> List[Request]:
        return self._finished
