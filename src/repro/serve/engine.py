"""Continuous (in-flight) batching over a fixed slot-based KV arena.

The engine owns ``max_batch`` generation *slots* in one decode arena
allocated exactly once (``init_decode_cache`` at construction — the
``serve/arena_alloc`` trace instant marks it; there is no
``extend_cache`` anywhere on the serve path).  Each step:

1. **Admit** — queued requests whose arrival time has passed take free
   slots (``mode='continuous'``), or — ``mode='static'`` — only when
   *every* slot is free, modelling the classic run-to-completion batch.
   Admission prefills the request right-padded to ``prompt_capacity``
   (batch-1, fixed shape → one compile) and copies its KV into the slot
   with :func:`~repro.models.model.write_prefill_slot`.
2. **Decode** — one :func:`~repro.models.model.decode_step_slots` over
   the whole arena; every row appends at its own position.  Finished
   rows (budget reached / EOS) free their slots immediately.

Traced, a step is one ``serve/step`` span holding a ``serve/admit`` per
admission (``serve/features``, then ``serve/prefill`` through the
first-token sync), ``serve/decode`` through the next tokens' copy to the
host, and ``serve/emit``, the per-slot append and retire loop.  Two
counters run always: ``Completion.admitted`` (the end of the queue
wait) and ``slot_steps`` (occupied slots summed over decode steps).

Both modes run the *same* per-step computation over the same arena
shape; they differ only in when a free slot may be refilled — the
benchmark's comparison is therefore pure scheduling.  Requests may
carry ``feature_ids``; admission serves them through the attached
:class:`~repro.serve.reuse.RequestStreamCache` (estimated-reuse tier).
"""
from __future__ import annotations

import dataclasses
import functools
from collections import deque
from typing import Deque, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import model as model_lib
from repro.models.config import ModelConfig
from repro.obs import trace as _trace
from repro.serve.request import Completion, Request, StepClock

SERVE_MODES = ("continuous", "static")
# block kinds whose decode state lives entirely in the self-attention KV
# arena; recurrent kinds and local-attention rings would carry padded
# prefill junk into real rows, so the engine refuses them
SERVABLE_KINDS = ("attn", "moe")


@functools.lru_cache(maxsize=None)
def _programs(cfg: ModelConfig):
    """One set of jitted serve programs per (frozen, hashable) config —
    every engine over the same config shares compilations, so a
    continuous-vs-static comparison pays tracing exactly once."""
    prefill = jax.jit(
        lambda p, toks, lens: model_lib.prefill_at(cfg, p, toks, lens)
    )
    write_slot = jax.jit(
        lambda arena, slot, pre: model_lib.write_prefill_slot(
            cfg, arena, slot, pre
        )
    )
    decode = jax.jit(
        lambda p, cache, toks: model_lib.decode_step_slots(cfg, p, cache, toks)
    )
    return prefill, write_slot, decode


@dataclasses.dataclass
class _Slot:
    request: Request
    tokens: List[int]
    admitted: float
    first_token: float


class ServeEngine:
    """Request queue → continuous-batching scheduler → prefill/decode."""

    def __init__(
        self,
        cfg: ModelConfig,
        params,
        *,
        max_batch: int,
        prompt_capacity: int,
        max_new_tokens: int,
        mode: str = "continuous",
        feature_cache=None,
        eos_id: Optional[int] = None,
        clock: Optional[StepClock] = None,
    ):
        if mode not in SERVE_MODES:
            raise ValueError(f"mode must be one of {SERVE_MODES}, got {mode!r}")
        for pattern, _ in cfg.stages:
            for kind in pattern:
                if kind not in SERVABLE_KINDS:
                    raise ValueError(
                        f"serving engine supports {SERVABLE_KINDS} blocks; "
                        f"got {kind!r} (recurrent state / local rings would "
                        "carry padded-prefill junk)"
                    )
        self.cfg = cfg
        self.params = params
        self.mode = mode
        self.max_batch = int(max_batch)
        self.prompt_capacity = int(prompt_capacity)
        self.max_new_tokens = int(max_new_tokens)
        self.capacity = self.prompt_capacity + self.max_new_tokens
        self.feature_cache = feature_cache
        self.eos_id = eos_id
        self.clock = clock or StepClock()

        # the one arena allocation of the engine's lifetime — decode
        # never reallocates (tests assert exactly one of these instants)
        self.arena = model_lib.init_decode_cache(
            cfg, self.max_batch, self.capacity,
            pos=jnp.zeros((self.max_batch,), jnp.int32),
        )
        arena_bytes = sum(
            x.nbytes for x in jax.tree_util.tree_leaves(self.arena)
        )
        _trace.instant(
            "serve/arena_alloc", "serve",
            args={"bytes": arena_bytes, "slots": self.max_batch,
                  "capacity": self.capacity},
        )

        self._prefill, self._write_slot, self._decode = _programs(cfg)

        self.queue: Deque[Request] = deque()
        self.slots: Dict[int, _Slot] = {}
        self._free: List[int] = list(range(self.max_batch))
        self._cur = np.zeros((self.max_batch, 1), np.int32)
        self.completions: List[Completion] = []
        # counters
        self.steps = 0
        self.decode_steps = 0
        # occupied slots summed over decode steps: / decode_steps is the
        # mean batch a decode step carried
        self.slot_steps = 0
        self.prefills = 0
        self.generated_tokens = 0

    # ------------------------------------------------------------- queue
    @property
    def free_slots(self) -> int:
        return len(self._free)

    @property
    def active(self) -> int:
        return len(self.slots)

    def submit(self, request: Request) -> None:
        if len(request.prompt) > self.prompt_capacity:
            raise ValueError(
                f"prompt of {len(request.prompt)} exceeds prompt_capacity "
                f"{self.prompt_capacity}"
            )
        if request.max_new_tokens > self.max_new_tokens:
            raise ValueError(
                f"max_new_tokens {request.max_new_tokens} exceeds the "
                f"engine's generation arena {self.max_new_tokens}"
            )
        self.queue.append(request)

    # --------------------------------------------------------- admission
    def _arrived(self) -> bool:
        return bool(self.queue) and self.queue[0].arrival <= self.clock.now()

    def _admit_one(self, req: Request, slot: int) -> None:
        now = self.clock.now()
        with _trace.span(
            "serve/admit", "serve",
            args={"rid": req.rid} if _trace.enabled() else None,
        ):
            if self.feature_cache is not None and req.feature_ids is not None:
                with _trace.span("serve/features", "serve"):
                    self.feature_cache.fetch(req.feature_ids, now)
            padded = np.zeros((1, self.prompt_capacity), np.int32)
            padded[0, : len(req.prompt)] = req.prompt
            with _trace.span("serve/prefill", "serve"):
                pre, logits = self._prefill(
                    self.params, jnp.asarray(padded),
                    jnp.asarray([len(req.prompt)], jnp.int32),
                )
                self.arena = self._write_slot(self.arena, slot, pre)
                first = int(jnp.argmax(logits[0], -1))
            # the first token is on the host only after the argmax sync
            first_at = self.clock.now()
            self._cur[slot, 0] = first
            self.slots[slot] = _Slot(
                request=req, tokens=[first], admitted=now, first_token=first_at
            )
            self.prefills += 1
            self.generated_tokens += 1
            if self._finished(self.slots[slot]):
                self._retire(slot, first_at)

    def _admit(self) -> int:
        admitted = 0
        if self.mode == "continuous":
            while self._free and self._arrived():
                self._admit_one(self.queue.popleft(), self._free.pop())
                admitted += 1
        else:  # static: refill only at a whole-batch boundary
            if not self.slots:
                while self._free and self._arrived():
                    self._admit_one(self.queue.popleft(), self._free.pop())
                    admitted += 1
        return admitted

    # ------------------------------------------------------- decode step
    def _finished(self, s: _Slot) -> bool:
        if len(s.tokens) >= s.request.max_new_tokens:
            return True
        return self.eos_id is not None and s.tokens[-1] == self.eos_id

    def _retire(self, slot: int, finished: float) -> None:
        s = self.slots.pop(slot)
        self._free.append(slot)
        self.completions.append(
            Completion(
                rid=s.request.rid,
                tokens=s.tokens,
                arrival=s.request.arrival,
                admitted=s.admitted,
                first_token=s.first_token,
                finished=finished,
            )
        )

    def step(self) -> None:
        """One engine step: admit, decode the whole arena once, retire."""
        with _trace.span(
            "serve/step", "serve",
            args={"step": self.steps} if _trace.enabled() else None,
        ):
            self._admit()
            if self.slots:
                with _trace.span("serve/decode", "serve"):
                    self.arena, logits = self._decode(
                        self.params, self.arena, jnp.asarray(self._cur)
                    )
                    nxt = np.asarray(jnp.argmax(logits, -1), np.int32).reshape(-1)
                self.decode_steps += 1
                self.slot_steps += len(self.slots)
                self.clock.advance(1.0)
                done = self.clock.now()
                with _trace.span("serve/emit", "serve"):
                    for slot in list(self.slots):
                        tok = int(nxt[slot])
                        self._cur[slot, 0] = tok
                        s = self.slots[slot]
                        s.tokens.append(tok)
                        self.generated_tokens += 1
                        if self._finished(s):
                            self._retire(slot, done)
            else:
                self.clock.advance(1.0)
            self.steps += 1

    def warmup(self) -> None:
        """Compile the prefill/slot-insert/decode programs (all fixed
        shapes, so each compiles exactly once) before measured steps.
        The junk KV this writes into slot 0 is overwritten at its next
        admission before any decode attends it."""
        toks = jnp.zeros((1, self.prompt_capacity), jnp.int32)
        pre, plog = self._prefill(self.params, toks, jnp.asarray([1], jnp.int32))
        int(jnp.argmax(plog[0], -1))  # the admit-path argmax program
        self.arena = self._write_slot(self.arena, 0, pre)
        self.arena, dlog = self._decode(
            self.params, self.arena, jnp.asarray(self._cur)
        )
        np.asarray(jnp.argmax(dlog, -1))  # the decode-path argmax program
        self.arena["pos"] = jnp.zeros((self.max_batch,), jnp.int32)

    # --------------------------------------------------------------- run
    def run(self, requests=None) -> List[Completion]:
        """Drive the engine until queue and slots drain; returns all
        completions (arrival order is whatever ``requests`` carries)."""
        if requests is not None:
            for r in sorted(requests, key=lambda r: r.arrival):
                self.submit(r)
        while self.queue or self.slots:
            if not self.slots and self.queue:
                gap = self.queue[0].arrival - self.clock.now()
                if gap > 0:  # idle: jump to the next arrival
                    self.clock.advance(gap)
            self.step()
        return self.completions
