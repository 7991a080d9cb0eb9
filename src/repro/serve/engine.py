"""Batched serving engine with continuous batching + Colmena steering hooks.

Slots hold independent requests; each engine step decodes one token for
every active slot (synchronized step, per-slot lengths). Finished slots
(eos or max tokens) are refilled from the admission queue without
stopping the batch — continuous batching. The engine exposes callbacks
(``on_token``, ``on_finish``) that a Colmena Thinker uses for steering
(e.g. early-stopping low-value generations — the paper's "stop evaluating
low-performing candidates" multi-fidelity lesson applied to serving).

The engine marks its work with ``jax.profiler.TraceAnnotation`` spans,
which land in a profiler trace on the device's clock; with no profiler
running each costs about 0.4 us (measured on a TPU v5e host). One
``step()`` holds:

- ``serve.admit``, only when a slot is filled, with one ``serve.prefill``
  per request placed in a slot (its serve-step calls and host round trips);
- ``serve.dispatch``: launching the decode step;
- ``serve.device_get``: the step's one device-to-host transfer (the next
  tokens, the finiteness flag, the cache blocks the decode-attention
  kernel fetched and, for a model with experts, the expert layers'
  counts);
- ``serve.bookkeeping``: the per-slot loop, with ``serve.hooks`` around
  each ``on_token``/``on_finish`` call (the caller's code).
"""

from __future__ import annotations

import queue
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from ..models.model_api import Model
from ..models import transformer as tmod
from .decode import make_serve_step


@dataclass
class Request:
    request_id: int
    prompt: np.ndarray                   # (P,) int32
    max_new_tokens: int = 32
    eos_token: Optional[int] = None
    # filled by the engine:
    generated: List[int] = field(default_factory=list)
    submitted_at: float = field(default_factory=time.monotonic)
    first_token_at: Optional[float] = None
    finished_at: Optional[float] = None
    cancelled: bool = False


@dataclass
class EngineStats:
    steps: int = 0
    tokens_generated: int = 0
    requests_finished: int = 0
    requests_cancelled: int = 0
    batch_occupancy_sum: float = 0.0
    nonfinite_steps: int = 0             # steps whose logits held a NaN/inf
    admissions: int = 0                  # requests placed in a slot
    prefill_calls: int = 0               # serve-step calls that fed prompt tokens
    prefill_tokens: int = 0              # prompt tokens fed through those calls
    experts_touched: int = 0             # decode steps: held experts a live token routed to, summed over layers
    expert_pairs: int = 0                # decode steps: (live token, held expert) pairs, summed over layers
    kv_blocks_read: int = 0              # decode steps: cache blocks the attention kernel fetched, summed over layers
    kv_blocks_held: int = 0              # decode steps: cache blocks the caches hold, summed over layers

    @property
    def mean_occupancy(self) -> float:
        return self.batch_occupancy_sum / max(self.steps, 1)


class ServingEngine:
    """Continuous-batching engine over Model.decode_step (transformer
    families; prompt prefill is token-by-token for recurrent families)."""

    def __init__(
        self,
        model: Model,
        params: Any,
        n_slots: int = 4,
        max_len: int = 256,
        on_token: Optional[Callable[[Request, int], bool]] = None,
        on_finish: Optional[Callable[[Request], None]] = None,
    ) -> None:
        self.model = model
        self.params = params
        self.n_slots = n_slots
        self.max_len = max_len
        self.on_token = on_token
        self.on_finish = on_finish
        self.stats = EngineStats()

        self._admit: "queue.Queue[Request]" = queue.Queue()
        self._slots: List[Optional[Request]] = [None] * n_slots
        # The cache is donated, so the step's output cache is its input's buffer:
        # each layer writes its new token there in place and nothing copies it.
        # Without donation, every step dispatched ahead of the device would hold
        # a whole cache of its own.
        self._serve = jax.jit(make_serve_step(model), donate_argnums=(1,))
        self._cache = model.init_cache(n_slots, max_len)
        self._lengths = jnp.zeros((n_slots,), jnp.int32)
        self._tokens = jnp.zeros((n_slots, 1), jnp.int32)
        self._rng = jax.random.PRNGKey(0)

    def compile(self) -> "jax.stages.Compiled":
        """Compile the step for this engine's shapes ahead of serving.

        Later steps run the returned executable, so no compilation falls
        inside the serving window; its ``as_text()`` shows which kernels
        the step runs."""
        self._serve = self._serve.lower(
            self.params, self._cache, self._tokens, self._lengths, self._rng
        ).compile()
        return self._serve

    # ----------------------------------------------------------------- admit
    def submit(self, req: Request) -> None:
        self._admit.put(req)

    def _try_fill_slots(self) -> None:
        free = [i for i, r in enumerate(self._slots) if r is None]
        if not free or self._admit.empty():
            return
        with TraceAnnotation("serve.admit"):
            for i in free:
                try:
                    req = self._admit.get_nowait()
                except queue.Empty:
                    return
                with TraceAnnotation("serve.prefill"):
                    self._prefill_slot(i, req)

    def _prefill_slot(self, i: int, req: Request) -> None:
        """Feed the prompt through decode steps for slot i.

        Idle slots are unaffected: their spurious cache writes land at the
        position their *next* real token will overwrite, and their outputs
        are discarded. The last prompt token is NOT prefed — it becomes
        slot i's current input so the next engine step generates from it."""
        lengths = np.asarray(self._lengths).copy()
        lengths[i] = 0
        self._lengths = jnp.asarray(lengths)
        for tok in req.prompt[:-1]:
            tok_vec = np.asarray(self._tokens).copy()
            tok_vec[i, 0] = int(tok)
            self._tokens = jnp.asarray(tok_vec)
            self._cache = self._serve(
                self.params, self._cache, self._tokens, self._lengths, self._rng
            )[2]
            self.stats.prefill_calls += 1
            self.stats.prefill_tokens += 1
            lengths = np.asarray(self._lengths).copy()
            lengths[i] += 1
            self._lengths = jnp.asarray(lengths)
        tok_vec = np.asarray(self._tokens).copy()
        tok_vec[i, 0] = int(req.prompt[-1])
        self._tokens = jnp.asarray(tok_vec)
        self._slots[i] = req
        self.stats.admissions += 1

    # ------------------------------------------------------------------ step
    def step(self) -> int:
        """One decode step for all active slots; returns #active."""
        self._try_fill_slots()
        active = [i for i, r in enumerate(self._slots) if r is not None]
        if not active:
            return 0
        with TraceAnnotation("serve.dispatch"):
            self._rng, sub = jax.random.split(self._rng)
            nxt, step_stats, self._cache = self._serve(
                self.params, self._cache, self._tokens, self._lengths, sub)
            self._tokens = nxt
            self._lengths = self._lengths + 1
        with TraceAnnotation("serve.device_get"):
            nxt_np, step_stats = jax.device_get((nxt, step_stats))

        with TraceAnnotation("serve.bookkeeping"):
            self._bookkeeping(active, nxt_np, step_stats)
        return len(active)

    def _bookkeeping(self, active: List[int], nxt_np: np.ndarray,
                     step_stats: Dict[str, np.ndarray]) -> None:
        self.stats.steps += 1
        if not step_stats["finite"]:
            self.stats.nonfinite_steps += 1
        self.stats.kv_blocks_read += int(step_stats["kv_blocks"][0])
        self.stats.kv_blocks_held += int(step_stats["kv_blocks"][1])
        if "experts" in step_stats:
            self.stats.experts_touched += int(step_stats["experts"][0])
            self.stats.expert_pairs += int(step_stats["experts"][1])
        self.stats.batch_occupancy_sum += len(active) / self.n_slots
        for i in active:
            req = self._slots[i]
            tok = int(nxt_np[i, 0])
            if req.first_token_at is None:
                req.first_token_at = time.monotonic()
            req.generated.append(tok)
            self.stats.tokens_generated += 1
            stop = False
            if self.on_token is not None:
                with TraceAnnotation("serve.hooks"):
                    stop = bool(self.on_token(req, tok))
                if stop:
                    req.cancelled = True
                    self.stats.requests_cancelled += 1
            if req.eos_token is not None and tok == req.eos_token:
                stop = True
            if len(req.generated) >= req.max_new_tokens:
                stop = True
            if stop:
                req.finished_at = time.monotonic()
                self.stats.requests_finished += 1
                if self.on_finish is not None:
                    with TraceAnnotation("serve.hooks"):
                        self.on_finish(req)
                self._slots[i] = None

    def run_until_drained(self, max_steps: int = 10_000) -> EngineStats:
        for _ in range(max_steps):
            if self.step() == 0 and self._admit.empty():
                break
        return self.stats
