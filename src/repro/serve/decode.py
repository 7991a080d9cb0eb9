"""serve_step: the jitted single-token decode used by the engine & dry run."""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding

from ..models.model_api import Model
from ..models.scopes import SAMPLE


def make_serve_step(model: Model, greedy: bool = True, temperature: float = 1.0) -> Callable:
    """Returns serve_step(params, cache, tokens, lengths, rng) ->
    (next_tokens (B,1), logits_finite (), cache), and for a model with
    experts a fourth output: the expert layers' counts (2,) int32, the held
    experts that a live token routed to and the (token, held expert) pairs,
    each summed over the layers (``moe.moe_decode``).

    ``logits_finite`` is True when every logit of the step is finite; the
    logits themselves stay on the device. XLA names the jitted program's
    module after this function, ``jit_serve_step``: profiles find the
    step by that name."""
    experts = model.cfg.family == "moe"

    def serve_step(params, cache, tokens, lengths, rng):
        if experts:
            logits, cache, counts = model.decode_step(params, cache, tokens, lengths, counts=True)
        else:
            logits, cache = model.decode_step(params, cache, tokens, lengths)
        with jax.named_scope(SAMPLE):
            if greedy:
                nxt = jnp.argmax(logits[:, -1], axis=-1)
            else:
                nxt = jax.random.categorical(rng, logits[:, -1] / temperature, axis=-1)
            finite = jnp.isfinite(logits).all()
        out = (nxt[:, None].astype(jnp.int32), finite, cache)
        return out + (counts,) if experts else out

    return serve_step


def make_dryrun_serve_step(model: Model) -> Callable:
    """Decode step shaped for the dry run: cache passes through as an
    explicit arg so the compiled program owns no state."""

    def serve_step(params, cache, tokens, lengths):
        logits, cache = model.decode_step(params, cache, tokens, lengths)
        nxt = jnp.argmax(logits[:, -1], axis=-1)
        return nxt[:, None].astype(jnp.int32), cache

    return serve_step
