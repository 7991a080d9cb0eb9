"""serve_step: the jitted single-token decode used by the engine & dry run."""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding

from ..models.model_api import Model


def make_serve_step(model: Model, greedy: bool = True, temperature: float = 1.0) -> Callable:
    """Returns serve_step(params, cache, tokens, lengths, rng) ->
    (next_tokens (B,1), logits_finite (), cache).

    ``logits_finite`` is True when every logit of the step is finite; the
    logits themselves stay on the device."""

    def serve_step(params, cache, tokens, lengths, rng):
        logits, cache = model.decode_step(params, cache, tokens, lengths)
        if greedy:
            nxt = jnp.argmax(logits[:, -1], axis=-1)
        else:
            nxt = jax.random.categorical(rng, logits[:, -1] / temperature, axis=-1)
        return nxt[:, None].astype(jnp.int32), jnp.isfinite(logits).all(), cache

    return serve_step


def make_dryrun_serve_step(model: Model) -> Callable:
    """Decode step shaped for the dry run: cache passes through as an
    explicit arg so the compiled program owns no state."""

    def serve_step(params, cache, tokens, lengths):
        logits, cache = model.decode_step(params, cache, tokens, lengths)
        nxt = jnp.argmax(logits[:, -1], axis=-1)
        return nxt[:, None].astype(jnp.int32), cache

    return serve_step
