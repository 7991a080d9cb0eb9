"""serve_step: the jitted single-token decode used by the engine & dry run."""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding

from ..models.kvcache import kv_blocks
from ..models.model_api import Model
from ..models.scopes import SAMPLE


def make_serve_step(model: Model, greedy: bool = True, temperature: float = 1.0) -> Callable:
    """Returns serve_step(params, cache, tokens, lengths, rng) ->
    (next_tokens (B,1), step_stats, cache).

    ``step_stats`` is what the host fetches besides the tokens, in the same
    transfer: ``finite`` () is True when every logit of the step is finite
    (the logits themselves stay on the device); ``kv_blocks`` (2,) int32
    the cache blocks that the decode-attention kernel fetched and that the
    caches hold, each summed over the attending layers
    (``kvcache.kv_blocks``); and for a model with experts ``experts`` (2,)
    int32, the held experts that a live token routed to and the (token,
    held expert) pairs, each summed over the layers (``moe.moe_decode``).
    XLA names the jitted program's module after this function,
    ``jit_serve_step``: profiles find the step by that name."""
    experts = model.cfg.family == "moe"

    def serve_step(params, cache, tokens, lengths, rng):
        stats = {"kv_blocks": kv_blocks(cache, lengths)}
        if experts:
            logits, cache, stats["experts"] = model.decode_step(params, cache, tokens, lengths,
                                                                counts=True)
        else:
            logits, cache = model.decode_step(params, cache, tokens, lengths)
        with jax.named_scope(SAMPLE):
            if greedy:
                nxt = jnp.argmax(logits[:, -1], axis=-1)
            else:
                nxt = jax.random.categorical(rng, logits[:, -1] / temperature, axis=-1)
            stats["finite"] = jnp.isfinite(logits).all()
        return nxt[:, None].astype(jnp.int32), stats, cache

    return serve_step


def make_dryrun_serve_step(model: Model) -> Callable:
    """Decode step shaped for the dry run: cache passes through as an
    explicit arg so the compiled program owns no state."""

    def serve_step(params, cache, tokens, lengths):
        logits, cache = model.decode_step(params, cache, tokens, lengths)
        nxt = jnp.argmax(logits[:, -1], axis=-1)
        return nxt[:, None].astype(jnp.int32), cache

    return serve_step
