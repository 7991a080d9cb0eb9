"""The decode step's in-place KV-cache write against the one-hot rewrite it
replaced, kept here as the reference: the same values land in the same
places, positions past the cache's end write nothing, and an engine whose
step uses either write generates the same tokens."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.models import build_model, kvcache
from repro.serve import Request, ServingEngine

L, B, KV, S, HD = 3, 4, 2, 16, 8


def onehot_update_cache(cache_k, cache_v, k_new, v_new, lengths, layer=None):
    """The write as a whole-cache rewrite, cache * (1 - onehot) + onehot * new;
    with ``layer`` it rewrites that layer's slice of a stacked cache."""
    if layer is not None:
        take = lambda c: jax.lax.dynamic_index_in_dim(c, layer, keepdims=False)
        ck, cv = onehot_update_cache(take(cache_k), take(cache_v), k_new, v_new, lengths)
        put = lambda c, u: jax.lax.dynamic_update_index_in_dim(c, u, layer, 0)
        return put(cache_k, ck), put(cache_v, cv)
    onehot = jax.nn.one_hot(lengths, cache_k.shape[2], dtype=cache_k.dtype)
    sel = onehot[:, None, :, None]
    return (cache_k * (1 - sel) + sel * k_new.swapaxes(1, 2),
            cache_v * (1 - sel) + sel * v_new.swapaxes(1, 2))


POSITIONS = {
    "random": lambda rng: rng.integers(0, S, B),
    "first_and_last": lambda rng: np.asarray([0, S - 1, 0, S - 1]),
    "past_the_end": lambda rng: np.asarray([S, S + 1, S - 1, 3 * S]),
    # _prefill_slot: slot 1 restarts at 0, the others (one idle past the end) keep theirs
    "prefill": lambda rng: np.asarray([9, 0, S + 4, 5]),
    # griffin's ring buffer writes at lengths % window
    "ring_slots": lambda rng: rng.integers(0, 10 * S, B) % S,
}


@pytest.mark.parametrize("layer", [None, 0, L - 1], ids=["per-layer", "stack-first", "stack-last"])
@pytest.mark.parametrize("case", sorted(POSITIONS))
def test_in_place_write_equals_onehot_rewrite(case, layer):
    rng = np.random.default_rng(sorted(POSITIONS).index(case))
    shape = (B, KV, S, HD) if layer is None else (L, B, KV, S, HD)
    draw = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.bfloat16)
    ck, cv = draw(*shape), draw(*shape)
    k_new, v_new = draw(B, 1, KV, HD), draw(B, 1, KV, HD)
    lengths = jnp.asarray(POSITIONS[case](rng), jnp.int32)
    lyr = None if layer is None else jnp.int32(layer)

    got = jax.jit(kvcache.update_cache)(ck, cv, k_new, v_new, lengths, lyr)
    want = jax.jit(onehot_update_cache)(ck, cv, k_new, v_new, lengths, lyr)
    for g, w, old in zip(got, want, (ck, cv)):
        assert g.dtype == old.dtype and g.shape == old.shape
        np.testing.assert_array_equal(np.asarray(g, np.float32), np.asarray(w, np.float32))


def test_engine_tokens_match_the_onehot_step(monkeypatch):
    """Greedy tokens of the engine, with idle slots counting past the
    cache's end, equal those of the same engine stepping the one-hot write."""
    cfg = smoke_config("phi4-mini-3.8b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(3))
    # At its init scale the smoke model repeats its last token whatever the
    # cache holds; larger layer weights make the tokens depend on it.
    params["layers"] = jax.tree.map(lambda w: w * 8 if w.ndim > 2 else w, params["layers"])
    rng = np.random.default_rng(5)
    prompts = [rng.integers(1, cfg.vocab_size, n).astype(np.int32) for n in (3, 5, 4, 6, 2)]

    def generate():
        eng = ServingEngine(model, params, n_slots=2, max_len=S)
        done = {}
        eng.on_finish = lambda r: done.setdefault(r.request_id, list(r.generated))
        for i, p in enumerate(prompts):
            eng.submit(Request(request_id=i, prompt=p, max_new_tokens=S - len(p)))
        stats = eng.run_until_drained()
        assert stats.nonfinite_steps == 0 and len(done) == len(prompts)
        return done, int(np.max(np.asarray(eng._lengths)))

    got, top = generate()
    assert top > S                           # an idle slot wrote past the end
    monkeypatch.setattr(kvcache, "update_cache", onehot_update_cache)
    want, _ = generate()
    assert got == want
