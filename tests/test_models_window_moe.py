"""Window and full attention mixed, YaRN, and an expert layer that holds a
share of the experts (Mellum2-12B-A2.5B's one-chip share), at a smoke size
on the CPU: the ring cache beside the full cache, idle slots, the dropless
serve path, the shares adding up to the uncut layer, and the dense step
left as it was."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, smoke_config
from repro.models import build_model
from repro.models import moe as moe_mod
from repro.models import transformer as tmod
from repro.models.layers import rope, yarn, yarn_inv_freq
from repro.serve import Request, ServingEngine, make_serve_step

ARCH = "mellum2-12b-a2.5b-ep4"

# transformers 4.57.6 ``_compute_yarn_parameters`` (float32 torch) at
# Mellum2's full layers: head_dim 128, rope_theta 5e5, factor 16, original
# 8192, beta_fast 32, beta_slow 1; every 8th of the 64 inverse frequencies,
# and the last. Its attention factor is the config's, 1.2772588722239782.
YARN_128 = {0: 1.0, 8: 0.193922758102417, 16: 0.03760603070259094, 24: 0.004879651125520468,
            32: 0.0003223574603907764, 40: 1.7140511772595346e-05, 48: 3.3239348340430297e-06,
            56: 6.445865778914595e-07, 63: 1.5344629389346665e-07}
# and at head_dim 16, theta 1e4, factor 4, original 64 (every frequency)
YARN_16 = [1.0, 0.23717081546783447, 0.04999999701976776, 0.007905694656074047,
           0.0024999999441206455, 0.0007905694656074047, 0.0002500000118743628,
           7.905694656074047e-05]


def smoke(dtype="float32", **kw):
    cfg = smoke_config(ARCH).with_(dtype=dtype, **kw)
    return cfg, build_model(cfg)


def test_yarn_frequencies_match_transformers():
    # float32 in torch against float64 here: 1e-6 relative is its rounding
    got = yarn_inv_freq(128, 5e5, 16.0, 8192, 32.0, 1.0)
    np.testing.assert_allclose([got[i] for i in YARN_128], list(YARN_128.values()), rtol=1e-6)
    np.testing.assert_allclose(yarn_inv_freq(16, 1e4, 4.0, 64, 32.0, 1.0), YARN_16, rtol=1e-6)
    freqs, scale = yarn(get_config(ARCH))
    assert scale == 1.2772588722239782
    np.testing.assert_array_equal(freqs, got)
    assert yarn(get_config("yi-6b")) is None
    # cos and sin carry the scale: at position 0 a head is only scaled, and
    # at position 1 its first pair turns by the first frequency (1 radian)
    x = jnp.asarray(np.random.default_rng(0).normal(size=(1, 2, 1, 128)), jnp.float32)
    out = np.asarray(rope(x, jnp.asarray([[0, 1]]), 5e5, (freqs, scale)))
    np.testing.assert_allclose(out[0, 0], scale * np.asarray(x[0, 0]), rtol=1e-6)
    x0, x64 = float(x[0, 1, 0, 0]), float(x[0, 1, 0, 64])
    np.testing.assert_allclose(out[0, 1, 0, [0, 64]], scale * np.array(
        [x0 * np.cos(1.0) - x64 * np.sin(1.0), x0 * np.sin(1.0) + x64 * np.cos(1.0)]), rtol=1e-5)


def test_registry_counts_the_share_and_the_published_model():
    full, share = get_config("mellum2-12b-a2.5b"), get_config(ARCH)
    assert (full.n_experts, full.router_width) == (64, 64)
    assert (share.n_experts, share.router_width, share.first_expert) == (16, 64, 0)
    d, f = 2304, 896
    assert full.n_params - share.n_params == 28 * 48 * 3 * d * f
    # routed experts per token on this chip: 8 x 16 / 64 = 2
    assert full.n_active_params - share.n_active_params == 28 * 6 * 3 * d * f
    cfg = smoke_config(ARCH)
    assert cfg.n_layers % cfg.full_every == 0 and cfg.router_width > cfg.n_experts


def test_cache_holds_full_layers_and_rings_beside_them():
    cfg, model = smoke()
    shapes = model.cache_shapes(3, 64)
    n_full = cfg.n_layers // cfg.full_every
    assert shapes["full"]["k"].shape == (n_full, 3, cfg.n_kv_heads, 64, cfg.head_dim)
    assert shapes["ring"]["v"].shape == (cfg.n_layers - n_full, 3, cfg.n_kv_heads,
                                         cfg.local_window, cfg.head_dim)
    published = build_model(get_config(ARCH)).cache_shapes(8, 2048)
    assert published["full"]["k"].shape == (7, 8, 4, 2048, 128)
    assert published["ring"]["k"].shape == (21, 8, 4, 1024, 128)


def test_idle_slot_writes_nothing_in_either_stack():
    """A slot at or past the cache's end is idle: the ring's modulo must
    not turn it into a write, and it counts no expert."""
    cfg, model = smoke()
    params = model.init(jax.random.PRNGKey(0))
    S = 32
    cache = jax.tree.map(lambda a: a + 1.0, model.init_cache(4, S))
    lengths = jnp.asarray([5, S, S + 3, 2 * S + 7], jnp.int32)   # S + 3 % 16 and 2S + 7 % 16 in range
    tokens = jnp.asarray([[1], [2], [3], [4]], jnp.int32)
    _, new, counts = model.decode_step(params, cache, tokens, lengths, counts=True)
    for kind in ("full", "ring"):
        for kv in ("k", "v"):
            before, after = np.asarray(cache[kind][kv]), np.asarray(new[kind][kv])
            np.testing.assert_array_equal(after[:, 1:], before[:, 1:])
            assert not np.array_equal(after[:, 0], before[:, 0])
    # slot 0 alone is live: it routes to at most experts_per_token held experts per layer
    touched, pairs = (int(c) for c in counts)
    assert 0 < touched == pairs <= cfg.n_layers * cfg.experts_per_token


def test_decode_over_the_ring_follows_the_window_rule():
    """A sliding layer's decode output over its ring equals attention over
    the keys that ``transformers``' ``sliding_window_overlay`` keeps,
    ``q - window < kv <= q``, long after the ring has wrapped."""
    from repro.models.kvcache import decode_attention_step

    cfg, _ = smoke(yarn_factor=0.0)
    W, S, B, steps = cfg.local_window, 64, 2, 50
    rng = np.random.default_rng(0)
    p = {k: jnp.asarray(rng.normal(0, 0.3, s), jnp.float32) for k, s in (
        ("wq", (cfg.d_model, cfg.n_heads, cfg.head_dim)), ("wk", (cfg.d_model, cfg.n_kv_heads, cfg.head_dim)),
        ("wv", (cfg.d_model, cfg.n_kv_heads, cfg.head_dim)), ("wo", (cfg.n_heads, cfg.head_dim, cfg.d_model)))}
    xs = jnp.asarray(rng.normal(0, 1, (steps, B, 1, cfg.d_model)), jnp.float32)
    ring = {kv: jnp.zeros((1, B, cfg.n_kv_heads, W, cfg.head_dim)) for kv in ("k", "v")}
    full = {kv: jnp.zeros((B, cfg.n_kv_heads, S, cfg.head_dim)) for kv in ("k", "v")}
    for t in range(steps):
        lengths = jnp.full((B,), t, jnp.int32)
        out, ring = decode_attention_step(cfg, p, ring, xs[t], lengths, 0, ring=S)
        _, full = decode_attention_step(cfg, p, full, xs[t], lengths)
    # attention of the last query over the full cache, masked by the rule
    q_pos = steps - 1
    q = jnp.einsum("bsd,dhk->bshk", xs[-1], p["wq"])
    q = rope(q, jnp.full((B, 1), q_pos), cfg.rope_theta)[:, 0]
    kv_pos = np.arange(S)
    seen = (kv_pos <= q_pos) & (kv_pos > q_pos - W)
    g = cfg.n_heads // cfg.n_kv_heads
    scores = jnp.einsum("bkgd,bksd->bkgs", q.reshape(B, cfg.n_kv_heads, g, -1), full["k"]) \
        * cfg.head_dim ** -0.5
    probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
    o = jnp.einsum("bkgs,bksd->bkgd", probs, full["v"]).reshape(B, cfg.n_heads, -1)
    want = jnp.einsum("bhk,hkd->bd", o, p["wo"])[:, None]
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=2e-5, atol=2e-5)


def routed_layer(E_held, R, first, seed=0, T=8):
    cfg = smoke_config(ARCH).with_(dtype="float32", n_experts=E_held, router_experts=R,
                                   first_expert=first)
    rng = np.random.default_rng(seed)
    d, f = cfg.d_model, cfg.d_ff
    p = {"router": rng.normal(0, 0.3, (d, R)), "w_gate": rng.normal(0, 0.1, (R, d, f)),
         "w_up": rng.normal(0, 0.1, (R, d, f)), "w_down": rng.normal(0, 0.1, (R, f, d))}
    x = jnp.asarray(rng.normal(0, 1, (T, 1, d)), jnp.float32)
    return cfg, {k: jnp.asarray(v, jnp.float32) for k, v in p.items()}, x


def held_share(p, first, n):
    return {k: (v if k == "router" else v[first:first + n]) for k, v in p.items()}


def test_shares_add_up_to_the_uncut_layer():
    """Four chips, each holding a quarter of the experts and routing over
    all of them, give parts that sum to the layer that holds every expert
    (float32 rounding of a sum of four terms)."""
    R, n = 16, 4
    cfg, p, x = routed_layer(R, R, 0, T=16)
    live = jnp.ones((x.shape[0],), bool)
    whole, whole_counts = moe_mod.moe_decode(cfg, p, x, live)
    parts, touched, pairs = 0.0, 0, 0
    for c in range(R // n):
        share = cfg.with_(n_experts=n, first_expert=c * n)
        y, counts = moe_mod.moe_decode(share, held_share(p, c * n, n), x, live)
        parts, touched, pairs = parts + y, touched + int(counts[0]), pairs + int(counts[1])
    np.testing.assert_allclose(np.asarray(parts), np.asarray(whole), rtol=1e-5, atol=1e-6)
    assert (touched, pairs) == tuple(int(c) for c in whole_counts)
    assert pairs == x.shape[0] * cfg.experts_per_token


def test_serve_path_drops_nothing_when_every_token_routes_to_one_expert():
    """Every token routes to held expert 0 (and one other): the serve path
    gives each its full gated sum, where the capacity path's floor of 4 rows
    an expert drops the tokens past it."""
    cfg, p, x = routed_layer(4, 8, 0, T=16)
    p["router"] = p["router"].at[:, 0].set(0.0)
    x = x.at[..., 0].set(0.0)
    # a large score for expert 0 from a dimension every token carries
    p["router"] = p["router"].at[0, 0].set(50.0)
    x = x.at[..., 0].set(1.0)
    live = jnp.ones((x.shape[0],), bool)
    held = held_share(p, 0, cfg.n_experts)
    y, counts = moe_mod.moe_decode(cfg, held, x, live)
    xf = x[:, 0]
    probs = jax.nn.softmax(xf @ p["router"], -1)
    top_p, top_i = jax.lax.top_k(probs, cfg.experts_per_token)
    assert (top_i[:, 0] == 0).all()
    top_p = top_p / top_p.sum(-1, keepdims=True)
    want = np.zeros(xf.shape, np.float32)
    for t in range(xf.shape[0]):
        for g, e in zip(np.asarray(top_p[t]), np.asarray(top_i[t])):
            if e < cfg.n_experts:
                h = jax.nn.silu(xf[t] @ p["w_gate"][e]) * (xf[t] @ p["w_up"][e])
                want[t] += g * np.asarray(h @ p["w_down"][e])
    np.testing.assert_allclose(np.asarray(y[:, 0]), want, rtol=1e-5, atol=1e-6)
    assert int(counts[1]) >= x.shape[0]
    capped, _ = moe_mod.moe_block(cfg.with_(capacity_factor=1.0), held, x.reshape(1, -1, cfg.d_model))
    assert not np.allclose(np.asarray(capped[0]), want, atol=1e-4)


def test_dense_step_returns_what_it_did_and_an_expert_step_adds_its_counts():
    for arch, keys in (("yi-6b", {"finite", "kv_blocks"}), (ARCH, {"finite", "kv_blocks", "experts"})):
        cfg = smoke_config(arch).with_(dtype="float32")
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        cache = model.init_cache(2, 32)
        nxt, stats, new_cache = jax.jit(make_serve_step(model))(
            params, cache, jnp.zeros((2, 1), jnp.int32), jnp.zeros((2,), jnp.int32),
            jax.random.PRNGKey(1))
        assert nxt.shape == (2, 1) and nxt.dtype == jnp.int32 and stats["finite"].shape == ()
        assert jax.tree.structure(new_cache) == jax.tree.structure(cache)
        assert set(stats) == keys
        for k in keys - {"finite"}:
            assert stats[k].shape == (2,) and stats[k].dtype == jnp.int32


def test_engine_counts_the_experts_its_decode_steps_touched():
    cfg, model = smoke("bfloat16")
    params = model.init(jax.random.PRNGKey(2))
    engine = ServingEngine(model, params, n_slots=2, max_len=64)
    for i in range(2):
        engine.submit(Request(i, np.arange(3, 8, dtype=np.int32) + i, max_new_tokens=6))
    stats = engine.run_until_drained()
    assert stats.steps > 0
    assert stats.steps <= stats.experts_touched <= stats.expert_pairs
    assert stats.expert_pairs <= stats.steps * 2 * cfg.n_layers * cfg.experts_per_token
    assert stats.experts_touched <= stats.steps * cfg.n_layers * cfg.n_experts


def test_prefill_of_a_window_pattern_raises():
    cfg, model = smoke()
    params = model.init(jax.random.PRNGKey(0))
    with pytest.raises(NotImplementedError, match="token by token"):
        tmod.prefill(cfg, params, model.init_cache(1, 32), {"tokens": jnp.zeros((1, 4), jnp.int32)})


def test_unscanned_layers_give_the_scanned_logits():
    """``scan_layers=False`` (the dry run's unrolled cost model) runs the
    same pattern: the same params, listed by layer, give the same forward
    and decode logits."""
    cfg, model = smoke(capacity_factor=64.0)
    params = model.init(jax.random.PRNGKey(4))
    flat_cfg = cfg.with_(scan_layers=False)
    flat = build_model(flat_cfg)
    listed = dict(params, layers=[jax.tree.map(lambda a: a[i], params["layers"])
                                  for i in range(cfg.n_layers)])
    tokens = jax.random.randint(jax.random.PRNGKey(5), (2, 24), 0, cfg.vocab_size)
    want, _ = model.forward(params, {"tokens": tokens})
    got, _ = flat.forward(listed, {"tokens": tokens})
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)
    caches = [model.init_cache(2, 32), flat.init_cache(2, 32)]
    steps = jax.jit(model.decode_step), jax.jit(flat.decode_step)
    for t in range(24):
        lengths = jnp.full((2,), t, jnp.int32)
        a, caches[0] = steps[0](params, caches[0], tokens[:, t:t + 1], lengths)
        b, caches[1] = steps[1](listed, caches[1], tokens[:, t:t + 1], lengths)
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), rtol=1e-5, atol=1e-5)
