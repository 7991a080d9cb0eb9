"""Per-kernel validation: Pallas (interpret=True) and XLA paths vs. the
pure-jnp oracle, swept over shapes/dtypes with hypothesis."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp
pytest.importorskip("hypothesis")  # optional dep: pip install -e .[test]
from hypothesis import given, settings, strategies as st, HealthCheck

from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.decode_attention.ops import decode_attention
from repro.kernels.rglru_scan.ops import rglru_scan
from repro.kernels.wkv6.ops import wkv6
from repro.kernels.rmsnorm.ops import rmsnorm

SETTINGS = dict(
    max_examples=8, deadline=None,
    suppress_health_check=[HealthCheck.too_slow, HealthCheck.data_too_large],
)


def rel_err(a, b):
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    return float(np.max(np.abs(a - b)) / (np.max(np.abs(b)) + 1e-9))


@st.composite
def attn_shapes(draw):
    b = draw(st.sampled_from([1, 2]))
    kvh = draw(st.sampled_from([1, 2]))
    group = draw(st.sampled_from([1, 2, 4]))
    s = draw(st.sampled_from([32, 64, 96]))
    d = draw(st.sampled_from([16, 32]))
    dtype = draw(st.sampled_from([jnp.float32, jnp.bfloat16]))
    return b, kvh * group, kvh, s, d, dtype


class TestFlashAttention:
    @given(attn_shapes(), st.booleans(), st.sampled_from([None, 24]))
    @settings(**SETTINGS)
    def test_xla_matches_ref(self, shp, causal, window, ):
        b, h, kvh, s, d, dtype = shp
        key = jax.random.PRNGKey(b * 1000 + h)
        q = jax.random.normal(key, (b, h, s, d), dtype)
        k = jax.random.normal(jax.random.fold_in(key, 1), (b, kvh, s, d), dtype)
        v = jax.random.normal(jax.random.fold_in(key, 2), (b, kvh, s, d), dtype)
        if window is not None and not causal:
            causal = True   # windows only used with causal attention here
        ref = flash_attention(q, k, v, causal=causal, window=window, impl="ref")
        out = flash_attention(q, k, v, causal=causal, window=window, impl="xla", block_k=32)
        tol = 2e-2 if dtype == jnp.bfloat16 else 1e-4
        assert rel_err(out, ref) < tol

    @given(attn_shapes())
    @settings(**SETTINGS)
    def test_pallas_interpret_matches_ref(self, shp):
        b, h, kvh, s, d, dtype = shp
        key = jax.random.PRNGKey(h * 100 + s)
        q = jax.random.normal(key, (b, h, s, d), dtype)
        k = jax.random.normal(jax.random.fold_in(key, 1), (b, kvh, s, d), dtype)
        v = jax.random.normal(jax.random.fold_in(key, 2), (b, kvh, s, d), dtype)
        ref = flash_attention(q, k, v, causal=True, impl="ref")
        out = flash_attention(q, k, v, causal=True, impl="interpret",
                              block_q=32, block_k=32)
        tol = 2e-2 if dtype == jnp.bfloat16 else 1e-4
        assert rel_err(out, ref) < tol

    def test_blockwise_skip_equals_full(self):
        key = jax.random.PRNGKey(0)
        q = jax.random.normal(key, (2, 4, 128, 32))
        k = jax.random.normal(jax.random.fold_in(key, 1), (2, 2, 128, 32))
        v = jax.random.normal(jax.random.fold_in(key, 2), (2, 2, 128, 32))
        ref = flash_attention(q, k, v, causal=True, impl="ref")
        out = flash_attention(q, k, v, causal=True, impl="xla", block_k=32,
                              skip_masked_blocks=True)
        assert rel_err(out, ref) < 1e-4

    def test_q_offset_decode_chunk(self):
        """Chunked prefill: q at an offset into the kv sequence."""
        key = jax.random.PRNGKey(3)
        skv, sq, off = 64, 16, 48
        q = jax.random.normal(key, (1, 2, sq, 16))
        k = jax.random.normal(jax.random.fold_in(key, 1), (1, 2, skv, 16))
        v = jax.random.normal(jax.random.fold_in(key, 2), (1, 2, skv, 16))
        ref = flash_attention(q, k, v, causal=True, q_offset=off, impl="ref")
        out = flash_attention(q, k, v, causal=True, q_offset=off, impl="xla", block_k=16)
        assert rel_err(out, ref) < 1e-4


class TestDecodeAttention:
    @given(st.sampled_from([1, 2, 4]), st.sampled_from([1, 2]),
           st.sampled_from([32, 64]), st.sampled_from([jnp.float32, jnp.bfloat16]))
    @settings(**SETTINGS)
    def test_interpret_matches_ref(self, group, kvh, s, dtype):
        b, d = 2, 16
        h = group * kvh
        key = jax.random.PRNGKey(group * 10 + s)
        q = jax.random.normal(key, (b, h, d), dtype)
        k = jax.random.normal(jax.random.fold_in(key, 1), (b, kvh, s, d), dtype)
        v = jax.random.normal(jax.random.fold_in(key, 2), (b, kvh, s, d), dtype)
        lengths = jnp.asarray([s // 2, s - 1], jnp.int32)
        ref = decode_attention(q, k, v, lengths, impl="ref")
        out = decode_attention(q, k, v, lengths, impl="interpret", block_k=16)
        tol = 2e-2 if dtype == jnp.bfloat16 else 1e-4
        assert rel_err(out, ref) < tol

    def test_windowed(self):
        key = jax.random.PRNGKey(1)
        q = jax.random.normal(key, (2, 2, 16))
        k = jax.random.normal(jax.random.fold_in(key, 1), (2, 2, 64, 16))
        v = jax.random.normal(jax.random.fold_in(key, 2), (2, 2, 64, 16))
        lengths = jnp.asarray([40, 63], jnp.int32)
        ref = decode_attention(q, k, v, lengths, window=16, impl="ref")
        out = decode_attention(q, k, v, lengths, window=16, impl="interpret", block_k=16)
        assert rel_err(out, ref) < 1e-4

    @pytest.mark.parametrize("layer", [0, 2, 3])
    @pytest.mark.parametrize("impl", ["interpret", "ref"])
    def test_layer_index_reads_its_layer_of_the_stack(self, layer, impl):
        """With a layer index into a stacked (L, B, KV, S, d) cache, each
        path reads that layer as if handed its slice."""
        n_layers, b, h, kvh, s, d = 4, 2, 4, 2, 64, 16
        key = jax.random.PRNGKey(layer)
        q = jax.random.normal(key, (b, h, d))
        k = jax.random.normal(jax.random.fold_in(key, 1), (n_layers, b, kvh, s, d))
        v = jax.random.normal(jax.random.fold_in(key, 2), (n_layers, b, kvh, s, d))
        lengths = jnp.asarray([1, s], jnp.int32)
        ref = decode_attention(q, k[layer], v[layer], lengths, impl="ref")
        out = jax.jit(lambda l: decode_attention(q, k, v, lengths, l, impl=impl, block_k=16))(
            jnp.int32(layer))
        assert rel_err(out, ref) < 1e-4


class TestRglruScan:
    @given(st.sampled_from([1, 3]), st.sampled_from([16, 64, 96]),
           st.sampled_from([8, 32]), st.sampled_from([jnp.float32, jnp.bfloat16]))
    @settings(**SETTINGS)
    def test_impls_match_ref(self, b, s, d, dtype):
        key = jax.random.PRNGKey(s + d)
        log_a = -jax.random.uniform(key, (b, s, d), jnp.float32, 0.01, 3.0).astype(dtype)
        x = jax.random.normal(jax.random.fold_in(key, 1), (b, s, d), dtype)
        h0 = jax.random.normal(jax.random.fold_in(key, 2), (b, d), dtype)
        hr, hfr = rglru_scan(log_a, x, h0, impl="ref")
        tol = 3e-2 if dtype == jnp.bfloat16 else 1e-4
        hx, hfx = rglru_scan(log_a, x, h0, impl="xla")
        assert rel_err(hx, hr) < tol and rel_err(hfx, hfr) < tol
        hp, hfp = rglru_scan(log_a, x, h0, impl="interpret")
        assert rel_err(hp, hr) < tol and rel_err(hfp, hfr) < tol

    def test_strong_decay_stable(self):
        """No overflow/NaN with extreme decay values."""
        b, s, d = 1, 64, 16
        log_a = jnp.full((b, s, d), -30.0)
        x = jnp.ones((b, s, d))
        h0 = jnp.ones((b, d)) * 100
        for impl in ("ref", "xla", "interpret"):
            hs, hf = rglru_scan(log_a, x, h0, impl=impl)
            assert np.isfinite(np.asarray(hs)).all()


class TestWkv6:
    @given(st.sampled_from([1, 2]), st.sampled_from([2, 4]),
           st.sampled_from([16, 48, 64]), st.sampled_from([8, 16]))
    @settings(**SETTINGS)
    def test_impls_match_ref(self, b, h, s, k_dim):
        key = jax.random.PRNGKey(s * 7 + h)
        mk = lambda i, shape, scale=0.5: jax.random.normal(jax.random.fold_in(key, i), shape) * scale
        r = mk(0, (b, h, s, k_dim))
        k = mk(1, (b, h, s, k_dim))
        v = mk(2, (b, h, s, k_dim))
        lw = -jax.random.uniform(jax.random.fold_in(key, 3), (b, h, s, k_dim), minval=0.01, maxval=4.0)
        u = mk(4, (h, k_dim), 0.3)
        s0 = mk(5, (b, h, k_dim, k_dim), 0.1)
        o_ref, s_ref = wkv6(r, k, v, lw, u, s0, impl="ref")
        o_x, s_x = wkv6(r, k, v, lw, u, s0, impl="xla", chunk=16)
        assert rel_err(o_x, o_ref) < 1e-3 and rel_err(s_x, s_ref) < 1e-3
        o_p, s_p = wkv6(r, k, v, lw, u, s0, impl="interpret", chunk=16)
        assert rel_err(o_p, o_ref) < 1e-3 and rel_err(s_p, s_ref) < 1e-3

    def test_extreme_decay_no_overflow(self):
        """The chunked form must not overflow even with huge decay."""
        b, h, s, kd = 1, 1, 32, 8
        key = jax.random.PRNGKey(0)
        r = jax.random.normal(key, (b, h, s, kd))
        k = jax.random.normal(jax.random.fold_in(key, 1), (b, h, s, kd))
        v = jax.random.normal(jax.random.fold_in(key, 2), (b, h, s, kd))
        lw = jnp.full((b, h, s, kd), -50.0)   # exp(+50cum) would overflow naive factoring
        u = jnp.zeros((h, kd))
        s0 = jnp.zeros((b, h, kd, kd))
        for impl in ("xla", "interpret"):
            o, sf = wkv6(r, k, v, lw, u, s0, impl=impl, chunk=16)
            assert np.isfinite(np.asarray(o)).all()
            assert np.isfinite(np.asarray(sf)).all()

    def test_statefulness_chunk_boundary(self):
        """Splitting a sequence across two calls == one call (state carry)."""
        b, h, s, kd = 1, 2, 32, 8
        key = jax.random.PRNGKey(9)
        mk = lambda i, shape: jax.random.normal(jax.random.fold_in(key, i), shape) * 0.5
        r, k, v = mk(0, (b, h, s, kd)), mk(1, (b, h, s, kd)), mk(2, (b, h, s, kd))
        lw = -jax.random.uniform(jax.random.fold_in(key, 3), (b, h, s, kd), minval=0.1, maxval=2.0)
        u = mk(4, (h, kd))
        s0 = jnp.zeros((b, h, kd, kd))
        o_full, s_full = wkv6(r, k, v, lw, u, s0, impl="xla", chunk=8)
        o1, s1 = wkv6(r[:, :, :16], k[:, :, :16], v[:, :, :16], lw[:, :, :16], u, s0, impl="xla", chunk=8)
        o2, s2 = wkv6(r[:, :, 16:], k[:, :, 16:], v[:, :, 16:], lw[:, :, 16:], u, s1, impl="xla", chunk=8)
        assert rel_err(np.concatenate([o1, o2], axis=2), o_full) < 1e-4
        assert rel_err(s2, s_full) < 1e-4


class TestOddLengthParity:
    """Pallas kernels vs refs on odd (non-multiple-of-block) sequence
    lengths: the padding/masking path must be exact in both dtypes."""

    @given(st.sampled_from([33, 40, 72, 100]),
           st.sampled_from([jnp.float32, jnp.bfloat16]),
           st.booleans())
    @settings(**SETTINGS)
    def test_flash_attention_odd_seq(self, s, dtype, causal):
        key = jax.random.PRNGKey(s)
        q = jax.random.normal(key, (1, 2, s, 16), dtype)
        k = jax.random.normal(jax.random.fold_in(key, 1), (1, 1, s, 16), dtype)
        v = jax.random.normal(jax.random.fold_in(key, 2), (1, 1, s, 16), dtype)
        ref = flash_attention(q, k, v, causal=causal, impl="ref")
        out = flash_attention(q, k, v, causal=causal, impl="interpret",
                              block_q=32, block_k=32)
        tol = 2e-2 if dtype == jnp.bfloat16 else 1e-4
        assert rel_err(out, ref) < tol

    def test_flash_attention_odd_seq_with_window_and_offset(self):
        key = jax.random.PRNGKey(7)
        q = jax.random.normal(key, (1, 2, 17, 16))
        k = jax.random.normal(jax.random.fold_in(key, 1), (1, 2, 50, 16))
        v = jax.random.normal(jax.random.fold_in(key, 2), (1, 2, 50, 16))
        for kwargs in ({"q_offset": 33}, {"window": 24, "q_offset": 33}):
            ref = flash_attention(q, k, v, causal=True, impl="ref", **kwargs)
            out = flash_attention(q, k, v, causal=True, impl="interpret",
                                  block_q=16, block_k=16, **kwargs)
            assert rel_err(out, ref) < 1e-4

    def test_flash_attention_odd_kv_only(self):
        """kv padding must not leak into the softmax when sq != skv."""
        key = jax.random.PRNGKey(11)
        q = jax.random.normal(key, (2, 2, 32, 16))
        k = jax.random.normal(jax.random.fold_in(key, 1), (2, 2, 45, 16))
        v = jax.random.normal(jax.random.fold_in(key, 2), (2, 2, 45, 16))
        ref = flash_attention(q, k, v, causal=False, impl="ref")
        out = flash_attention(q, k, v, causal=False, impl="interpret",
                              block_q=16, block_k=16)
        assert rel_err(out, ref) < 1e-4

    @given(st.sampled_from([(3, 5, 48), (7, 40), (13, 33)]),
           st.sampled_from([jnp.float32, jnp.bfloat16]))
    @settings(**SETTINGS)
    def test_rmsnorm_odd_rows(self, shape, dtype):
        from repro.kernels.rmsnorm.kernel import rmsnorm_pallas

        key = jax.random.PRNGKey(shape[-1])
        x = jax.random.normal(key, shape, dtype)
        w = jax.random.normal(jax.random.fold_in(key, 1), (shape[-1],), dtype) * 0.1
        ref = rmsnorm(x, w, impl="ref")
        # block_rows=4 forces row padding for every odd row count here
        out = rmsnorm_pallas(x, w, block_rows=4, interpret=True)
        tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
        assert rel_err(out, ref) < tol


class TestRmsnorm:
    @given(st.sampled_from([(4, 32), (2, 3, 64), (1, 128)]),
           st.sampled_from([jnp.float32, jnp.bfloat16]),
           st.sampled_from([0.0, 1.0]))
    @settings(**SETTINGS)
    def test_interpret_matches_ref(self, shape, dtype, offset):
        key = jax.random.PRNGKey(shape[-1])
        x = jax.random.normal(key, shape, dtype)
        w = jax.random.normal(jax.random.fold_in(key, 1), (shape[-1],), dtype) * 0.1
        ref = rmsnorm(x, w, scale_offset=offset, impl="ref")
        out = rmsnorm(x, w, scale_offset=offset, impl="interpret")
        tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
        assert rel_err(out, ref) < tol

    def test_unit_variance_property(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (128, 64)) * 7 + 3
        y = rmsnorm(x, jnp.ones((64,)), impl="ref")
        ms = np.mean(np.asarray(y) ** 2, axis=-1)
        assert np.allclose(ms, np.asarray((x / np.sqrt((np.asarray(x)**2).mean(-1, keepdims=True)))**2).mean(-1), atol=1e-3)
