"""Per-kernel validation: Pallas (interpret=True) and XLA paths vs. the
pure-jnp oracle, swept over shapes/dtypes with hypothesis."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp
pytest.importorskip("hypothesis")  # optional dep: pip install -e .[test]
from hypothesis import given, settings, strategies as st, HealthCheck

from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.decode_attention.kernel import block_for, last_filled_block
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


def _valid_lengths(bk, s):
    """Valid lengths at each edge of a block: 1, bk - 1, bk, bk + 1, S."""
    return jnp.asarray([1, bk - 1, bk, bk + 1, s], jnp.int32)


class TestDecodeAttention:
    """The kernel fetches and computes only each slot's filled blocks
    (``kernel.last_filled_block``); at every valid length it equals the masked
    full read of the reference."""

    @pytest.mark.parametrize("group,kvh", [(1, 1), (2, 2), (3, 8), (8, 4)])
    @given(st.sampled_from([jnp.float32, jnp.bfloat16]))
    @settings(**SETTINGS)
    def test_interpret_matches_ref(self, group, kvh, dtype):
        """The cells' GQA shapes (group 3 of 8 kv heads, 8 of 4) at small S."""
        s, d, bk = 64, 16, 16
        h = group * kvh
        lengths = _valid_lengths(bk, s)
        key = jax.random.PRNGKey(group * 10 + kvh)
        q = jax.random.normal(key, (len(lengths), h, d), dtype)
        k = jax.random.normal(jax.random.fold_in(key, 1), (len(lengths), kvh, s, d), dtype)
        v = jax.random.normal(jax.random.fold_in(key, 2), (len(lengths), kvh, s, d), dtype)
        ref = decode_attention(q, k, v, lengths, impl="ref")
        out = decode_attention(q, k, v, lengths, impl="interpret", block_k=bk)
        tol = 2e-2 if dtype == jnp.bfloat16 else 1e-4
        assert rel_err(out, ref) < tol

    def test_windowed(self):
        """A sliding-window layer's ring of W read at ``min(n + 1, W)``, as
        the decode step reads it, equals the reference over the last W
        positions of the unrolled sequence: position p lives in slot p % W."""
        h, kvh, w, d, bk = 4, 2, 32, 16, 16
        lengths = jnp.asarray([1, 15, 16, 17, 31, 32, 40, 63], jnp.int32)   # valid entries n + 1
        b = len(lengths)
        key = jax.random.PRNGKey(1)
        q = jax.random.normal(key, (b, h, d))
        seq_k = jax.random.normal(jax.random.fold_in(key, 1), (b, kvh, 64, d))
        seq_v = jax.random.normal(jax.random.fold_in(key, 2), (b, kvh, 64, d))
        ring_k, ring_v, last_k, last_v = [], [], [], []
        for i, n in enumerate(np.asarray(lengths)):
            kept = np.arange(max(n - w, 0), n)
            slots = np.zeros(w, np.int64)
            slots[kept % w] = kept
            ring_k.append(seq_k[i][:, slots])
            ring_v.append(seq_v[i][:, slots])
            last = np.arange(w) + max(n - w, 0)
            last_k.append(seq_k[i][:, last])
            last_v.append(seq_v[i][:, last])
        valid = jnp.minimum(lengths, w)
        ref = decode_attention(q, jnp.stack(last_k), jnp.stack(last_v), valid, impl="ref")
        out = decode_attention(q, jnp.stack(ring_k), jnp.stack(ring_v), valid, impl="interpret",
                               block_k=bk)
        assert rel_err(out, ref) < 1e-4

    @pytest.mark.parametrize("layer", [0, 2, 3])
    @pytest.mark.parametrize("impl", ["interpret", "ref"])
    def test_layer_index_reads_its_layer_of_the_stack(self, layer, impl):
        """With a layer index into a stacked (L, B, KV, S, d) cache, each
        path reads that layer as if handed its slice: a full stack, and a
        stack of rings of W read at ``min(n + 1, W)`` as the decode step
        reads them."""
        n_layers, h, kvh, s, d, bk, w = 4, 4, 2, 64, 16, 16, 32
        key = jax.random.PRNGKey(layer)
        lengths = _valid_lengths(bk, s)
        b = len(lengths)
        q = jax.random.normal(key, (b, h, d))
        for cache_len, valid in ((s, lengths), (w, jnp.minimum(lengths + 1, w))):
            k = jax.random.normal(jax.random.fold_in(key, 1), (n_layers, b, kvh, cache_len, d))
            v = jax.random.normal(jax.random.fold_in(key, 2), (n_layers, b, kvh, cache_len, d))
            ref = decode_attention(q, k[layer], v[layer], valid, impl="ref")
            out = jax.jit(lambda l: decode_attention(q, k, v, valid, l, impl=impl,
                                                     block_k=bk))(jnp.int32(layer))
            assert rel_err(out, ref) < 1e-4

    @pytest.mark.parametrize("cache_len", [64, 32], ids=["full", "ring"])
    def test_blocks_outside_the_filled_ones_are_neither_read_nor_computed(self, cache_len):
        """NaN in every whole block past each slot's last filled block, of a
        full cache and of a ring read at ``min(n + 1, W)``: the output is
        finite and equals the reference over the finite cache."""
        h, kvh, d, bk = 6, 2, 16, 16
        lengths = jnp.minimum(_valid_lengths(bk, 64), cache_len)
        b = len(lengths)
        key = jax.random.PRNGKey(7)
        q = jax.random.normal(key, (b, h, d))
        k = jax.random.normal(jax.random.fold_in(key, 1), (b, kvh, cache_len, d))
        v = jax.random.normal(jax.random.fold_in(key, 2), (b, kvh, cache_len, d))
        last = last_filled_block(lengths, bk, cache_len // bk)
        outside = jnp.arange(cache_len)[None] // bk > last[:, None]
        assert outside.any()
        nan = outside[:, None, :, None]
        kn, vn = jnp.where(nan, jnp.nan, k), jnp.where(nan, jnp.nan, v)
        ref = decode_attention(q, k, v, lengths, impl="ref")
        out = decode_attention(q, kn, vn, lengths, impl="interpret", block_k=bk)
        assert np.isfinite(np.asarray(out)).all()
        assert rel_err(out, ref) < 1e-4

    # (kv heads, group, S): phi4-mini, yi-6b, Mellum2's full layers and its
    # rings, each with the grid steps a call took with a per-head 1024 block
    @pytest.mark.parametrize("kvh,group,s,old_steps", [
        (8, 3, 2048, 128), (4, 8, 2048, 64), (4, 8, 2048, 64), (4, 8, 1024, 32)],
        ids=["phi4-mini", "yi-6b", "mellum2-full", "mellum2-ring"])
    def test_block_rule_fits_vmem_and_keeps_the_grid(self, kvh, group, s, old_steps):
        """The block from the shapes at d 128 in bf16: k and v
        double-buffered fit the default scoped VMEM (16 MiB), it tiles the
        cache, and a call of 8 slots takes no more grid steps than before."""
        bk = block_for(s, kvh, 128, 2)
        assert s % bk == 0 and bk % 8 == 0
        assert 2 * 2 * kvh * bk * 128 * 2 <= 16 * 1024 * 1024
        assert 8 * (s // bk) <= old_steps


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
