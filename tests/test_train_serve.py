"""Training + serving substrate tests: optimizers, accumulation equivalence,
checkpointing, gradient compression (hypothesis properties), data pipeline
determinism, serving engine continuous batching."""

import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
pytest.importorskip("hypothesis")  # optional dep: pip install -e .[test]
from hypothesis import given, settings, strategies as st, HealthCheck

from repro.configs import smoke_config
from repro.models import build_model
from repro.serve import Request, ServingEngine
from repro.train import (
    CheckpointManager,
    CompressedSync,
    DataConfig,
    OptimizerConfig,
    PrefetchLoader,
    SyntheticLM,
    compress_tree,
    decompress_tree,
    init_train_state,
    make_train_step,
    payload_bytes,
    quantize_int8,
    dequantize_int8,
)

HSET = dict(max_examples=10, deadline=None,
            suppress_health_check=[HealthCheck.too_slow])


@pytest.fixture(scope="module")
def small_model():
    cfg = smoke_config("yi-6b").with_(dtype="float32")
    return cfg, build_model(cfg)


class TestOptimizers:
    @pytest.mark.parametrize("name,state_dtype", [
        ("adamw", "float32"), ("adamw", "bfloat16"),
        ("adafactor", "float32"), ("adafactor", "bfloat16"),
    ])
    def test_converges(self, small_model, name, state_dtype):
        cfg, m = small_model
        oc = OptimizerConfig(name=name, lr=3e-3, warmup_steps=2, total_steps=50,
                             state_dtype=state_dtype)
        params, opt = init_train_state(m, oc, jax.random.PRNGKey(0))
        step = jax.jit(make_train_step(m, oc))
        data = SyntheticLM(cfg, seq_len=16, batch=8)
        first = last = None
        for s in range(12):
            batch = {k: jnp.asarray(v) for k, v in data.batch_at(s).items()}
            params, opt, metrics = step(params, opt, batch)
            if s == 0:
                first = float(metrics["loss"])
        last = float(metrics["loss"])
        assert np.isfinite(last) and last < first

    def test_lr_schedule_shape(self):
        from repro.train.optimizer import lr_at
        oc = OptimizerConfig(lr=1.0, warmup_steps=10, total_steps=100, min_lr_frac=0.1)
        lrs = [float(lr_at(oc, jnp.asarray(s))) for s in [0, 5, 10, 55, 100]]
        assert lrs[0] < lrs[1] < lrs[2]          # warmup
        assert lrs[2] == pytest.approx(1.0)      # peak
        assert lrs[4] == pytest.approx(0.1, abs=0.02)   # floor

    def test_grad_accum_equivalent(self, small_model):
        """grad_accum=1 vs 4 produce (nearly) identical updates."""
        cfg, _ = small_model
        data = SyntheticLM(cfg, seq_len=16, batch=8)
        batch = {k: jnp.asarray(v) for k, v in data.batch_at(0).items()}
        outs = {}
        for accum in (1, 4):
            c = cfg.with_(grad_accum=accum)
            m = build_model(c)
            oc = OptimizerConfig(lr=1e-3, warmup_steps=0, total_steps=10)
            params, opt = init_train_state(m, oc, jax.random.PRNGKey(0))
            step = jax.jit(make_train_step(m, oc))
            new_p, _, metrics = step(params, opt, batch)
            outs[accum] = (new_p, float(metrics["loss"]))
        p1 = jax.tree_util.tree_leaves(outs[1][0])
        p4 = jax.tree_util.tree_leaves(outs[4][0])
        max_err = max(float(jnp.max(jnp.abs(a - b))) for a, b in zip(p1, p4))
        assert max_err < 1e-4
        assert outs[1][1] == pytest.approx(outs[4][1], rel=1e-4)


class TestCheckpoint:
    def test_atomic_and_gc(self, tmp_path, small_model):
        cfg, m = small_model
        params = m.init(jax.random.PRNGKey(0))
        ck = CheckpointManager(str(tmp_path), keep=2)
        for s in (1, 2, 3, 4):
            ck.save(s, {"params": params}, extra={"s": s})
        assert ck.all_steps() == [3, 4]           # gc keeps last 2
        restored, extra = ck.restore(4, {"params": params})
        assert extra == {"s": 4}
        for a, b in zip(jax.tree_util.tree_leaves(restored),
                        jax.tree_util.tree_leaves({"params": params})):
            assert np.allclose(a, b)

    def test_async_overlap(self, tmp_path, small_model):
        cfg, m = small_model
        params = m.init(jax.random.PRNGKey(0))
        ck = CheckpointManager(str(tmp_path))
        t0 = time.monotonic()
        ck.save_async(1, {"params": params})
        submit_time = time.monotonic() - t0
        ck.wait()
        assert ck.latest_step() == 1
        assert submit_time < 5.0  # snapshot is cheap; write happens in background

    def test_crash_leaves_no_partial(self, tmp_path, small_model):
        """A .tmp dir from a crashed writer must not be visible as a step."""
        cfg, m = small_model
        ck = CheckpointManager(str(tmp_path))
        os.makedirs(os.path.join(str(tmp_path), "step_00000009.tmp"))
        assert ck.latest_step() is None


class TestGradCompression:
    @given(st.integers(1, 5), st.floats(1e-4, 10.0))
    @settings(**HSET)
    def test_quantize_bounded_error(self, rows, scale):
        rng = np.random.default_rng(rows)
        x = jnp.asarray(rng.standard_normal((rows, 64)) * scale)
        q, s = quantize_int8(x)
        deq = dequantize_int8(q, s)
        # per-row error bounded by scale/2 = max|x|/254
        bound = np.asarray(jnp.max(jnp.abs(x), axis=-1, keepdims=True)) / 254 + 1e-9
        assert (np.abs(np.asarray(deq - x)) <= bound * 1.01).all()

    def test_error_feedback_unbiased_over_time(self):
        """Sum of dequantized payloads + final error == sum of raw grads."""
        rng = np.random.default_rng(0)
        err = None
        total_raw = np.zeros((8, 16))
        total_sent = np.zeros((8, 16))
        for step in range(20):
            g = {"w": jnp.asarray(rng.standard_normal((8, 16)) * 1e-3)}
            payload, err = compress_tree(g, err)
            total_raw += np.asarray(g["w"])
            total_sent += np.asarray(decompress_tree(payload)["w"])
        residual = np.asarray(jax.tree_util.tree_leaves(err)[0])
        assert np.allclose(total_sent + residual, total_raw, atol=1e-5)

    def test_sync_compression_ratio(self):
        g = {"w": jnp.asarray(np.random.default_rng(1).standard_normal((64, 128)))}
        sync = CompressedSync(n_pods=2)
        sync.contribute(0, g)
        sync.contribute(1, g)
        avg = sync.reduce()
        assert sync.bytes_uncompressed / sync.bytes_sent > 3.5
        rel = float(jnp.max(jnp.abs(avg["w"] - g["w"])) / jnp.max(jnp.abs(g["w"])))
        assert rel < 2e-2


class TestData:
    def test_deterministic_across_restart(self):
        cfg = smoke_config("gemma-2b")
        d1 = SyntheticLM(cfg, 16, 4)
        d2 = SyntheticLM(cfg, 16, 4)
        b1, b2 = d1.batch_at(7), d2.batch_at(7)
        assert np.array_equal(b1["tokens"], b2["tokens"])

    def test_labels_are_shifted_tokens(self):
        cfg = smoke_config("gemma-2b")
        b = SyntheticLM(cfg, 16, 2).batch_at(0)
        assert np.array_equal(b["tokens"][:, 1:], b["labels"][:, :-1])

    def test_learnable_structure(self):
        """Markov component: following token is predictable > chance."""
        cfg = smoke_config("gemma-2b")
        d = SyntheticLM(cfg, 256, 4)
        b = d.batch_at(0)
        pred = d.next_pref[b["tokens"]]
        hit = (pred == b["labels"]).mean()
        assert hit > 0.5

    def test_prefetch_matches_direct(self):
        cfg = smoke_config("gemma-2b")
        src = SyntheticLM(cfg, 8, 2)
        loader = PrefetchLoader(src, start_step=0)
        step, batch = next(loader)
        assert step == 0
        assert np.array_equal(batch["tokens"], src.batch_at(0)["tokens"])
        loader.close()


class TestServingEngine:
    def test_continuous_batching_drains(self):
        cfg = smoke_config("gemma-2b").with_(dtype="float32")
        m = build_model(cfg)
        params = m.init(jax.random.PRNGKey(0))
        eng = ServingEngine(m, params, n_slots=2, max_len=48)
        for i in range(5):
            eng.submit(Request(request_id=i, prompt=np.arange(1, 4, dtype=np.int32),
                               max_new_tokens=4))
        stats = eng.run_until_drained()
        assert stats.requests_finished == 5
        assert stats.tokens_generated == 20

    def test_steering_hook_cancels(self):
        cfg = smoke_config("gemma-2b").with_(dtype="float32")
        m = build_model(cfg)
        params = m.init(jax.random.PRNGKey(0))
        eng = ServingEngine(m, params, n_slots=2, max_len=48,
                            on_token=lambda req, tok: len(req.generated) >= 1)
        eng.submit(Request(request_id=0, prompt=np.asarray([1, 2], np.int32),
                           max_new_tokens=10))
        stats = eng.run_until_drained()
        assert stats.requests_cancelled == 1
        assert stats.tokens_generated == 1

    def test_prefix_isolation_between_slots(self):
        """Two different prompts decoded concurrently give the same tokens
        as decoded alone (slot isolation)."""
        cfg = smoke_config("gemma-2b").with_(dtype="float32")
        m = build_model(cfg)
        params = m.init(jax.random.PRNGKey(0))

        def gen(prompts):
            eng = ServingEngine(m, params, n_slots=len(prompts), max_len=48)
            for i, p in enumerate(prompts):
                eng.submit(Request(request_id=i, prompt=p, max_new_tokens=5))
            reqs = {}
            eng.on_finish = lambda r: reqs.setdefault(r.request_id, r.generated)
            eng.run_until_drained()
            return reqs

        p0 = np.asarray([5, 6, 7], np.int32)
        p1 = np.asarray([9, 10], np.int32)
        together = gen([p0, p1])
        alone0 = gen([p0])
        alone1 = gen([p1])
        assert together[0] == alone0[0]
        assert together[1] == alone1[0 if 0 in alone1 else 1] or together[1] == list(alone1.values())[0]

    def test_compiled_engine_matches_jitted(self):
        """engine.compile() swaps in the AOT executable without changing
        what is generated."""
        cfg = smoke_config("phi4-mini-3.8b").with_(dtype="float32")
        m = build_model(cfg)
        params = m.init(jax.random.PRNGKey(0))
        prompt = np.asarray([3, 1, 4, 1, 5], np.int32)

        def gen(compile_first):
            eng = ServingEngine(m, params, n_slots=2, max_len=32)
            if compile_first:
                eng.compile()
            out = []
            eng.on_finish = lambda r: out.append(r.generated)
            eng.submit(Request(request_id=0, prompt=prompt, max_new_tokens=6))
            stats = eng.run_until_drained()
            assert stats.nonfinite_steps == 0
            return out

        assert gen(True) == gen(False)


class TestServeLauncher:
    def test_run_reports_served_tokens(self):
        from repro.launch.serve import run

        out = run("phi4-mini-3.8b", n_requests=3, n_slots=2, max_new=4, steer=False)
        assert out["config"] == "phi4-mini-3.8b"
        assert out["requests"] == 3 and out["tokens"] == 12
        assert out["out_of_vocab_tokens"] == 0
        assert out["nonfinite_logit_steps"] == 0
        assert out["compile_s"] > 0
        assert out["kernels"] == []          # CPU: the reference paths

    def test_run_rejects_prompts_that_overflow_the_cache(self):
        from repro.launch.serve import run

        with pytest.raises(ValueError, match="overflow"):
            run("phi4-mini-3.8b", n_requests=1, max_new=100)
