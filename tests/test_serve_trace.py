"""What ServingEngine records of itself: its ``serve.*`` spans in a profiler
trace, its prefill counters, its one transfer per decode step, and the
name of its compiled step. On the CPU, at a smoke width."""

from __future__ import annotations

import glob
import os

import jax
import numpy as np
import pytest
from jax._src.array import ArrayImpl

from repro.configs import smoke_config
from repro.kernels.decode_attention import kernel as kernel_mod
from repro.models import build_model
from repro.models.scopes import DECODE_SCOPES
from repro.serve import Request, ServingEngine
from repro.serve import engine as engine_mod

PROMPTS = (5, 3, 7)          # prompt lengths: the engine feeds P - 1 tokens of each
NEW = (4, 6, 3)


@pytest.fixture(scope="module")
def model_params():
    cfg = smoke_config("phi4-mini-3.8b").with_(dtype="float32")
    m = build_model(cfg)
    return m, m.init(jax.random.PRNGKey(0))


def engine(model_params, **kw):
    m, params = model_params
    eng = ServingEngine(m, params, n_slots=2, max_len=48, **kw)
    rng = np.random.default_rng(0)
    for rid, (p, n) in enumerate(zip(PROMPTS, NEW)):
        eng.submit(Request(request_id=rid, prompt=rng.integers(1, 100, p).astype(np.int32),
                           max_new_tokens=n))
    return eng


def host_spans(log_dir):
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                           for e in line.events if e.name.startswith("serve."))
    return sorted(out, key=lambda e: (e[1], -e[2]))


def inside(child, parents):
    return any(p[1] <= child[1] and child[2] <= p[2] for p in parents)


def test_step_span_tree(model_params, tmp_path):
    hooks = []
    eng = engine(model_params, on_token=lambda r, t: hooks.append(t) and False,
                 on_finish=lambda r: hooks.append(None))
    with jax.profiler.trace(str(tmp_path)):
        stats = eng.run_until_drained()
    spans = host_spans(str(tmp_path))
    named = {n: [s for s in spans if s[0] == n] for n in
             ("serve.admit", "serve.prefill", "serve.dispatch", "serve.device_get",
              "serve.bookkeeping", "serve.hooks")}
    assert len(named["serve.prefill"]) == len(PROMPTS) == stats.admissions
    assert 1 <= len(named["serve.admit"]) <= len(PROMPTS)
    assert all(inside(s, named["serve.admit"]) for s in named["serve.prefill"])
    for name in ("serve.dispatch", "serve.device_get", "serve.bookkeeping"):
        assert len(named[name]) == stats.steps
    assert len(named["serve.hooks"]) == len(hooks) == stats.tokens_generated + len(PROMPTS)
    assert all(inside(s, named["serve.bookkeeping"]) for s in named["serve.hooks"])
    # within one step: dispatch, then the transfer, then the bookkeeping
    for d, g, b in zip(named["serve.dispatch"], named["serve.device_get"], named["serve.bookkeeping"]):
        assert d[2] <= g[1] and g[2] <= b[1]


def test_prefill_counters(model_params):
    stats = engine(model_params).run_until_drained()
    assert stats.admissions == len(PROMPTS)
    assert stats.prefill_tokens == stats.prefill_calls == sum(p - 1 for p in PROMPTS)
    assert stats.tokens_generated == sum(NEW)


def test_one_transfer_per_decode_step(model_params, monkeypatch):
    eng = engine(model_params)
    eng.step()                                           # fills both slots
    calls, in_get = [], []
    real_get, real_value = jax.device_get, ArrayImpl._value

    def device_get(x):
        calls.append("device_get")
        in_get.append(True)
        try:
            return real_get(x)
        finally:
            in_get.pop()

    def value(self):
        if not in_get:
            calls.append("implicit")
        return real_value.fget(self)

    class Numpy:                                         # the engine's ``np``, counting
        def __getattr__(self, name):
            return getattr(np, name)

        def asarray(self, a, *args, **kw):
            if isinstance(a, jax.Array) and not in_get:
                calls.append("implicit")
            return np.asarray(a, *args, **kw)

        array = asarray

    monkeypatch.setattr(jax, "device_get", device_get)
    monkeypatch.setattr(ArrayImpl, "_value", property(value))        # int(), bool(), .item()
    monkeypatch.setattr(engine_mod, "np", Numpy())
    steps = 0
    while all(r is not None for r in eng._slots):       # decode steps, no admission
        eng.step()
        steps += 1
    assert steps >= 2 and calls == ["device_get"] * steps


def test_compiled_step_is_named_jit_serve_step(model_params):
    text = engine(model_params).compile().as_text()
    assert text.startswith("HloModule jit_serve_step,")
    for scope in ("embed", "layers", "attn", "kv_cache.update", "mlp", "unembed", "sample"):
        assert f"/{scope}/" in text
    assert DECODE_SCOPES == ("embed", "layers", "attn", "kv_cache.update", "mlp", "unembed", "sample")


@pytest.mark.parametrize("arch", ["phi4-mini-3.8b", "mellum2-12b-a2.5b-ep4"], ids=["dense", "window"])
def test_kv_block_counters_match_a_hand_count(arch, monkeypatch):
    """``kv_blocks_read`` and ``kv_blocks_held`` over the decode steps, with
    the kernel's blocks at 8 positions (any block reaches the target).

    Two slots of a 32-position cache, 4 blocks each. Slot 0 holds a prompt
    of 5 and decodes 6 tokens, at valid lengths 5..10: 1, 1, 1, 1, 2, 2
    blocks. Slot 1 holds a prompt of 12 and decodes 3, at 12..14: 2 blocks
    each; then it idles while slot 0 finishes, its stale lengths counting
    on to 15, 16, 17: 2, 2, 3 blocks. A full layer reads 8 + 6 + 7 = 21 of
    6 steps x 2 slots x 4 = 48 blocks. A ring of 16 (2 blocks) reads at
    min(valid, 16): 8 + 6 + (2 + 2 + 2) = 20 of 6 x 2 x 2 = 24 blocks.
    phi4-mini's smoke model has 2 full layers; Mellum2's 2 full and 6
    sliding."""
    monkeypatch.setattr(kernel_mod, "TARGET_BLOCK_BYTES", 1)
    cfg = smoke_config(arch).with_(dtype="float32")
    m = build_model(cfg)
    eng = ServingEngine(m, m.init(jax.random.PRNGKey(0)), n_slots=2, max_len=32)
    for rid, (p, n) in enumerate(((5, 6), (12, 3))):
        eng.submit(Request(request_id=rid, prompt=np.arange(1, p + 1, dtype=np.int32),
                           max_new_tokens=n))
    stats = eng.run_until_drained()
    assert stats.steps == 6
    n_full, n_ring = (2, 0) if arch == "phi4-mini-3.8b" else (2, 6)
    assert cfg.n_layers == n_full + n_ring
    assert stats.kv_blocks_read == 21 * n_full + 20 * n_ring
    assert stats.kv_blocks_held == 48 * n_full + 24 * n_ring
