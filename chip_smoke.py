"""Quickest proof that the system runs on one TPU chip.

Run from the repository root on a machine with one TPU:

    python chip_smoke.py

Everything runs in this one process (a chip belongs to one process), in
these phases; any failure exits non-zero and prints no result line:

  (a) device  — exits 1 unless JAX's first device is a TPU;
  (b) compile — phi4-mini-3.8b at its published widths in bf16, random
                weights from a seed, built and served through
                ``repro.launch.serve.run`` (build_model -> ServingEngine).
                The serve step is compiled before serving; its compile
                time is printed as set-up time;
  (c) kernels — the compiled serve step must hold the Pallas
                decode-attention and rmsnorm kernels, so a step that
                quietly took the reference path fails the run;
  (d) parity  — the Pallas decode kernel against the jnp reference at
                the served shapes, within DECODE_ATOL + DECODE_RTOL*|ref|;
  (e) serve   — 8 requests, 16-64 prompt tokens, 32 new tokens, 4 slots:
                all finish, every token is in the vocabulary, every
                step's logits are finite;
  (f) steer   — one surrogate-steered campaign of 48 results whose
                DeepEnsemble retrains at least once with its parameters
                on the TPU.

The last line of standard output is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.
The JAX compilation cache goes where ``JAX_COMPILATION_CACHE_DIR`` says,
else to ``.jax_cache/`` in the checkout.
"""

from __future__ import annotations

import json
import sys
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent

ARCH = "phi4-mini-3.8b"
N_REQUESTS, N_SLOTS, MAX_NEW = 8, 4, 32
CAMPAIGN_BUDGET, N_CANDIDATES = 48, 512
# bf16 inputs; the reference rounds probabilities to bf16 before the PV
# product while the kernel keeps them in f32, and both round the output
# to bf16 (relative step 2^-8).
DECODE_ATOL, DECODE_RTOL = 2e-2, 2e-2


def log(msg: str) -> None:
    print(msg, flush=True)


def phase_device():
    import jax

    devices = jax.devices()
    dev = devices[0]
    log(f"[a] device: platform={dev.platform} kind={dev.device_kind} count={len(devices)}")
    if dev.platform != "tpu":
        raise RuntimeError(f"no TPU: JAX's first device is {dev.platform!r}")
    return dev, len(devices)


def phase_serve() -> dict:
    from repro.launch.serve import run

    out = run(ARCH, N_REQUESTS, N_SLOTS, MAX_NEW, steer=False, published=True)
    log(f"[b] config={out['config']} dtype={out['dtype']} params={out['params']}")
    log(f"[b] peak_bytes_in_use after init: {out['peak_bytes_after_init']}")
    log(f"[b] serve step compile (set-up): {out['compile_s']:.3f} s")
    log(f"[c] Pallas kernels in the compiled serve step: {out['kernels']}")
    missing = {"decode_attention", "rmsnorm"} - set(out["kernels"])
    if missing:
        raise RuntimeError(f"serve step lacks Pallas kernels {sorted(missing)}")
    log(f"[e] requests={out['requests']} tokens={out['tokens']} wall_s={out['wall_s']:.3f} "
        f"tokens_per_s={out['tokens_per_s']:.1f} median_ttft_s={out['median_ttft_s']:.4f}")
    log(f"[e] out_of_vocab_tokens={out['out_of_vocab_tokens']} "
        f"nonfinite_logit_steps={out['nonfinite_logit_steps']}")
    if out["requests"] != N_REQUESTS or out["tokens"] != N_REQUESTS * MAX_NEW:
        raise RuntimeError(f"served {out['requests']} requests / {out['tokens']} tokens, "
                           f"expected {N_REQUESTS} / {N_REQUESTS * MAX_NEW}")
    if out["out_of_vocab_tokens"] or out["nonfinite_logit_steps"]:
        raise RuntimeError("serving produced out-of-vocab tokens or non-finite logits")
    log(f"[e] peak_bytes_in_use after serving: {out['peak_bytes_in_use']}")
    return out


def phase_decode_parity() -> float:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.kernels import decode_attention
    from repro.launch.serve import PUBLISHED_MAX_LEN as MAX_LEN

    cfg = get_config(ARCH)
    b, h, kv, d = N_SLOTS, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    kq, kk, kv_ = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(kq, (b, h, d), jnp.bfloat16)
    k = jax.random.normal(kk, (b, kv, MAX_LEN, d), jnp.bfloat16)
    v = jax.random.normal(kv_, (b, kv, MAX_LEN, d), jnp.bfloat16)
    lengths = jnp.array([1, 700, 1500, MAX_LEN], jnp.int32)[:b]
    got = jax.jit(lambda *a: decode_attention(*a, impl="pallas"))(q, k, v, lengths)
    want = jax.jit(lambda *a: decode_attention(*a, impl="ref"))(q, k, v, lengths)
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    err = np.abs(got - want)
    worst = float((err - DECODE_RTOL * np.abs(want)).max())
    log(f"[d] decode_attention pallas vs ref: B={b} H={h} KV={kv} S={MAX_LEN} d={d} bf16, "
        f"max_abs_err={float(err.max()):.5f} (tolerance {DECODE_ATOL} + {DECODE_RTOL}*|ref|)")
    if not np.isfinite(got).all() or worst > DECODE_ATOL:
        raise RuntimeError("Pallas decode attention disagrees with the reference")
    return float(err.max())


def phase_campaign() -> dict:
    import jax
    import numpy as np

    from repro.surrogate import (DeepEnsemble, make_policy, make_scenario,
                                 run_active_campaign, warmup_jit)
    from repro.surrogate.thinker import campaign_ensemble_config

    scenario = make_scenario("quadratic", dim=4)
    ens_cfg = campaign_ensemble_config(CAMPAIGN_BUDGET)
    t0 = time.monotonic()
    warmup_jit(scenario.dim, ens_cfg, predict_rows=N_CANDIDATES)
    log(f"[f] ensemble fit/predict compile (set-up): {time.monotonic() - t0:.3f} s")
    ens = DeepEnsemble(scenario.dim, ens_cfg, seed=0)
    t0 = time.monotonic()
    out = run_active_campaign(scenario, make_policy("ucb"), budget=CAMPAIGN_BUDGET,
                              retrain_after=8, n_candidates=N_CANDIDATES, seed=0,
                              ensemble=ens, sim_sleep_s=0.005, timeout=600)
    wall = time.monotonic() - t0
    platforms = {dev.platform for leaf in jax.tree_util.tree_leaves(ens.params)
                 for dev in leaf.devices()}
    mean, std = ens.predict(scenario.sample(np.random.default_rng(1), 64))
    log(f"[f] campaign: results={out['n']} retrains={out['retrains']} hits={out['hits']} "
        f"best={out['best']:.4f} wall_s={wall:.3f} ensemble_on={sorted(platforms)}")
    if out["n"] < CAMPAIGN_BUDGET or out["retrains"] < 1 or ens.fit_count < 1:
        raise RuntimeError("campaign ended short or never retrained its surrogate")
    if platforms != {"tpu"}:
        raise RuntimeError(f"ensemble parameters live on {sorted(platforms)}, not the TPU")
    if not (np.isfinite(mean).all() and np.isfinite(std).all()):
        raise RuntimeError("surrogate predictions are not finite")
    return out


def main() -> int:
    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke: {ROOT} holds no src/repro; run it from a checkout",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    try:
        from repro.launch.compile_cache import use_compile_cache

        log(f"compile cache: {use_compile_cache()}")
        dev, count = phase_device()
        phase_serve()
        phase_decode_parity()
        phase_campaign()
        stats = dev.memory_stats() or {}
        log(f"peak_bytes_in_use at end: {stats.get('peak_bytes_in_use')}")
    except Exception:  # noqa: BLE001 - every failure ends the run non-zero
        traceback.print_exc()
        print("chip_smoke: FAILED", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
