"""Serving driver: continuous-batching engine + Colmena request steering.

A Thinker-side policy watches tokens as they stream (the paper's
multi-fidelity lesson: stop evaluating low-performing candidates early)
and cancels generations whose running score falls below a threshold.

By default the model is the architecture's reduced smoke config in
float32 (CPU-sized); ``--published`` builds the registry config at its
published widths in its own dtype (bf16), which needs an accelerator.

Usage:
  PYTHONPATH=src python -m repro.launch.serve --requests 16
  PYTHONPATH=src python -m repro.launch.serve --published --requests 8 --max-new 32
"""

from __future__ import annotations

import argparse
import json
import time
from typing import Optional

import jax
import numpy as np

from ..configs import get_config, smoke_config
from ..kernels import compiled_kernels
from ..models import build_model
from ..serve import Request, ServingEngine
from .compile_cache import use_compile_cache

# Per-slot cache length. The Pallas decode kernel streams the cache in
# 1024-token blocks, so a published-width cache is a multiple of 1024.
PUBLISHED_MAX_LEN, SMOKE_MAX_LEN = 2048, 128
PROMPT_LEN = (16, 64)        # prompt tokens per request, drawn uniformly


def _peak_bytes() -> Optional[int]:
    stats = jax.devices()[0].memory_stats()
    return stats.get("peak_bytes_in_use") if stats else None


def run(arch: str = "phi4-mini-3.8b", n_requests: int = 12, n_slots: int = 4,
        max_new: int = 16, steer: bool = True, *, published: bool = False) -> dict:
    """Build the model (random weights, seed 0), compile the serve step,
    and serve ``n_requests`` prompts of ``PROMPT_LEN`` tokens."""
    cfg = get_config(arch) if published else smoke_config(arch).with_(dtype="float32")
    max_len = PUBLISHED_MAX_LEN if published else SMOKE_MAX_LEN
    if PROMPT_LEN[1] + max_new > max_len:
        raise ValueError(f"prompts of {PROMPT_LEN[1]} + {max_new} new tokens overflow max_len {max_len}")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    jax.block_until_ready(params)
    peak_after_init = _peak_bytes()
    rng = np.random.default_rng(0)

    def on_token(req: Request, tok: int) -> bool:
        # steering policy: abandon degenerate generations (repeated token)
        if steer and len(req.generated) >= 4:
            if len(set(req.generated[-4:])) == 1:
                return True
        return False

    finished = []
    engine = ServingEngine(model, params, n_slots=n_slots, max_len=max_len,
                           on_token=on_token, on_finish=finished.append)
    t0 = time.monotonic()
    compiled = engine.compile()
    compile_s = time.monotonic() - t0

    t0 = time.monotonic()
    for i in range(n_requests):
        size = rng.integers(PROMPT_LEN[0], PROMPT_LEN[1] + 1)
        prompt = rng.integers(1, cfg.vocab_size, size=size).astype(np.int32)
        engine.submit(Request(request_id=i, prompt=prompt, max_new_tokens=max_new))
    stats = engine.run_until_drained()
    wall = time.monotonic() - t0
    ttft = [r.first_token_at - r.submitted_at for r in finished if r.first_token_at]
    generated = [t for r in finished for t in r.generated]
    dev = jax.devices()[0]
    return {
        "config": cfg.name,
        "dtype": cfg.dtype,
        "params": model.n_params(),
        "device": {"platform": dev.platform, "kind": dev.device_kind},
        "compile_s": compile_s,
        "kernels": sorted(compiled_kernels(compiled.as_text())),
        "requests": stats.requests_finished,
        "cancelled_by_steering": stats.requests_cancelled,
        "tokens": stats.tokens_generated,
        "out_of_vocab_tokens": sum(not 0 <= t < cfg.vocab_size for t in generated),
        "nonfinite_logit_steps": stats.nonfinite_steps,
        "wall_s": wall,
        "tokens_per_s": stats.tokens_generated / wall,
        "mean_occupancy": stats.mean_occupancy,
        "median_ttft_s": float(np.median(ttft)) if ttft else None,
        "peak_bytes_after_init": peak_after_init,
        "peak_bytes_in_use": _peak_bytes(),
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="phi4-mini-3.8b")
    ap.add_argument("--published", action="store_true",
                    help="published widths in the config's dtype (needs an accelerator)")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--no-steer", action="store_true")
    args = ap.parse_args()
    use_compile_cache()
    print(json.dumps(run(args.arch, args.requests, args.slots, args.max_new,
                         steer=not args.no_steer, published=args.published), indent=2))


if __name__ == "__main__":
    main()
