"""The float32 reference against the program, at a size the CPU holds.

The reference follows the program's equations (checked against the
program's own float32 forward pass), the engine's prefill-then-decode in
bf16 stays within the comparison's limit, and the float8 control fails it.
"""

import json
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bench import weights
from bench.reference import dense
from bench.run import model_dict

from repro.configs import smoke_config
from repro.models import build_model
from repro.serve import Request, ServingEngine

STD = 0.14          # N(0, 0.02) at width 3072 is N(0, 0.14) at the smoke width of 64
LIMIT = 0.1         # program at most ~0.02 on these seeds, control 0.6 and more
CONFIGS = Path(__file__).resolve().parents[2] / "bench" / "configs"
# the keys that the harness handed the readers before it handed them the
# configuration file's whole ``model`` object
DENSE_KEYS = ("family", "n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim", "d_ff",
              "vocab_size", "activation", "norm_eps", "norm_type", "norm_offset",
              "embed_scale", "rope_theta", "tie_embeddings")


def sizes(cfg):
    """The model dict the harness hands the reference for ``cfg``."""
    return model_dict(cfg, json.loads((CONFIGS / f"{cfg.name}.json").read_text()))


@pytest.mark.parametrize("arch", ["phi4-mini-3.8b", "yi-6b"])
def test_dense_files_give_the_same_model_dict(arch):
    config = json.loads((CONFIGS / f"{arch}.json").read_text())
    assert config["reference"] == "dense"
    cfg = smoke_config(arch)
    got = model_dict(cfg, config)
    assert tuple(got) == DENSE_KEYS
    assert got == {k: getattr(cfg, k) for k in DENSE_KEYS}


@pytest.mark.parametrize("arch", ["phi4-mini-3.8b", "yi-6b"])
def test_reference_matches_the_program_forward_in_float32(arch):
    cfg = smoke_config(arch).with_(dtype="float32")
    model = build_model(cfg)
    params = weights.make(model.shapes(), 3, STD)
    tokens = np.random.default_rng(0).integers(1, cfg.vocab_size, 40).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        want, _ = model.forward(params, {"tokens": jnp.asarray(tokens)[None]})
    want = np.asarray(want[0, :, : cfg.vocab_size])
    h = dense.hidden(sizes(cfg), params, tokens)[: len(tokens)]
    table = params["embed"] if cfg.tie_embeddings else params["unembed"]
    with jax.default_matmul_precision("highest"):
        got = np.asarray(h @ table[: cfg.vocab_size].T)
    np.testing.assert_allclose(got, want, atol=2e-4 * np.abs(want).max())


def serve(arch, seed, n_new=40):
    cfg = smoke_config(arch).with_(dtype="bfloat16")
    model = build_model(cfg)
    params = weights.make(model.shapes(), seed, STD)
    done = []
    engine = ServingEngine(model, params, n_slots=2, max_len=128, on_finish=done.append)
    rng = np.random.default_rng(seed)
    for i in range(3):
        prompt = rng.integers(1, cfg.vocab_size, int(rng.integers(5, 30))).astype(np.int32)
        engine.submit(Request(i, prompt, max_new_tokens=n_new))
    engine.run_until_drained()
    return cfg, params, done


@pytest.mark.parametrize("arch,seed", [("phi4-mini-3.8b", 1), ("yi-6b", 2)])
def test_engine_within_limit_and_control_fails(arch, seed):
    cfg, params, done = serve(arch, seed)
    assert len(done) == 3
    served, control = [], []
    for req in done:
        out = dense.served_gaps(sizes(cfg), params, req.prompt,
                                np.asarray(req.generated, np.int32), control=True)
        assert out["served"].shape == (len(req.generated),)
        served.append(out["served"].max())
        control.append(out["control"].max())
    assert max(served) <= LIMIT
    assert max(control) > LIMIT


def test_a_wrong_token_reads_a_wide_gap():
    cfg, params, done = serve("yi-6b", 4, n_new=12)
    req = done[0]
    bad = np.asarray(req.generated, np.int32).copy()
    bad[5] = (bad[5] + 1) % cfg.vocab_size
    out = dense.served_gaps(sizes(cfg), params, req.prompt, bad)
    assert out["served"][5] > LIMIT
