"""Mellum2-12B-A2.5B-Instruct: 64-expert top-8 MoE in every layer, three
sliding-window layers (1024) then one full layer, YaRN on the full layers.
[hf:JetBrains/Mellum2-12B-A2.5B-Instruct config.json]

Two entries: the published model, which holds all 64 experts, and one
chip's share of a four-way expert-parallel deployment on a TPU v5e-4 host,
which holds experts 0-15 of every layer and routes over all 64; the
attention, the router and the vocabulary are replicated on every chip."""
from .base import ModelConfig, register

CONFIG = register(ModelConfig(
    name="mellum2-12b-a2.5b",
    family="moe",
    n_layers=28,
    d_model=2304,
    n_heads=32,
    n_kv_heads=4,
    head_dim=128,
    d_ff=896,                   # per-expert moe_intermediate_size
    vocab_size=98_304,
    activation="swiglu",
    norm_eps=1e-6,
    rope_theta=500_000.0,
    n_experts=64,
    router_experts=64,
    experts_per_token=8,
    local_window=1024,
    full_every=4,
    yarn_factor=16.0,
    yarn_original_len=8192,
    yarn_beta_fast=32.0,
    yarn_beta_slow=1.0,
    yarn_attention_factor=1.2772588722239782,
))

CHIP_SHARE = register(CONFIG.with_(name="mellum2-12b-a2.5b-ep4", n_experts=16, first_expert=0))
