"""Plain reference of SmolLM-135M (llama architecture): RMSNorm, grouped
query attention with rotary positions on every head dim, SwiGLU, an LM head
tied to the embedding.  Imports nothing of the system under test."""
from __future__ import annotations

import jax.numpy as jnp

import reference_lm as lm


def shape(cfg: dict) -> lm.Shape:
    return lm.Shape(
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"], n_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], d_ff=cfg["intermediate_size"],
        vocab=cfg["vocab_size"], tied=cfg["tie_word_embeddings"],
        eps=cfg["rms_norm_eps"])


def rope(cfg: dict):
    hd, theta = cfg["head_dim"], cfg["rope_theta"]
    inv_freq = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    return lambda x, pos: lm.rotate_pairs(x, pos, inv_freq)
