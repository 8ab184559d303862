"""Plain reference of ChatGLM3-6B: RMSNorm, grouped query attention (2 key
and value groups) with rotary positions on the first half of each head's
dims, SwiGLU, an untied LM head.  Follows THUDM's modeling_chatglm.py:
RotaryEmbedding(kv_channels // 2) gives inverse frequencies
10000^(-2i/64), i < 32, applied to adjacent pairs of dims 0..63.
The query/key/value biases are zero (see the configuration's ``assumed``).
Imports nothing of the system under test."""
from __future__ import annotations

import jax.numpy as jnp

import reference_lm as lm


def shape(cfg: dict) -> lm.Shape:
    return lm.Shape(
        n_layers=cfg["num_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"], n_kv_heads=cfg["multi_query_group_num"],
        head_dim=cfg["kv_channels"], d_ff=cfg["ffn_hidden_size"],
        vocab=cfg["padded_vocab_size"], tied=False, eps=cfg["layernorm_epsilon"])


def rope(cfg: dict):
    rot = cfg["kv_channels"] // 2
    inv_freq = 10000.0 ** (-jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    return lambda x, pos: lm.rotate_pairs(x, pos, inv_freq)
