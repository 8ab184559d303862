"""Plain decoder-only transformer in float32 ``jax.numpy``: the pieces the
configurations' references share.  No kernels, no cache, no batching; every
matrix product at ``Precision.HIGHEST`` (a TPU otherwise multiplies float32
in one bf16 pass).  Imports nothing of the system under test.

Weights are a dict of bf16 arrays made on the device from the seed, with the
layers stacked on a leading axis::

    embed (V, D), final_norm (D,), lm_head (D, V) unless tied,
    layers: attn_norm, mlp_norm (L, D); wq (L, D, Hq*hd); wk, wv (L, D, Hkv*hd);
            wo (L, Hq*hd, D); w_gate, w_up (L, D, F); w_down (L, F, D)

``quant`` selects the control: every linear layer's weight (per output
channel) and input (per row) rounded to float8 e4m3 before the product.
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


@dataclasses.dataclass(frozen=True)
class Shape:
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    tied: bool
    eps: float


def fold_seed(seed: int):
    """A JAX key from a seed of any size (``jax.random.key`` keeps 32 bits)."""
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


def make_weights(s: Shape, seed: int, dtype=jnp.bfloat16) -> dict:
    """Random weights in one jitted call on the default device.  Dense
    layers ~ N(0, 1/d_in), embeddings ~ N(0, 0.02^2), norm scales ~ 1 + N(0, 0.1^2)."""

    def init(key):
        ks = iter(jax.random.split(key, 16))
        L, D, F = s.n_layers, s.d_model, s.d_ff
        q, kv = s.n_heads * s.head_dim, s.n_kv_heads * s.head_dim

        def dense(shape, d_in):
            return (jax.random.normal(next(ks), shape) * d_in ** -0.5).astype(dtype)

        def scale(shape):
            return (1.0 + 0.1 * jax.random.normal(next(ks), shape)).astype(dtype)

        w = {
            "embed": (0.02 * jax.random.normal(next(ks), (s.vocab, D))).astype(dtype),
            "final_norm": scale((D,)),
            "layers": {
                "attn_norm": scale((L, D)), "mlp_norm": scale((L, D)),
                "wq": dense((L, D, q), D), "wk": dense((L, D, kv), D),
                "wv": dense((L, D, kv), D), "wo": dense((L, q, D), q),
                "w_gate": dense((L, D, F), D), "w_up": dense((L, D, F), D),
                "w_down": dense((L, F, D), F),
            },
        }
        if not s.tied:
            w["lm_head"] = dense((D, s.vocab), D)
        return w

    return jax.jit(init)(fold_seed(seed))


def _q8(x: jnp.ndarray, axis: int) -> jnp.ndarray:
    """Round to float8 e4m3 with a scale per slice along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    sc = jnp.where(amax > 0, amax / F8_MAX, 1.0)
    return (x / sc).astype(F8).astype(jnp.float32) * sc


def linear(x: jnp.ndarray, w: jnp.ndarray, quant: bool) -> jnp.ndarray:
    """x (S, d_in) @ w (d_in, d_out) in float32."""
    w = w.astype(jnp.float32)
    if quant:
        x, w = _q8(x, axis=-1), _q8(w, axis=0)
    return jnp.dot(x, w, precision=HI)


def rms_norm(x: jnp.ndarray, scale: jnp.ndarray, eps: float) -> jnp.ndarray:
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def rotate_pairs(x: jnp.ndarray, pos: jnp.ndarray, inv_freq: jnp.ndarray) -> jnp.ndarray:
    """Rotate adjacent pairs (2i, 2i+1) of the leading ``2*len(inv_freq)``
    dims of x (S, H, hd) by ``pos * inv_freq[i]``; the rest pass."""
    n = 2 * inv_freq.shape[0]
    ang = pos[:, None].astype(jnp.float32) * inv_freq[None, :]  # (S, n/2)
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0:n:2], x[..., 1:n:2]
    rot = jnp.stack([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1).reshape(
        x.shape[:-1] + (n,))
    return jnp.concatenate([rot, x[..., n:]], axis=-1)


def forward_hidden(s: Shape, w: dict, tokens: jnp.ndarray,
                   rope: Callable[[jnp.ndarray, jnp.ndarray], jnp.ndarray],
                   quant: bool = False) -> jnp.ndarray:
    """Final normed hidden states (S, D) of one causal sequence."""
    S = tokens.shape[0]
    pos = jnp.arange(S)
    x = w["embed"][tokens].astype(jnp.float32)
    causal = jnp.tril(jnp.ones((S, S), bool))
    group = s.n_heads // s.n_kv_heads

    def layer(x, lw):
        f32 = {k: v.astype(jnp.float32) for k, v in lw.items()
               if k in ("attn_norm", "mlp_norm")}
        h = rms_norm(x, f32["attn_norm"], s.eps)
        q = linear(h, lw["wq"], quant).reshape(S, s.n_heads, s.head_dim)
        k = linear(h, lw["wk"], quant).reshape(S, s.n_kv_heads, s.head_dim)
        v = linear(h, lw["wv"], quant).reshape(S, s.n_kv_heads, s.head_dim)
        q, k = rope(q, pos), rope(k, pos)
        k = jnp.repeat(k, group, axis=1)  # query head i reads kv head i // group
        v = jnp.repeat(v, group, axis=1)
        sc = jnp.einsum("qhd,khd->hqk", q, k, precision=HI) / jnp.sqrt(
            jnp.float32(s.head_dim))
        sc = jnp.where(causal[None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        a = jnp.einsum("hqk,khd->qhd", p, v, precision=HI).reshape(S, -1)
        x = x + linear(a, lw["wo"], quant)
        h = rms_norm(x, f32["mlp_norm"], s.eps)
        g = jax.nn.silu(linear(h, lw["w_gate"], quant)) * linear(h, lw["w_up"], quant)
        return x + linear(g, lw["w_down"], quant), None

    x, _ = jax.lax.scan(layer, x, w["layers"])
    return rms_norm(x, w["final_norm"].astype(jnp.float32), s.eps)


def logits_at(s: Shape, w: dict, hidden: jnp.ndarray, rows: jnp.ndarray,
              quant: bool = False) -> jnp.ndarray:
    """Logits (len(rows), V) of the hidden states at ``rows``."""
    head = w["embed"].T if s.tied else w["lm_head"]
    return linear(hidden[rows], head, quant)


def make_gap_fn(s: Shape, rope, quant: bool) -> Callable:
    """jitted (weights, tokens (S,), rows (P,), served (P,)) -> gaps (P,):
    how far the reference logit of each served token lies below the
    reference's best.  With ``quant`` the served token is replaced by the
    float8 control's own first choice at that row."""

    def gaps(w, tokens, rows, served):
        h = forward_hidden(s, w, tokens, rope)
        ref = logits_at(s, w, h, rows)
        if quant:
            hq = forward_hidden(s, w, tokens, rope, quant=True)
            served = jnp.argmax(logits_at(s, w, hq, rows, quant=True), axis=-1)
        pick = jnp.take_along_axis(ref, served[:, None], axis=-1)[:, 0]
        return jnp.max(ref, axis=-1) - pick

    return jax.jit(gaps)


def widest_gap(gap_fn, weights, seqs, pad_len: int, pad_rows: int) -> float:
    """Widest gap over ``seqs`` = [(prompt, served tokens)], each padded to
    one shape so a single program serves them all."""
    worst = 0.0
    for prompt, served in seqs:
        toks = list(prompt) + list(served[:-1])
        rows = np.arange(len(prompt) - 1, len(prompt) - 1 + len(served))
        t = np.zeros(pad_len, np.int32)
        t[: len(toks)] = toks
        r = np.zeros(pad_rows, np.int32)
        r[: len(rows)] = rows
        v = np.zeros(pad_rows, np.int32)
        v[: len(served)] = served
        g = np.asarray(gap_fn(weights, jnp.asarray(t), jnp.asarray(r), jnp.asarray(v)))
        worst = max(worst, float(np.max(g[: len(served)])))
    return worst


def matmul_flops_per_token(s: Shape) -> int:
    """Multiply-adds x 2 of every weight product one token passes through,
    the LM head excluded."""
    q, kv = s.n_heads * s.head_dim, s.n_kv_heads * s.head_dim
    per_layer = s.d_model * (q + 2 * kv) + q * s.d_model + 3 * s.d_model * s.d_ff
    return 2 * s.n_layers * per_layer


def prefill_flops(s: Shape, n: int) -> int:
    """A causal prefill of n true tokens; the LM head at the last one only."""
    attn = 4 * s.n_layers * s.n_heads * s.head_dim * (n * (n + 1) // 2)
    return n * matmul_flops_per_token(s) + attn + 2 * s.d_model * s.vocab


def decode_flops(s: Shape, ctx: int) -> int:
    """One decoded token that attends to ``ctx`` cached positions."""
    attn = 4 * s.n_layers * s.n_heads * s.head_dim * ctx
    return matmul_flops_per_token(s) + attn + 2 * s.d_model * s.vocab
