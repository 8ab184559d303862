"""Foundational JAX layers: norms, RoPE, attention (GQA/MLA), MLPs.

Everything is functional: ``init_*`` builds param pytrees, ``apply``-style
functions consume them.  Attention math routes through ``kernels.ops`` so
the Pallas kernels (TPU) and the pure-jnp oracle (CPU / dry-run) share one
call site.  Softmax/logits accumulate in f32 regardless of param dtype.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# sharding hints (no-ops outside a mesh context; see distribution.sharding)
# ---------------------------------------------------------------------------
def hint(x: jnp.ndarray, *logical_axes: Optional[str]) -> jnp.ndarray:
    from ..distribution import sharding

    return sharding.constrain(x, logical_axes)


#: int8 KV-cache quantization (serving lever, EXPERIMENTS.md §Perf C3).
#: Applies to non-ring GQA caches; MLA's latent cache is already compressed.
_KV_QUANT = {"enabled": False}


def set_kv_quant(enabled: bool) -> None:
    _KV_QUANT["enabled"] = bool(enabled)


def kv_quant_enabled() -> bool:
    return _KV_QUANT["enabled"]


# ---------------------------------------------------------------------------
# initializers
# ---------------------------------------------------------------------------
def _dense_init(key, d_in: int, d_out: int, dtype) -> jnp.ndarray:
    scale = (1.0 / d_in) ** 0.5
    return (jax.random.normal(key, (d_in, d_out), jnp.float32) * scale).astype(dtype)


def init_norm(d: int, dtype, kind: str = "rmsnorm") -> Params:
    p = {"scale": jnp.ones((d,), dtype)}
    if kind == "layernorm":
        p["bias"] = jnp.zeros((d,), dtype)
    return p


def apply_norm(p: Params, x: jnp.ndarray, kind: str = "rmsnorm", eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    if kind == "layernorm":
        mu = xf.mean(-1, keepdims=True)
        var = ((xf - mu) ** 2).mean(-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps)
        y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    else:
        ms = (xf * xf).mean(-1, keepdims=True)
        y = xf * jax.lax.rsqrt(ms + eps) * p["scale"].astype(jnp.float32)
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------
def rope_tables(
    positions: jnp.ndarray, dim: int, theta: float
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """positions (...,S) -> (…,S,dim/2) sin/cos tables in f32."""
    half = dim // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    ang = positions[..., None].astype(jnp.float32) * freqs
    return jnp.sin(ang), jnp.cos(ang)


def apply_rope(
    x: jnp.ndarray,
    sin: jnp.ndarray,
    cos: jnp.ndarray,
    mode: str = "full",
) -> jnp.ndarray:
    """x (B,S,H,D); rotate pairs (even, odd).  mode='half' rotates only the
    first half of D (ChatGLM-style partial rotary)."""
    if mode == "none":
        return x
    d = x.shape[-1]
    rot_d = d if mode == "full" else d // 2
    xr, xp = x[..., :rot_d], x[..., rot_d:]
    x1, x2 = xr[..., 0::2], xr[..., 1::2]
    s = sin[:, :, None, : rot_d // 2]
    c = cos[:, :, None, : rot_d // 2]
    y1 = x1 * c - x2 * s
    y2 = x2 * c + x1 * s
    yr = jnp.stack([y1, y2], axis=-1).reshape(xr.shape).astype(x.dtype)
    return jnp.concatenate([yr, xp], axis=-1) if mode == "half" else yr


# ---------------------------------------------------------------------------
# GQA attention
# ---------------------------------------------------------------------------
def init_attention(key, cfg: ArchConfig, dtype) -> Params:
    d, hd = cfg.d_model, cfg.head_dim_
    kq, kk, kv, ko = jax.random.split(key, 4)
    return {
        "wq": _dense_init(kq, d, cfg.n_heads * hd, dtype),
        "wk": _dense_init(kk, d, cfg.n_kv_heads * hd, dtype),
        "wv": _dense_init(kv, d, cfg.n_kv_heads * hd, dtype),
        "wo": _dense_init(ko, cfg.n_heads * hd, d, dtype),
    }


def init_cross_attention(key, cfg: ArchConfig, dtype) -> Params:
    return init_attention(key, cfg, dtype)


def _split_heads(x, n_heads, hd):
    b, s, _ = x.shape
    return x.reshape(b, s, n_heads, hd)


# Every cache leaf keeps its sequence axis LAST: K/V are (B, Hkv, D, S),
# int8 scales (B, Hkv, S), MLA's latents (B, R, S).  That is the layout the
# TPU stores them in and the decode attention reads, so a one-token write is
# one lane column per slot and head, and nothing is relaid out around it.
def _seq_minor(a: jnp.ndarray) -> jnp.ndarray:
    """A fresh (B, S, ...) block in the cache layout (B, ..., S)."""
    return jnp.moveaxis(a, 1, -1)


def _layer_of(buf: jnp.ndarray, layer) -> jnp.ndarray:
    """Layer ``layer`` of a layer-stacked cache leaf; the leaf itself when
    ``layer`` is None.  Inside the decode loop this is a dynamic index into
    the group's stack, which XLA fuses into the op that reads it."""
    return buf if layer is None else buf[layer]


def _put(buf: jnp.ndarray, val: jnp.ndarray, pos) -> jnp.ndarray:
    """Write the block ``val`` (..., s) at sequence position ``pos`` of
    ``buf`` (..., Smax)."""
    start = (0,) * (val.ndim - 1) + (pos,)
    return jax.lax.dynamic_update_slice(buf, val.astype(buf.dtype), start)


def _put_rows(cache: Params, rows, new: Params, stacked: bool = False) -> Params:
    """Ragged one-token write: for each slot b, ``new[n][b]`` (...) into
    leaf ``n`` of ``cache`` (B, ..., Smax) at sequence position ``rows[b]``;
    with ``stacked``, ``new[n]`` (L, B, ...) into every layer of a layer
    stack (L, B, ..., Smax) at once.

    A loop over the slots rewrites, in every leaf, the tile of up to 128
    positions that holds the slot's position, with the token set in its
    lane.  Tiles and positions stay sequence-minor: XLA's scatter of single
    positions would store the cache token-major instead, and its gather of
    tiles would relay out the whole stack."""
    names = list(new)
    slot = 1 if stacked else 0

    def one(b, leaves):
        out = []
        for n, buf in zip(names, leaves):
            x = jnp.expand_dims(jnp.take(new[n], b, axis=slot), slot)
            smax = buf.shape[-1]
            w = min(128, smax)
            t0 = jnp.minimum(rows[b] - rows[b] % w, smax - w)
            start = [0] * buf.ndim
            start[slot], start[-1] = b, t0
            size = list(buf.shape)
            size[slot], size[-1] = 1, w
            tile = jax.lax.dynamic_slice(buf, start, size)
            tile = jnp.where(
                jnp.arange(w) == rows[b] - t0, x.astype(buf.dtype)[..., None], tile
            )
            out.append(jax.lax.dynamic_update_slice(buf, tile, start))
        return out

    leaves = jax.lax.fori_loop(0, rows.shape[0], one, [cache[n] for n in names])
    return dict(zip(names, leaves))


def _decode_write(cache: Params, new: Params, wr, idx, layer) -> Params:
    """After a decode step's attention, the token ``new`` (leaf -> (B, ...,
    1) block) goes to sequence position ``wr``: written now, or, inside the
    decode layer loop (``layer`` given), handed back for ``commit_tokens``."""
    tok = {n: a[..., 0] for n, a in new.items()}
    if layer is not None:
        return {"new": tok, "pos": wr, "index": idx + 1}
    if jnp.ndim(idx) == 1:
        upd = _put_rows(cache, wr, tok)
    else:
        upd = {n: _put(cache[n], a, wr) for n, a in new.items()}
    return {**cache, **upd, "index": idx + 1}


def commit_tokens(cache: Params, pending: Params) -> Params:
    """Write the tokens that a decode step's layer loop handed back (stacked
    over the group's layers by the loop) into the group's stacked cache, all
    layers in one pass over the slots.  Every layer of a group holds the same
    per-slot index (prefill, insert and decode write them together), so the
    first layer's write positions serve them all."""
    pos = pending["pos"][0]
    toks = pending["new"]
    if jnp.ndim(pos) == 1:
        upd = _put_rows(cache, pos, toks, stacked=True)
    else:
        upd = {n: _put(cache[n], a[..., None], pos) for n, a in toks.items()}
    return {**cache, **upd, "index": pending["index"]}


def attention(
    p: Params,
    x: jnp.ndarray,
    cfg: ArchConfig,
    positions: jnp.ndarray,
    cache: Optional[Params] = None,
    kv_x: Optional[jnp.ndarray] = None,
    layer: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, Optional[Params]]:
    """GQA self- or cross-attention.

    cache: None (training/prefill without cache) or
           {"k","v" (B,Hkv,Dh,Smax), "index" scalar or (B,)} for decode; the
           updated cache is returned.  kv_x: encoder states for cross-attention
           (cache then holds precomputed K/V; positions ignored for K).
    layer: when given, every cache leaf carries a leading layer axis and this
           call reads and writes layer ``layer`` of it in place: a decode step
           writes only the new token, and attends over the stack's layer
           without slicing it out (models/transformer._scan_group).
    """
    hd = cfg.head_dim_
    b, s, _ = x.shape
    q = _split_heads(x @ p["wq"], cfg.n_heads, hd)
    q = hint(q, "batch", "seq", "heads", None)

    cross = kv_x is not None
    if cross:
        if cache is not None and "k" in cache:  # precomputed at prefill
            k, v = cache["k"], cache["v"]
        else:
            k = _split_heads(kv_x @ p["wk"], cfg.n_kv_heads, hd)
            v = _split_heads(kv_x @ p["wv"], cfg.n_kv_heads, hd)
            if cache is not None:
                cache = {**cache, "k": k, "v": v}
        sin = cos = None
    else:
        k = _split_heads(x @ p["wk"], cfg.n_kv_heads, hd)
        v = _split_heads(x @ p["wv"], cfg.n_kv_heads, hd)
        sin, cos = rope_tables(positions, hd, cfg.rope_theta)
        q = apply_rope(q, sin, cos, cfg.rope_mode)
        k = apply_rope(k, sin, cos, cfg.rope_mode)

    from ..kernels import ops as kops

    if cache is not None and not cross:
        idx = _layer_of(cache["index"], layer)
        smax = cache["k"].shape[-1]
        ring = bool(cfg.sliding_window) and smax == cfg.sliding_window
        quant = "k_s" in cache  # int8 KV cache (set_kv_quant)
        if quant:
            from ..kernels.ref import quantize_kv

            k_w, ks_w = quantize_kv(k)
            v_w, vs_w = quantize_kv(v)
            new = {"k": k_w, "k_s": ks_w, "v": v_w, "v_s": vs_w}
        elif ring and s >= smax:
            # prefill past the window: the ring holds its last smax tokens,
            # rolled so that token t sits at t % smax
            r = s % smax
            new = {"k": jnp.roll(k[:, -smax:], r, axis=1),
                   "v": jnp.roll(v[:, -smax:], r, axis=1)}
        else:
            new = {"k": k, "v": v}
        new = {n: _seq_minor(a) for n, a in new.items()}
        if s == 1:
            # decode: attend to the cache's valid slots and to this step's
            # token, which takes slot wr (the ring's oldest, or the clamped
            # last slot) — the same as writing it first and attending to
            # idx + 1 slots
            wr = idx % smax if ring else jnp.minimum(idx, smax - 1)
            if quant:
                k_new, v_new = (
                    new[n][..., 0].astype(jnp.float32) * new[n + "_s"]
                    for n in ("k", "v")
                )
                out = kops.decode_attention_q8(
                    q, cache["k"], cache["k_s"], cache["v"], cache["v_s"],
                    length=idx, layer=layer, new=(k_new, v_new, wr),
                )
            else:
                k_new, v_new = (new[n][..., 0].astype(cache[n].dtype) for n in ("k", "v"))
                out = kops.decode_attention(
                    q, cache["k"], cache["v"], length=idx,
                    sliding_window=cfg.sliding_window, layer=layer,
                    new=(k_new, v_new, wr),
                )
            cache = _decode_write(cache, new, wr, idx, layer)
        else:
            # prefill (from an empty cache): causal attention over the fresh
            # block; keys/values recorded into the cache for later decode.
            wr = 0 if ring and s >= smax else idx
            upd = {n: _put(cache[n], a, wr) for n, a in new.items()}
            cache = {**cache, **upd, "index": idx + s}
            out = kops.flash_attention(
                q, k, v, causal=True, sliding_window=cfg.sliding_window
            )
    elif cross:
        out = kops.cross_attention(q, k, v)
    else:
        out = kops.flash_attention(
            q, k, v, causal=True, sliding_window=cfg.sliding_window
        )
    out = hint(out, "batch", "seq", "heads", None)
    y = out.reshape(b, s, cfg.n_heads * hd) @ p["wo"]
    return hint(y, "batch", "seq", None), cache


# ---------------------------------------------------------------------------
# MLA attention (DeepSeek-V3) — latent-compressed KV cache
# ---------------------------------------------------------------------------
def init_mla(key, cfg: ArchConfig, dtype) -> Params:
    d = cfg.d_model
    ks = jax.random.split(key, 8)
    qk_dim = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    return {
        "wq_a": _dense_init(ks[0], d, cfg.q_lora_rank, dtype),
        "q_norm": init_norm(cfg.q_lora_rank, dtype),
        "wq_b": _dense_init(ks[1], cfg.q_lora_rank, cfg.n_heads * qk_dim, dtype),
        "wkv_a": _dense_init(ks[2], d, cfg.kv_lora_rank + cfg.qk_rope_head_dim, dtype),
        "kv_norm": init_norm(cfg.kv_lora_rank, dtype),
        "wkv_b": _dense_init(
            ks[3],
            cfg.kv_lora_rank,
            cfg.n_heads * (cfg.qk_nope_head_dim + cfg.v_head_dim),
            dtype,
        ),
        "wo": _dense_init(ks[4], cfg.n_heads * cfg.v_head_dim, d, dtype),
    }


def mla_attention(
    p: Params,
    x: jnp.ndarray,
    cfg: ArchConfig,
    positions: jnp.ndarray,
    cache: Optional[Params] = None,
    layer: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, Optional[Params]]:
    """Multi-head Latent Attention.  The cache stores only the compressed
    latent c_kv (kv_lora_rank) and the shared rotary key k_pe — DeepSeek-V3's
    memory saving, reproduced exactly.  ``layer`` as in ``attention``."""
    b, s, _ = x.shape
    nope, rope_d, vh = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    h = cfg.n_heads

    q = apply_norm(p["q_norm"], x @ p["wq_a"]) @ p["wq_b"]
    q = q.reshape(b, s, h, nope + rope_d)
    q_nope, q_pe = q[..., :nope], q[..., nope:]

    kv_a = x @ p["wkv_a"]
    c_kv, k_pe = kv_a[..., : cfg.kv_lora_rank], kv_a[..., cfg.kv_lora_rank :]
    c_kv = apply_norm(p["kv_norm"], c_kv)
    sin, cos = rope_tables(positions, rope_d, cfg.rope_theta)
    q_pe = apply_rope(q_pe, sin, cos)
    k_pe = apply_rope(k_pe[:, :, None, :], sin, cos)  # single shared rope head

    from ..kernels import ops as kops

    decode = cache is not None and s == 1
    if cache is not None:
        idx = _layer_of(cache["index"], layer)
        new = {"c_kv": _seq_minor(c_kv), "k_pe": _seq_minor(k_pe[:, :, 0, :])}
        smax = cache["c_kv"].shape[-1]
        if not decode:
            # prefill: record the block; attention over the fresh block only
            upd = {n: _put(cache[n], a, idx) for n, a in new.items()}
            cache = {**cache, **upd, "index": idx + s}
    if decode:
        # ---- decode with weight ABSORPTION (DeepSeek-V3's trick) ----------
        # Never decompress the latent cache: fold wkv_b's key half into the
        # query and apply its value half after attending over the latents:
        # the cache's valid slots and this step's latent, which takes slot
        # wr (as in ``attention``).
        f32 = jnp.float32
        wb = p["wkv_b"].reshape(cfg.kv_lora_rank, h, nope + vh)
        wb_k, wb_v = wb[..., :nope], wb[..., nope:]
        q_eff = jnp.einsum("bshn,rhn->bshr", q_nope.astype(f32), wb_k.astype(f32))
        c_all, pe_all = (_layer_of(cache[n], layer).astype(f32) for n in ("c_kv", "k_pe"))
        c_new, pe_new = (new[n][..., 0].astype(cache[n].dtype).astype(f32)
                         for n in ("c_kv", "k_pe"))
        scale = 1.0 / jnp.sqrt(f32(nope + rope_d))
        scores = (jnp.einsum("bshr,brt->bhst", q_eff, c_all)
                  + jnp.einsum("bshd,bdt->bhst", q_pe.astype(f32), pe_all)) * scale
        s_new = (jnp.einsum("bshr,br->bhs", q_eff, c_new)
                 + jnp.einsum("bshd,bd->bhs", q_pe.astype(f32), pe_new))[..., None] * scale
        wr = jnp.minimum(idx, smax - 1)
        lim, wr_b = (jnp.broadcast_to(a, (b,))[:, None, None, None]
                     for a in (jnp.minimum(idx, smax), wr))
        t = jnp.arange(smax)[None, None, None, :]
        scores = jnp.where((t < lim) & (t != wr_b), scores, -1e30)
        m = jnp.maximum(scores.max(-1, keepdims=True), s_new)
        pr, p_new = jnp.exp(scores - m), jnp.exp(s_new - m)
        denom = pr.sum(-1, keepdims=True) + p_new
        ctx = jnp.einsum("bhst,brt->bshr", pr / denom, c_all)
        ctx = ctx + jnp.moveaxis(p_new / denom, 1, 2) * c_new[:, None, None, :]
        out = jnp.einsum("bshr,rhv->bshv", ctx, wb_v.astype(f32)).astype(x.dtype)
        cache = _decode_write(cache, new, wr, idx, layer)
    else:
        # ---- train / prefill: decompress K/V (dense MXU matmuls) ----------
        kv = (c_kv @ p["wkv_b"]).reshape(b, s, h, nope + vh)
        k_nope, v = kv[..., :nope], kv[..., nope:]
        k = jnp.concatenate(
            [
                k_nope,
                jnp.broadcast_to(
                    k_pe[:, :, 0, :][:, :, None, :], k_nope.shape[:3] + (rope_d,)
                ),
            ],
            axis=-1,
        )
        qfull = jnp.concatenate([q_nope, q_pe], axis=-1)
        out = kops.flash_attention(qfull, k, v, causal=True)
    y = out.reshape(b, s, h * vh) @ p["wo"]
    return hint(y, "batch", "seq", None), cache


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------
def init_mlp(key, d: int, f: int, kind: str, dtype) -> Params:
    k1, k2, k3 = jax.random.split(key, 3)
    p = {"w_out": _dense_init(k2, f, d, dtype)}
    if kind == "swiglu":
        p["w_gate"] = _dense_init(k1, d, f, dtype)
        p["w_up"] = _dense_init(k3, d, f, dtype)
    else:
        p["w_in"] = _dense_init(k1, d, f, dtype)
    return p


def apply_mlp(p: Params, x: jnp.ndarray, kind: str) -> jnp.ndarray:
    if kind == "swiglu":
        h = jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])
    elif kind == "relu2":
        h = jnp.square(jax.nn.relu(x @ p["w_in"]))
    else:  # gelu
        h = jax.nn.gelu(x @ p["w_in"])
    h = hint(h, "batch", "seq", "mlp")
    return h @ p["w_out"]


# ---------------------------------------------------------------------------
# embeddings / LM head
# ---------------------------------------------------------------------------
def init_embedding(key, vocab: int, d: int, dtype) -> jnp.ndarray:
    return (jax.random.normal(key, (vocab, d), jnp.float32) * 0.02).astype(dtype)


def embed(table: jnp.ndarray, tokens: jnp.ndarray) -> jnp.ndarray:
    return hint(jnp.take(table, tokens, axis=0), "batch", "seq", None)


def lm_logits(table_or_w: jnp.ndarray, x: jnp.ndarray, tied: bool) -> jnp.ndarray:
    w = table_or_w.T if tied else table_or_w
    logits = x.astype(jnp.float32) @ w.astype(jnp.float32)
    return hint(logits, "batch", "seq", "vocab")
