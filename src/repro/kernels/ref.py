"""Pure-jnp oracles for every kernel.  Naive, obviously-correct math used by
the per-kernel allclose sweeps and as the CPU execution path."""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

__all__ = ["attention_ref", "decode_attention_ref", "ssd_scan_ref"]

_NEG = -1e30


def attention_ref(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    causal: bool = True,
    sliding_window: Optional[int] = None,
) -> jnp.ndarray:
    """q (B,Sq,Hq,D), k/v (B,Sk,Hkv,D) -> (B,Sq,Hq,D); GQA by head grouping.

    Materializes the full score matrix — the oracle, not the fast path.
    """
    b, sq, hq, d = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    dv = v.shape[-1]
    g = hq // hkv
    qg = q.reshape(b, sq, hkv, g, d).astype(jnp.float32)
    kf = k.astype(jnp.float32)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", qg, kf) / jnp.sqrt(d).astype(jnp.float32)
    qpos = jnp.arange(sq)[:, None] + (sk - sq)  # align ends (prefill/causal)
    kpos = jnp.arange(sk)[None, :]
    mask = jnp.ones((sq, sk), bool)
    if causal:
        mask &= kpos <= qpos
    if sliding_window is not None:
        mask &= kpos > qpos - sliding_window
    scores = jnp.where(mask[None, None, None], scores, _NEG)
    p = jnp.exp(scores - scores.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", p, v.astype(jnp.float32))
    return out.reshape(b, sq, hq, dv).astype(q.dtype)


def decode_attention_ref(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    length,
    sliding_window: Optional[int] = None,
    new=None,
) -> jnp.ndarray:
    """One-token decode: q (B,1,Hq,D) vs ring-buffer cache k (B,Hkv,D,Smax),
    v (B,Hkv,Dv,Smax) — the serving cache's sequence-minor layout.

    Valid cache slots are arange(Smax) < length (ring buffers pass
    length >= Smax once wrapped, making every slot valid — attention is
    order-invariant so slot order does not matter).  ``length`` may be a
    scalar (uniform batch) or a (B,) vector (ragged continuous batching).

    ``new`` = (k_new (B,Hkv,D), v_new (B,Hkv,Dv), pos): this step's token,
    not yet in the cache.  Slot ``pos`` (scalar or (B,)), which the token is
    about to overwrite, is left out, and the token is attended to as well —
    the same as writing it at ``pos`` and attending to ``length + 1`` slots.
    """
    b, sq, hq, d = q.shape
    hkv, smax = k.shape[1], k.shape[-1]
    dv = v.shape[2]
    g = hq // hkv
    qg = q.reshape(b, sq, hkv, g, d)
    scale = 1.0 / jnp.sqrt(d).astype(jnp.float32)
    # f32 ACCUMULATION over bf16 operands (preferred_element_type), never a
    # wholesale astype(f32) of k/v — that materializes an f32 shadow of the
    # entire KV cache, which XLA then carries through the decode loop.
    # (k first: XLA's CPU runtime refuses bf16 x bf16 -> f32 with q first)
    scores = jnp.einsum(
        "bhdk,bqhgd->bhgqk", k, qg, preferred_element_type=jnp.float32
    ) * scale
    lim = jnp.broadcast_to(jnp.minimum(jnp.asarray(length), smax), (b,))
    valid = jnp.arange(smax)[None, :] < lim[:, None]  # (B, Smax)
    if new is not None:
        k_new, v_new, pos = new
        valid &= jnp.arange(smax)[None, :] != jnp.broadcast_to(pos, (b,))[:, None]
        s_new = jnp.einsum(
            "bqhgd,bhd->bhgq", qg, k_new, preferred_element_type=jnp.float32
        )[..., None] * scale  # (B,Hkv,G,Sq,1)
    scores = jnp.where(valid[:, None, None, None, :], scores, _NEG)
    m = scores.max(-1, keepdims=True)
    if new is not None:
        m = jnp.maximum(m, s_new)
    p = jnp.exp(scores - m)
    denom = p.sum(-1, keepdims=True)
    if new is not None:
        p_new = jnp.exp(s_new - m)
        denom = denom + p_new
    out = jnp.einsum(
        "bhgqk,bhdk->bqhgd", (p / denom).astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    )
    if new is not None:
        w_new = jnp.moveaxis((p_new / denom)[..., 0], 3, 1)  # (B,Sq,Hkv,G)
        out = out + w_new[..., None] * v_new.astype(jnp.float32)[:, None, :, None, :]
    return out.reshape(b, sq, hq, dv).astype(q.dtype)


def quantize_kv(k: jnp.ndarray, axis: int = -1):
    """Per-(token, head) symmetric int8 quantization of a KV tensor.

    k (B,S,Hkv,D) -> (q int8 (B,S,Hkv,D), scale f32 (B,S,Hkv)); ``axis`` is
    the head-dim axis that a scale covers."""
    amax = jnp.max(jnp.abs(k.astype(jnp.float32)), axis=axis)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(
        jnp.round(k.astype(jnp.float32) / jnp.expand_dims(scale, axis)), -127, 127
    ).astype(jnp.int8)
    return q, scale


def decode_attention_q8_ref(
    q: jnp.ndarray,  # (B,1,Hq,D)
    k_q: jnp.ndarray,  # (B,Hkv,D,Smax) int8
    k_s: jnp.ndarray,  # (B,Hkv,Smax) f32
    v_q: jnp.ndarray,
    v_s: jnp.ndarray,
    length,
    new=None,
) -> jnp.ndarray:
    """int8-KV decode oracle: dequantize then run the fp oracle.  The Pallas
    kernel dequantizes per VMEM tile instead — HBM reads HALVE.  ``new`` as
    in ``decode_attention_ref``, its token already dequantized."""
    k = k_q.astype(jnp.float32) * k_s[:, :, None, :]
    v = v_q.astype(jnp.float32) * v_s[:, :, None, :]
    return decode_attention_ref(q, k, v, length, new=new)


def ssd_scan_ref(
    x: jnp.ndarray,
    dt: jnp.ndarray,
    A: jnp.ndarray,
    B: jnp.ndarray,
    C: jnp.ndarray,
    initial_state: Optional[jnp.ndarray] = None,
):
    """Mamba-2 SSD, naive sequential recurrence (the oracle).

    x (Bt,S,H,P)  dt (Bt,S,H)  A (H,) negative  B,C (Bt,S,N)
    state h (Bt,H,P,N):  h_t = exp(A*dt_t) h_{t-1} + dt_t * x_t B_t^T
                         y_t = h_t C_t
    Returns y (Bt,S,H,P), final state.
    """
    bt, s, h, p = x.shape
    n = B.shape[-1]
    xf = x.astype(jnp.float32)
    dtf = dt.astype(jnp.float32)
    Bf = B.astype(jnp.float32)
    Cf = C.astype(jnp.float32)
    Af = A.astype(jnp.float32)
    h0 = (
        jnp.zeros((bt, h, p, n), jnp.float32)
        if initial_state is None
        else initial_state.astype(jnp.float32)
    )

    def step(hprev, t):
        decay = jnp.exp(Af[None, :] * dtf[:, t])  # (Bt,H)
        upd = jnp.einsum("bh,bhp,bn->bhpn", dtf[:, t], xf[:, t], Bf[:, t])
        hnew = hprev * decay[:, :, None, None] + upd
        y = jnp.einsum("bhpn,bn->bhp", hnew, Cf[:, t])
        return hnew, y

    import jax

    hT, ys = jax.lax.scan(step, h0, jnp.arange(s))
    y = jnp.moveaxis(ys, 0, 1)  # (Bt,S,H,P)
    return y.astype(x.dtype), hT
