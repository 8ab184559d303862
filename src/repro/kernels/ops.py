"""Kernel dispatch: Pallas on TPU, memory-efficient jnp elsewhere.

One call site for the model code.  ``set_impl`` switches globally:
  * "pallas"  — pl.pallas_call kernels (TPU; or interpret=True in tests)
  * "jnp"     — query-chunked online-softmax jnp (identical math; used for
                the CPU dry-run so the lowered HLO carries real FLOPs)
  * "ref"     — naive oracle (tiny smoke tests)
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from . import ref as _ref

_IMPL = {"mode": "jnp", "interpret": False}


def set_impl(mode: str, interpret: bool = False) -> None:
    assert mode in ("pallas", "jnp", "ref")
    _IMPL["mode"] = mode
    _IMPL["interpret"] = interpret


def get_impl() -> str:
    return _IMPL["mode"]


# ---------------------------------------------------------------------------
# flash attention (train / prefill)
# ---------------------------------------------------------------------------
def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    causal: bool = True,
    sliding_window: Optional[int] = None,
    q_chunk: int = 512,
) -> jnp.ndarray:
    if _IMPL["mode"] == "pallas":
        from .flash_attention import flash_attention_pallas

        return flash_attention_pallas(
            q, k, v, causal=causal, sliding_window=sliding_window,
            interpret=_IMPL["interpret"],
        )
    # named_scope marks the region in HLO metadata: on TPU this runs as the
    # Pallas kernel whose score/prob tiles stay in VMEM, so the roofline
    # analyzer (distribution/hlo_analysis) books interior bytes separately.
    with jax.named_scope("pallas_flash_attention"):
        if _IMPL["mode"] == "ref" or q.shape[1] <= q_chunk:
            return _ref.attention_ref(q, k, v, causal, sliding_window)
        return _chunked_attention(q, k, v, causal, sliding_window, q_chunk)


def _chunked_attention(q, k, v, causal, window, q_chunk):
    """Query-chunked attention: peak memory O(chunk x S) not O(S^2)."""
    b, s, hq, d = q.shape
    if s % q_chunk:
        return _ref.attention_ref(q, k, v, causal, window)
    sk = k.shape[1]  # may differ from s (cross-attention)
    hkv = k.shape[2]
    g = hq // hkv
    n_chunks = s // q_chunk
    qc = q.reshape(b, n_chunks, q_chunk, hkv, g, d)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    scale = 1.0 / jnp.sqrt(d).astype(jnp.float32)
    kpos = jnp.arange(sk)

    def one_chunk(ci):
        qi = qc[:, ci].astype(jnp.float32)  # (B,C,Hkv,G,D)
        scores = jnp.einsum("bqhgd,bkhd->bhgqk", qi, kf) * scale
        qpos = ci * q_chunk + jnp.arange(q_chunk) + (sk - s)  # align ends
        mask = jnp.ones((q_chunk, sk), bool)
        if causal:
            mask &= kpos[None, :] <= qpos[:, None]
        if window is not None:
            mask &= kpos[None, :] > qpos[:, None] - window
        scores = jnp.where(mask[None, None, None], scores, -1e30)
        p = jnp.exp(scores - scores.max(-1, keepdims=True))
        p = p / p.sum(-1, keepdims=True)
        out = jnp.einsum("bhgqk,bkhd->bqhgd", p, vf)
        return out.reshape(b, q_chunk, hq, vf.shape[-1]).astype(q.dtype)

    # checkpoint per q-chunk: the backward pass RECOMPUTES scores/probs
    # chunk-by-chunk instead of saving the stacked (n_chunks x C x S) prob
    # tensor as a residual — the flash-attention backward structure, so the
    # lowered HLO's HBM buffers match what the Pallas kernel materializes.
    out = jax.lax.map(
        jax.checkpoint(one_chunk, prevent_cse=False), jnp.arange(n_chunks)
    )  # (n,B,C,Hq,Dv)
    return jnp.moveaxis(out, 0, 1).reshape(b, s, hq, v.shape[-1])


# ---------------------------------------------------------------------------
# decode attention (one new token vs a long KV cache)
# ---------------------------------------------------------------------------
def decode_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    length,
    sliding_window: Optional[int] = None,
    layer=None,
    new=None,
) -> jnp.ndarray:
    """q (B,1,Hq,D) against a sequence-minor cache k (B,Hkv,D,Smax), v
    (B,Hkv,Dv,Smax).  With ``layer``, k/v are layer stacks (L,B,Hkv,D,Smax)
    and the call attends over layer ``layer`` without slicing it out.
    ``new`` = (k_new, v_new, pos): this step's token, attended to in place
    of cache slot ``pos`` (ref.decode_attention_ref)."""
    # Both paths attend to every valid slot.  That equals windowed attention
    # only when the cache holds at most one window (a ring buffer of window
    # slots, or a max_len below the window), so a wider cache is refused.
    if sliding_window is not None and k.shape[-1] > sliding_window:
        raise ValueError(
            f"decode over a {k.shape[-1]}-slot cache cannot apply a "
            f"{sliding_window}-token sliding window; size the cache to the window"
        )
    if _IMPL["mode"] == "pallas":
        from .decode_attention import decode_attention_pallas

        return decode_attention_pallas(
            q, k, v, length=length, interpret=_IMPL["interpret"], layer=layer, new=new
        )
    with jax.named_scope("pallas_decode_attention"):
        if layer is not None:  # a dynamic index that XLA fuses into the reads
            k, v = k[layer], v[layer]
        return _ref.decode_attention_ref(q, k, v, length, sliding_window, new)


def decode_attention_q8(
    q: jnp.ndarray,
    k_q: jnp.ndarray,
    k_s: jnp.ndarray,
    v_q: jnp.ndarray,
    v_s: jnp.ndarray,
    length,
    layer=None,
    new=None,
) -> jnp.ndarray:
    """int8-KV flash decoding: HBM KV reads halve; dequant happens per VMEM
    tile inside the kernel (beyond-paper serving lever, EXPERIMENTS.md §Perf
    Cell C).  Layouts, ``layer`` and ``new`` (its token dequantized) as in
    ``decode_attention``."""
    if _IMPL["mode"] == "pallas":
        from .decode_attention import decode_attention_q8_pallas

        return decode_attention_q8_pallas(
            q, k_q, k_s, v_q, v_s, length=length, interpret=_IMPL["interpret"],
            layer=layer, new=new,
        )
    with jax.named_scope("pallas_decode_attention_q8"):
        if layer is not None:
            k_q, k_s, v_q, v_s = (a[layer] for a in (k_q, k_s, v_q, v_s))
        return _ref.decode_attention_q8_ref(q, k_q, k_s, v_q, v_s, length, new)


def cross_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray) -> jnp.ndarray:
    return flash_attention(q, k, v, causal=False, sliding_window=None)


# ---------------------------------------------------------------------------
# Mamba-2 SSD scan
# ---------------------------------------------------------------------------
def ssd_scan(
    x: jnp.ndarray,
    dt: jnp.ndarray,
    A: jnp.ndarray,
    B: jnp.ndarray,
    C: jnp.ndarray,
    chunk: int = 256,
    initial_state=None,
):
    if _IMPL["mode"] == "pallas":
        from .ssd_scan import ssd_scan_pallas

        return ssd_scan_pallas(
            x, dt, A, B, C, chunk=chunk, initial_state=initial_state,
            interpret=_IMPL["interpret"],
        )
    with jax.named_scope("pallas_ssd_scan"):
        if _IMPL["mode"] == "ref" or x.shape[1] <= chunk:
            return _ref.ssd_scan_ref(x, dt, A, B, C, initial_state)
        return _chunked_ssd(x, dt, A, B, C, chunk, initial_state)


def _chunked_ssd(x, dt, A, B, C, chunk, initial_state):
    """Chunkwise SSD (Mamba-2 Sec 6): intra-chunk dense matmuls (MXU work)
    + inter-chunk state recurrence via lax.scan.  Identical math to the
    sequential oracle."""
    bt, s, h, p = x.shape
    if s % chunk:
        return _ref.ssd_scan_ref(x, dt, A, B, C, initial_state)
    n = B.shape[-1]
    nc = s // chunk
    xf = x.astype(jnp.float32).reshape(bt, nc, chunk, h, p)
    dtf = dt.astype(jnp.float32).reshape(bt, nc, chunk, h)
    Bf = B.astype(jnp.float32).reshape(bt, nc, chunk, n)
    Cf = C.astype(jnp.float32).reshape(bt, nc, chunk, n)
    Af = A.astype(jnp.float32)

    # per-step log decay a_t = A*dt_t ; cumulative within chunk
    la = Af[None, None, None, :] * dtf  # (bt,nc,L,h)
    cum = jnp.cumsum(la, axis=2)  # inclusive cumsum_{t'<=t}

    # intra-chunk: y_intra[t] = sum_{u<=t} C_t . B_u dt_u x_u * exp(cum_t - cum_u)
    seg = cum[:, :, :, None, :] - cum[:, :, None, :, :]  # (bt,nc,T,U,h)
    tri = jnp.tril(jnp.ones((chunk, chunk), bool))
    decay = jnp.where(tri[None, None, :, :, None], jnp.exp(seg), 0.0)
    cb = jnp.einsum("bctn,bcun->bctu", Cf, Bf)  # (bt,nc,T,U)
    w = cb[..., None] * decay * dtf[:, :, None, :, :]  # (bt,nc,T,U,h)
    y_intra = jnp.einsum("bctuh,bcuhp->bcthp", w, xf)

    # chunk state contribution: S_c = sum_u exp(cum_L - cum_u) dt_u x_u B_u^T
    tail = jnp.exp(cum[:, :, -1:, :] - cum) * dtf  # (bt,nc,L,h)
    S_c = jnp.einsum("bcuh,bcuhp,bcun->bchpn", tail, xf, Bf)
    chunk_decay = jnp.exp(cum[:, :, -1, :])  # (bt,nc,h)

    h0 = (
        jnp.zeros((bt, h, p, n), jnp.float32)
        if initial_state is None
        else initial_state.astype(jnp.float32)
    )

    def scan_fn(hprev, c):
        hnew = hprev * chunk_decay[:, c][:, :, None, None] + S_c[:, c]
        return hnew, hprev

    hT, hprevs = jax.lax.scan(scan_fn, h0, jnp.arange(nc))
    hprevs = jnp.moveaxis(hprevs, 0, 1)  # (bt,nc,h,p,n) state entering chunk

    # inter-chunk: y_inter[t] = C_t . (exp(cum_t) * h_prev)
    y_inter = jnp.einsum(
        "bcth,bchpn,bctn->bcthp", jnp.exp(cum), hprevs, Cf
    )
    y = (y_intra + y_inter).reshape(bt, s, h, p)
    return y.astype(x.dtype), hT
