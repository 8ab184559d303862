"""Pallas TPU flash-decoding: one query token against a long KV cache.

The serving hot spot of the paper's workloads.  TPU adaptation:
  * the KV sequence is tiled into block_k x d VMEM tiles; the (tiny) query
    tile stays resident; online-softmax accumulators live in VMEM scratch
    across the sequential k grid dimension;
  * all q-heads of one KV group are PACKED into a single (G, d) MXU operand
    so the matmul sees a >=8x128 tile instead of a vector — the
    GQA-packing trick that keeps the MXU busy at decode time;
  * the valid-length mask is a scalar broadcast against the block iota.
"""
from __future__ import annotations

import functools
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary")
)


def _dec_kernel(
    len_ref,  # (B*Hkv, 1) int32 in SMEM — per-row valid length (ragged batch)
    q_ref,  # (1, 1, G, D)
    k_ref,  # (1, block_k, D)
    v_ref,  # (1, block_k, Dv)
    o_ref,  # (1, 1, G, Dv)
    acc_ref, m_ref, l_ref,
    *, scale: float, block_k: int, n_k: int,
):
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    length = len_ref[pl.program_id(0), 0]
    k_start = kj * block_k

    @pl.when(k_start < length)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)  # (G, D)
        k = k_ref[0].astype(jnp.float32)  # (bk, D)
        v = v_ref[0].astype(jnp.float32)  # (bk, Dv)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (G, bk)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(kpos < length, s, NEG_INF)

        m_prev = m_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        p = jnp.where(kpos < length, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[:, :1] = alpha * l_ref[:, :1] + jnp.sum(p, axis=1, keepdims=True)
        m_ref[:, :1] = m_new
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(kj == n_k - 1)
    def _finalize():
        l = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def decode_attention_pallas(
    q: jnp.ndarray,  # (B, 1, Hq, D)
    k: jnp.ndarray,  # (B, Smax, Hkv, D)
    v: jnp.ndarray,  # (B, Smax, Hkv, Dv)
    length,  # int32: valid cache slots — scalar (uniform) or (B,) (ragged)
    block_k: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    b, sq, hq, d = q.shape
    assert sq == 1, "decode kernel takes a single query token"
    smax, hkv, dv = k.shape[1], k.shape[2], v.shape[-1]
    g = hq // hkv
    block_k = min(block_k, smax)
    assert smax % block_k == 0
    n_k = smax // block_k

    qt = q.reshape(b, hkv, g, d)  # pack group heads
    kt = jnp.moveaxis(k, 2, 1).reshape(b * hkv, smax, d)
    vt = jnp.moveaxis(v, 2, 1).reshape(b * hkv, smax, dv)
    qt = qt.reshape(b * hkv, 1, g, d)
    # per-(batch x kv-head) valid length in SMEM; a scalar length broadcasts,
    # a (B,) vector gives each continuous-batching slot its own mask.
    lb = jnp.broadcast_to(jnp.minimum(jnp.asarray(length, jnp.int32), smax), (b,))
    lsc = jnp.repeat(lb, hkv)[:, None]

    kernel = functools.partial(
        _dec_kernel, scale=1.0 / (d ** 0.5), block_k=block_k, n_k=n_k
    )
    out = pl.pallas_call(
        kernel,
        grid=(b * hkv, 1, n_k),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, g, d), lambda bh, z, j: (bh, 0, 0, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, z, j: (bh, j, 0)),
            pl.BlockSpec((1, block_k, dv), lambda bh, z, j: (bh, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, dv), lambda bh, z, j: (bh, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hkv, 1, g, dv), q.dtype),
        scratch_shapes=[_vmem((g, dv)), _vmem((g, 128)), _vmem((g, 128))],
        compiler_params=_PARAMS,
        interpret=interpret,
    )(lsc, qt.reshape(b * hkv, 1, g, d), kt, vt)
    return out.reshape(b, hkv, g, dv).reshape(b, 1, hq, dv)


# ---------------------------------------------------------------------------
# int8-KV variant: dequantize per VMEM tile — HBM KV reads halve
# ---------------------------------------------------------------------------
def _dec_q8_kernel(
    len_ref,  # (B*Hkv, 1) int32 in SMEM
    q_ref,  # (1, 1, G, D)
    kq_ref,  # (1, block_k, D) int8
    ks_ref,  # (1, 1, block_k) f32
    vq_ref,  # (1, block_k, Dv) int8
    vs_ref,  # (1, 1, block_k) f32
    o_ref,  # (1, 1, G, Dv)
    acc_ref, m_ref, l_ref,
    *, scale: float, block_k: int, n_k: int,
):
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    length = len_ref[pl.program_id(0), 0]
    k_start = kj * block_k

    @pl.when(k_start < length)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)  # (G, D)
        k = kq_ref[0].astype(jnp.float32)  # (bk, D) int8 values
        v = vq_ref[0].astype(jnp.float32)  # (bk, Dv) int8 values
        # per-token scales run along the lane (key) axis of the score tile:
        # q.(k_u * ks_u) == (q.k_u) * ks_u, and p @ (v * vs) == (p * vs) @ v
        ks = ks_ref[0]  # (1, bk)
        vs = vs_ref[0]  # (1, bk)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * (ks * scale)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(kpos < length, s, NEG_INF)
        m_prev = m_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        p = jnp.where(kpos < length, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[:, :1] = alpha * l_ref[:, :1] + jnp.sum(p, axis=1, keepdims=True)
        m_ref[:, :1] = m_new
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p * vs, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(kj == n_k - 1)
    def _finalize():
        l = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def decode_attention_q8_pallas(
    q: jnp.ndarray,  # (B, 1, Hq, D)
    k_q: jnp.ndarray,  # (B, Smax, Hkv, D) int8
    k_s: jnp.ndarray,  # (B, Smax, Hkv) f32
    v_q: jnp.ndarray,
    v_s: jnp.ndarray,
    length,  # scalar or (B,) int32
    block_k: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    b, sq, hq, d = q.shape
    assert sq == 1
    smax, hkv, dv = k_q.shape[1], k_q.shape[2], v_q.shape[-1]
    g = hq // hkv
    block_k = min(block_k, smax)
    assert smax % block_k == 0
    n_k = smax // block_k

    qt = q.reshape(b, hkv, g, d).reshape(b * hkv, 1, g, d)
    kt = jnp.moveaxis(k_q, 2, 1).reshape(b * hkv, smax, d)
    vt = jnp.moveaxis(v_q, 2, 1).reshape(b * hkv, smax, dv)
    # scales as (rows, 1, Smax): a (1, block_k) tile per block is TPU-tileable
    kst = jnp.moveaxis(k_s, 2, 1).reshape(b * hkv, 1, smax)
    vst = jnp.moveaxis(v_s, 2, 1).reshape(b * hkv, 1, smax)
    lb = jnp.broadcast_to(jnp.minimum(jnp.asarray(length, jnp.int32), smax), (b,))
    lsc = jnp.repeat(lb, hkv)[:, None]

    kernel = functools.partial(
        _dec_q8_kernel, scale=1.0 / (d ** 0.5), block_k=block_k, n_k=n_k
    )
    out = pl.pallas_call(
        kernel,
        grid=(b * hkv, 1, n_k),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, g, d), lambda bh, z, j: (bh, 0, 0, 0)),
            pl.BlockSpec((1, block_k, d), lambda bh, z, j: (bh, j, 0)),
            pl.BlockSpec((1, 1, block_k), lambda bh, z, j: (bh, 0, j)),
            pl.BlockSpec((1, block_k, dv), lambda bh, z, j: (bh, j, 0)),
            pl.BlockSpec((1, 1, block_k), lambda bh, z, j: (bh, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, dv), lambda bh, z, j: (bh, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hkv, 1, g, dv), q.dtype),
        scratch_shapes=[_vmem((g, dv)), _vmem((g, 128)), _vmem((g, 128))],
        compiler_params=_PARAMS,
        interpret=interpret,
    )(lsc, qt, kt, kst, vt, vst)
    return out.reshape(b, hkv, g, dv).reshape(b, 1, hq, dv)


def _vmem(shape):
    return pltpu.VMEM(shape, jnp.float32)
