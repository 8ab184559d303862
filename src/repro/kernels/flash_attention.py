"""Pallas TPU flash attention (forward), GQA-aware, causal + sliding window.

TPU adaptation notes (vs. the CUDA flash-attention algorithm):
  * tiling is chosen for VMEM and the 128x128 MXU: block_q x d and
    block_k x d tiles stream HBM->VMEM while the online-softmax accumulators
    (acc, m, l) live in VMEM scratch across the k-block grid dimension;
  * the k-block loop is the innermost grid dimension with "arbitrary"
    semantics (sequential), q/head/batch dims are parallel;
  * GQA is handled in the BlockSpec index map: query head h reads kv head
    h // group — no materialized key/value replication.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _fa_kernel(
    q_ref, k_ref, v_ref, o_ref,
    acc_ref, m_ref, l_ref,
    *, scale: float, block_q: int, block_k: int, n_k: int,
    causal: bool, window: Optional[int],
):
    qi = pl.program_id(2)
    kj = pl.program_id(3)

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = qi * block_q
    k_start = kj * block_k

    # skip blocks that are fully masked out (above the causal diagonal /
    # outside the sliding window)
    run = True
    if causal:
        run = k_start <= q_start + block_q - 1
    if window is not None:
        run = jnp.logical_and(run, k_start + block_k - 1 > q_start - window)

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)  # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)  # (bk, d)
        v = v_ref[0, 0].astype(jnp.float32)  # (bk, dv)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (bq, bk)

        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        mask = jnp.ones_like(s, dtype=jnp.bool_)
        if causal:
            mask = jnp.logical_and(mask, kpos <= qpos)
        if window is not None:
            mask = jnp.logical_and(mask, kpos > qpos - window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[:, :1]  # (bq, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)  # (bq,1)
        l_ref[:, :1] = alpha * l_ref[:, :1] + jnp.sum(p, axis=1, keepdims=True)
        m_ref[:, :1] = m_new
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(kj == n_k - 1)
    def _finalize():
        l = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0, 0, :, :] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "sliding_window", "block_q", "block_k", "interpret"),
)
def flash_attention_pallas(
    q: jnp.ndarray,  # (B, S, Hq, D)
    k: jnp.ndarray,  # (B, S, Hkv, D)
    v: jnp.ndarray,  # (B, S, Hkv, Dv)
    causal: bool = True,
    sliding_window: Optional[int] = None,
    block_q: int = 256,
    block_k: int = 256,
    interpret: bool = False,
) -> jnp.ndarray:
    b, s, hq, d = q.shape
    hkv, dv = k.shape[2], v.shape[-1]
    g = hq // hkv
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    assert s % block_q == 0 and s % block_k == 0
    n_q, n_k = s // block_q, s // block_k

    # layout: (B, H, S, D) blocks
    qt = jnp.moveaxis(q, 2, 1)
    kt = jnp.moveaxis(k, 2, 1)
    vt = jnp.moveaxis(v, 2, 1)

    kernel = functools.partial(
        _fa_kernel,
        scale=1.0 / (d ** 0.5),
        block_q=block_q,
        block_k=block_k,
        n_k=n_k,
        causal=causal,
        window=sliding_window,
    )
    out = pl.pallas_call(
        kernel,
        grid=(b, hq, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda bb, h, i, j: (bb, h, i, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda bb, h, i, j: (bb, h // g, j, 0)),
            pl.BlockSpec((1, 1, block_k, dv), lambda bb, h, i, j: (bb, h // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, dv), lambda bb, h, i, j: (bb, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hq, s, dv), q.dtype),
        scratch_shapes=[
            _vmem((block_q, dv)),
            _vmem((block_q, 128)),
            _vmem((block_q, 128)),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(qt, kt, vt)
    return jnp.moveaxis(out, 1, 2)  # back to (B, S, Hq, Dv)


def _vmem(shape):
    return pltpu.VMEM(shape, jnp.float32)
