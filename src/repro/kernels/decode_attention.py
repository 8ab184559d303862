"""Pallas TPU flash-decoding: one query token against a long KV cache.

The serving hot spot of the paper's workloads.  TPU adaptation:
  * the cache is sequence-minor, (B, Hkv, D, S) per layer (models/layers.py),
    so the KV sequence is tiled into d x block_k VMEM tiles with the keys on
    the lanes; the (tiny) query tile stays resident; online-softmax
    accumulators live in VMEM scratch across the sequential k grid dimension;
  * all q-heads of one KV group are PACKED into a single (G, d) MXU operand
    so the matmul sees a >=8x128 tile instead of a vector — the
    GQA-packing trick that keeps the MXU busy at decode time;
  * the valid-length mask is a scalar broadcast against the block iota;
  * given this step's token (``new``), not yet written, the kernel leaves
    out the slot that the token will overwrite and opens its online softmax
    with the token itself;
  * given ``layer``, k/v are the decode loop's whole layer stack
    (L, B, Hkv, D, S): the layer index is prefetched into SMEM and the block
    index maps read that layer's tiles in place, so no layer is sliced out.
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
    lyr_ref,  # (1,) int32 in SMEM — the layer (read by the index maps)
    len_ref,  # (B*Hkv,) int32 in SMEM — per-row valid length (ragged batch)
    pos_ref,  # (B*Hkv,) int32 in SMEM — per-row slot left out (-1: none)
    q_ref,  # (1, 1, G, D)
    *refs,  # k (1, D, bk), [k scales (1, 1, bk)], v (1, Dv, bk), [v scales],
            # [the new token's k (1, 1, D), v (1, 1, Dv)], o (1, 1, G, Dv),
            # then the scratch acc, m, l
    scale: float, block_k: int, n_k: int, quant: bool, fresh: bool,
):
    del lyr_ref
    it = iter(refs)
    k_ref = next(it)
    ks_ref = next(it) if quant else None
    v_ref = next(it)
    vs_ref = next(it) if quant else None
    kn_ref, vn_ref = (next(it), next(it)) if fresh else (None, None)
    o_ref, acc_ref, m_ref, l_ref = it
    row, kj = pl.program_id(0), pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        if fresh:
            # the new token opens the online softmax: weight 1 on its value
            q = q_ref[0, 0].astype(jnp.float32)  # (G, D)
            s_new = jnp.sum(q * kn_ref[0].astype(jnp.float32), axis=1,
                            keepdims=True) * scale  # (G, 1)
            m_ref[...] = jnp.broadcast_to(s_new, m_ref.shape)
            l_ref[...] = jnp.ones_like(l_ref)
            acc_ref[...] = jnp.broadcast_to(vn_ref[0].astype(jnp.float32), acc_ref.shape)
        else:
            acc_ref[...] = jnp.zeros_like(acc_ref)
            m_ref[...] = jnp.full_like(m_ref, NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)

    length = len_ref[row]
    k_start = kj * block_k

    @pl.when(k_start < length)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)  # (G, D)
        k = k_ref[0].astype(jnp.float32)  # (D, bk)
        v = v_ref[0].astype(jnp.float32)  # (Dv, bk)
        # per-token int8 scales run along the lane (key) axis of the score
        # tile: q.(k_u * ks_u) == (q.k_u) * ks_u, p @ (v * vs) == (p * vs) @ v
        s = jax.lax.dot_general(
            q, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        ) * (ks_ref[0] * scale if quant else scale)  # (G, bk)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        keep = (kpos < length) & (kpos != pos_ref[row])
        s = jnp.where(keep, s, NEG_INF)

        m_prev = m_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        p = jnp.where(keep, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[:, :1] = alpha * l_ref[:, :1] + jnp.sum(p, axis=1, keepdims=True)
        m_ref[:, :1] = m_new
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p * vs_ref[0] if quant else p, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(kj == n_k - 1)
    def _finalize():
        l = jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def _decode_call(q, length, layer, new, kv_leaves, dv, block_k, interpret):
    """Run the kernel over rows of (batch x kv-head).  ``kv_leaves`` are
    cache leaves (B, Hkv, ..., S), or layer stacks of them when ``layer`` is
    given, each viewed as rows (L*B*Hkv, X, S) without a copy: (k, v), or
    (k, k scales, v, v scales) for an int8 cache."""
    b, sq, hq, d = q.shape
    assert sq == 1, "decode kernel takes a single query token"
    k = kv_leaves[0]
    hkv, smax = k.shape[-3], k.shape[-1]
    g, rows = hq // hkv, b * hkv
    block_k = min(block_k, smax)
    assert smax % block_k == 0
    n_k = smax // block_k

    def per_row(x):  # scalar or (B,) -> (B*Hkv,) int32
        return jnp.repeat(jnp.broadcast_to(jnp.asarray(x, jnp.int32), (b,)), hkv)

    lyr = jnp.reshape(jnp.asarray(0 if layer is None else layer, jnp.int32), (1,))
    # per-(batch x kv-head) valid length in SMEM; a scalar length broadcasts,
    # a (B,) vector gives each continuous-batching slot its own mask.
    lens = per_row(jnp.minimum(jnp.asarray(length, jnp.int32), smax))
    pos = per_row(-1 if new is None else new[2])

    def tiles(x):
        x = x.reshape((-1, 1, smax) if x.ndim == k.ndim - 1 else (-1,) + x.shape[-2:])
        spec = pl.BlockSpec(
            (1, x.shape[1], block_k),
            lambda bh, z, j, ly, ln, ps: (ly[0] * rows + bh, 0, j),
        )
        return x, spec

    ins, specs = map(list, zip(*map(tiles, kv_leaves)))
    if new is not None:
        for x in new[:2]:  # (B, Hkv, X) -> rows (B*Hkv, 1, X)
            ins.append(x.reshape(rows, 1, x.shape[-1]))
            specs.append(pl.BlockSpec(
                (1, 1, x.shape[-1]), lambda bh, z, j, ly, ln, ps: (bh, 0, 0)))
    kernel = functools.partial(
        _dec_kernel, scale=1.0 / (d ** 0.5), block_k=block_k, n_k=n_k,
        quant=len(kv_leaves) == 4, fresh=new is not None,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(rows, 1, n_k),
            in_specs=[
                pl.BlockSpec((1, 1, g, d), lambda bh, z, j, ly, ln, ps: (bh, 0, 0, 0)),
                *specs,
            ],
            out_specs=pl.BlockSpec(
                (1, 1, g, dv), lambda bh, z, j, ly, ln, ps: (bh, 0, 0, 0)),
            scratch_shapes=[_vmem((g, dv)), _vmem((g, 128)), _vmem((g, 128))],
        ),
        out_shape=jax.ShapeDtypeStruct((rows, 1, g, dv), q.dtype),
        compiler_params=_PARAMS,
        interpret=interpret,
    )(lyr, lens, pos, q.reshape(rows, 1, g, d), *ins)
    return out.reshape(b, 1, hq, dv)


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def decode_attention_pallas(
    q: jnp.ndarray,  # (B, 1, Hq, D)
    k: jnp.ndarray,  # (B, Hkv, D, Smax), or (L, B, Hkv, D, Smax) with layer
    v: jnp.ndarray,  # (B, Hkv, Dv, Smax), likewise
    length,  # int32: valid cache slots — scalar (uniform) or (B,) (ragged)
    block_k: int = 512,
    interpret: bool = False,
    layer=None,  # int32 scalar: the layer of a stacked k/v to attend over
    new=None,  # (k_new (B,Hkv,D), v_new (B,Hkv,Dv), pos), as in the oracle
) -> jnp.ndarray:
    return _decode_call(q, length, layer, new, (k, v), v.shape[-2], block_k, interpret)


# ---------------------------------------------------------------------------
# int8-KV variant: dequantize per VMEM tile — HBM KV reads halve
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def decode_attention_q8_pallas(
    q: jnp.ndarray,  # (B, 1, Hq, D)
    k_q: jnp.ndarray,  # (B, Hkv, D, Smax) int8, or a layer stack with layer
    k_s: jnp.ndarray,  # (B, Hkv, Smax) f32
    v_q: jnp.ndarray,
    v_s: jnp.ndarray,
    length,  # scalar or (B,) int32
    block_k: int = 512,
    interpret: bool = False,
    layer=None,
    new=None,  # as in decode_attention_pallas, the token dequantized
) -> jnp.ndarray:
    # scales as rows (rows, 1, Smax): a (1, block_k) tile per block is
    # TPU-tileable
    return _decode_call(
        q, length, layer, new, (k_q, k_s, v_q, v_s), v_q.shape[-2], block_k, interpret
    )


def _vmem(shape):
    return pltpu.VMEM(shape, jnp.float32)
