"""Pallas TPU chunked Mamba-2 SSD scan.

TPU adaptation of the GPU SSD algorithm: instead of warp-level parallel
scans, the sequence is tiled into L-step chunks; within a chunk everything
is dense (chunk x chunk and chunk x state matmuls on the MXU), and the
inter-chunk recurrence is the innermost sequential grid dimension carrying
the (P x N) state in VMEM scratch.  Grid: (batch, heads, chunks).

Layout: heads go ahead of the sequence, so every block's last two dims are
(chunk, P) / (1, chunk) / (chunk, 1) tiles the TPU lowering accepts; the
per-head decays ``A`` sit whole in SMEM.  Cumulative sums are masked
reductions of the step decays, once per orientation (column for the query
axis, row for the key axis), so the kernel needs no in-kernel transpose.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(
    a_ref,  # (H,) f32 in SMEM: A of every head
    x_ref,  # (1, 1, chunk, P)
    dtr_ref,  # (1, 1, 1, chunk) f32: dt as a row
    dtc_ref,  # (1, 1, chunk, 1) f32: dt as a column
    b_ref,  # (1, chunk, N)
    c_ref,  # (1, chunk, N)
    h0_ref,  # (1, 1, P, N) initial state
    y_ref,  # (1, 1, chunk, P)
    hT_ref,  # (1, 1, P, N) final state
    state_ref,  # VMEM scratch (P, N)
    *, chunk: int, n_chunks: int,
):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = h0_ref[0, 0].astype(jnp.float32)

    A = a_ref[pl.program_id(1)]
    x = x_ref[0, 0].astype(jnp.float32)  # (L, P)
    dt_row = dtr_ref[0, 0]  # (1, L)
    dt_col = dtc_ref[0, 0]  # (L, 1)
    Bm = b_ref[0].astype(jnp.float32)  # (L, N)
    Cm = c_ref[0].astype(jnp.float32)  # (L, N)

    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    tri = col <= row  # tri[t, u] = u <= t
    la_row = A * dt_row
    la_col = A * dt_col
    # inclusive cumulative log decay, as a column (t) and as a row (u)
    cum_col = jnp.sum(jnp.where(tri, la_row, 0.0), axis=1, keepdims=True)  # (L,1)
    cum_row = jnp.sum(jnp.where(row <= col, la_col, 0.0), axis=0, keepdims=True)  # (1,L)
    total = jnp.sum(la_col, axis=0, keepdims=True)  # (1,1) = cum at chunk end

    # intra-chunk: w[t,u] = (C_t.B_u) * exp(cum_t - cum_u) * dt_u,  u <= t
    decay = jnp.where(tri, jnp.exp(cum_col - cum_row), 0.0)
    cb = jax.lax.dot_general(
        Cm, Bm, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (T,U)
    w = cb * decay * dt_row
    y_intra = jax.lax.dot_general(
        w, x, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )  # (T,P)

    # inter-chunk: y_inter[t] = exp(cum_t) * C_t @ state^T
    h_prev = state_ref[...]  # (P,N)
    y_inter = jnp.exp(cum_col) * jax.lax.dot_general(
        Cm, h_prev, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (T,P)
    y_ref[0, 0] = (y_intra + y_inter).astype(y_ref.dtype)

    # state update: h = h*exp(cum_L) + sum_u exp(cum_L - cum_u) dt_u x_u B_u^T
    tail = jnp.exp(total - cum_col) * dt_col  # (L,1)
    upd = jax.lax.dot_general(
        x * tail, Bm, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )  # (P,N)
    state_ref[...] = h_prev * jnp.exp(total) + upd

    @pl.when(ci == n_chunks - 1)
    def _emit_state():
        hT_ref[0, 0] = state_ref[...]


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan_pallas(
    x: jnp.ndarray,  # (B, S, H, P)
    dt: jnp.ndarray,  # (B, S, H) f32
    A: jnp.ndarray,  # (H,) f32 (negative)
    B: jnp.ndarray,  # (B, S, N)
    C: jnp.ndarray,  # (B, S, N)
    chunk: int = 256,
    initial_state: Optional[jnp.ndarray] = None,  # (B, H, P, N) f32
    interpret: bool = False,
):
    bt, s, h, p = x.shape
    n = B.shape[-1]
    chunk = min(chunk, s)
    assert s % chunk == 0
    nc = s // chunk
    if initial_state is None:
        initial_state = jnp.zeros((bt, h, p, n), jnp.float32)

    xt = jnp.moveaxis(x, 2, 1)  # (B, H, S, P)
    dth = jnp.moveaxis(dt.astype(jnp.float32), 2, 1)  # (B, H, S)
    kernel = functools.partial(_ssd_kernel, chunk=chunk, n_chunks=nc)
    y, hT = pl.pallas_call(
        kernel,
        grid=(bt, h, nc),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, chunk, p), lambda b_, h_, c_: (b_, h_, c_, 0)),
            pl.BlockSpec((1, 1, 1, chunk), lambda b_, h_, c_: (b_, h_, 0, c_)),
            pl.BlockSpec((1, 1, chunk, 1), lambda b_, h_, c_: (b_, h_, c_, 0)),
            pl.BlockSpec((1, chunk, n), lambda b_, h_, c_: (b_, c_, 0)),
            pl.BlockSpec((1, chunk, n), lambda b_, h_, c_: (b_, c_, 0)),
            pl.BlockSpec((1, 1, p, n), lambda b_, h_, c_: (b_, h_, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, p), lambda b_, h_, c_: (b_, h_, c_, 0)),
            pl.BlockSpec((1, 1, p, n), lambda b_, h_, c_: (b_, h_, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bt, h, s, p), x.dtype),
            jax.ShapeDtypeStruct((bt, h, p, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(
        A.astype(jnp.float32).reshape(h),
        xt,
        dth[:, :, None, :],
        dth[..., None],
        B,
        C,
        initial_state,
    )
    return jnp.moveaxis(y, 1, 2), hT
