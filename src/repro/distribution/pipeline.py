"""GPipe pipeline parallelism over the 'pod' mesh axis.

The multi-pod mesh's ``pod`` axis is the DCN boundary: inter-pod links are
an order of magnitude slower than intra-pod ICI, so the only traffic that
belongs on them is (a) data-parallel gradient reduction or (b) pipeline
activations.  This module provides (b): layers are split into one stage per
pod; microbatches stream through stages with ``ppermute`` handoffs (the
GPipe fill/drain schedule).

``jax.shard_map`` is manual over ONLY the stage axis — inside a stage the
usual GSPMD data/model sharding still applies, so PP composes with DP/TP.

  y = gpipe(stage_fn, stage_params, x, mesh=mesh, n_micro=4)

stage_params: pytree whose leaves have a leading ``n_stages`` dim (sharded
over 'pod').  stage_fn(params_one_stage, x_mb) -> y_mb applies ONE stage.
x: (n_micro, mb, ...) microbatched inputs, replicated over 'pod'.
Bubble fraction is the GPipe (S-1)/(S-1+M); pick n_micro >> n_stages.
"""
from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


__all__ = ["gpipe"]


def gpipe(
    stage_fn: Callable[[Any, jnp.ndarray], jnp.ndarray],
    stage_params: Any,
    x: jnp.ndarray,
    *,
    mesh,
    n_micro: int,
    stage_axis: str = "pod",
) -> jnp.ndarray:
    n_stages = mesh.shape[stage_axis]
    assert x.shape[0] == n_micro, "x must be (n_micro, mb, ...)"
    if n_stages == 1:
        def seq(params, xs):
            def body(h, p):
                return jax.vmap(stage_fn, in_axes=(None, 0))(p, h), None
            # params leaves: (1, ...) -> apply the single stage per microbatch
            p0 = jax.tree.map(lambda a: a[0], params)
            return jax.vmap(stage_fn, in_axes=(None, 0))(p0, xs)
        return seq(stage_params, x)

    perm = [(i, i + 1) for i in range(n_stages - 1)]

    def local(params_st, xs):
        # params_st leaves: (1, ...) — this rank's stage
        p_local = jax.tree.map(lambda a: a[0], params_st)
        r = jax.lax.axis_index(stage_axis)
        total = n_micro + n_stages - 1
        buf = jnp.zeros_like(xs[0])
        y = jnp.zeros_like(xs)

        def step(t, carry):
            buf, y = carry
            # stage 0 ingests microbatch t (while available); others use buf
            feed_idx = jnp.clip(t, 0, n_micro - 1)
            inp = jnp.where(r == 0, xs[feed_idx], buf)
            out = stage_fn(p_local, inp)
            # hand off to the next stage over the DCN link
            nxt = jax.lax.ppermute(out, stage_axis, perm)
            # last stage emits microbatch t-(S-1)
            oidx = t - (n_stages - 1)
            upd = jax.lax.dynamic_update_slice_in_dim(
                y, out[None], jnp.clip(oidx, 0, n_micro - 1), axis=0
            )
            y = jnp.where((r == n_stages - 1) & (oidx >= 0), upd, y)
            return nxt, y

        buf, y = jax.lax.fori_loop(0, total, step, (buf, y))
        # results live on the last stage; broadcast so out_specs can be
        # replicated over the stage axis (callers usually reduce right after)
        return jax.lax.psum(
            jnp.where(r == n_stages - 1, y, jnp.zeros_like(y)), stage_axis
        )

    pspec = jax.tree.map(lambda _: P(stage_axis), stage_params)
    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(pspec, P()),
        out_specs=P(),
        axis_names={stage_axis},
        check_vma=False,
    )(stage_params, x)
