"""Expert-parallel MoE over the 'model' mesh axis (production path).

The baseline "dispatch" implementation (models/moe.py) pays two dense
(T x E*C x D) one-hot einsums per MoE layer — O(T * T*k*cf * D) FLOPs, which
is why dispatch-MoE cells show useful-FLOPs ratios under 0.1.  This module
replaces dispatch/combine with sort + scatter/gather bookkeeping inside a
``jax.shard_map`` over the model axis:

  * activations enter replicated across 'model' (the TP convention between
    blocks), token-sharded across the data axes;
  * each device builds capacity-bounded buffers for the experts IT OWNS
    (argsort by expert id, positions via searchsorted — O(T k log(Tk))
    bookkeeping, zero matmul FLOPs);
  * per-device expert FFN on (E_local, C, D) — the only dense compute;
  * combine = scatter-add back to token slots + ``psum`` over 'model'
    (one (T_local, D) all-reduce, the same wire cost as a TP MLP).

Expert/mesh shape handling:
  * E >= m ("model" size): E_local = E/m experts per device (DeepSeek-V3:
    256 experts over 16 -> 16/device);
  * E <  m: each expert is REPLICATED over rep = m/E devices with its FFN
    hidden dim F split rep ways (expert+tensor hybrid; Mixtral: 8 experts
    over 16 -> every expert on 2 devices with F/2 each).  The closing psum
    sums the TP partials and the EP combine in one collective.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..configs.base import ArchConfig

__all__ = ["apply_moe_alltoall"]


def _mesh_info():
    from . import sharding

    ctx = sharding.current()
    if ctx is None:
        return None, (), 1, 1
    mesh = ctx["mesh"]
    daxes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    dp = 1
    for a in daxes:
        dp *= mesh.shape[a]
    m = mesh.shape.get("model", 1)
    return mesh, daxes, dp, m


def _local_moe(xt, gates, eidx, wg, wu, wo, *, e_local: int, rep: int,
               cap: int, k: int):
    """Per-device EP MoE: xt (Tl,D) replicated over 'model', token-sharded
    over data; wg/wu/wo are THIS device's expert slices (E_local, D, Fl)."""
    t, d = xt.shape
    r = jax.lax.axis_index("model")
    e_lo = (r // rep) * e_local  # first global expert owned here

    # ---- dispatch bookkeeping (sort + positions; no matmuls) -------------
    ef = eidx.reshape(-1)  # (T*k,) global expert ids
    mine = (ef >= e_lo) & (ef < e_lo + e_local)
    key = jnp.where(mine, ef - e_lo, e_local)  # foreign -> sentinel bucket
    order = jnp.argsort(key, stable=True)
    se = key[order]  # sorted local-expert ids (sentinel last)
    seg_start = jnp.searchsorted(se, jnp.arange(e_local + 1))
    pos = jnp.arange(t * k) - seg_start[jnp.clip(se, 0, e_local)]
    keep = (se < e_local) & (pos < cap)
    src_tok = order // k

    # scatter tokens into (E_local, C, D); out-of-bounds rows are dropped
    e_idx = jnp.where(keep, se, e_local)
    c_idx = jnp.where(keep, pos, 0)
    buf = jnp.zeros((e_local, cap, d), xt.dtype)
    buf = buf.at[e_idx, c_idx].set(
        jnp.where(keep[:, None], xt[src_tok], 0).astype(xt.dtype),
        mode="drop",
    )

    # ---- expert FFN (the only dense compute) ------------------------------
    g = jnp.einsum("ecd,edf->ecf", buf, wg)
    u = jnp.einsum("ecd,edf->ecf", buf, wu)
    a = jax.nn.silu(g.astype(jnp.float32)).astype(u.dtype) * u
    out = jnp.einsum("ecf,efd->ecd", a, wo)  # (E_local, C, D)

    # ---- combine: gather back + weighted scatter-add by token -------------
    vals = out[jnp.clip(e_idx, 0, e_local - 1), c_idx]  # (T*k, D)
    gsort = gates.reshape(-1)[order]
    w = jnp.where(keep, gsort, 0.0).astype(jnp.float32)
    y = jnp.zeros((t, d), jnp.float32).at[src_tok].add(vals.astype(jnp.float32) * w[:, None])
    return jax.lax.psum(y, "model").astype(xt.dtype)


def apply_moe_alltoall(
    p: Dict[str, Any], xt: jnp.ndarray, gates: jnp.ndarray,
    eidx: jnp.ndarray, cfg: ArchConfig
) -> jnp.ndarray:
    mesh, daxes, dp, m = _mesh_info()
    e, k = cfg.n_experts, cfg.experts_per_token
    experts = p["experts"]
    if mesh is None or "model" not in mesh.axis_names or (e % m and m % e):
        # no EP mesh (or incompatible expert count): grouped dispatch
        from ..models.moe import _apply_dispatch

        return _apply_dispatch(p, xt, gates, eidx, cfg)

    t = xt.shape[0]
    if t % dp:
        dp, daxes = 1, ()  # tiny batch (e.g. long-context decode): replicate
    t_local = max(1, t // dp)
    e_local = max(1, e // m)
    rep = max(1, m // e)
    cap = max(4, int(math.ceil(t_local * k / e * cfg.capacity_factor)))
    cap = min(cap, t_local * k)

    wg, wu, wo = experts["w_gate"], experts["w_up"], experts["w_out"]
    if rep > 1:  # expert+tensor hybrid: split F over rep replicas
        ef, d_, f_ = wg.shape
        wg = wg.reshape(ef, d_, rep, f_ // rep).transpose(0, 2, 1, 3).reshape(ef * rep, d_, f_ // rep)
        wu = wu.reshape(ef, d_, rep, f_ // rep).transpose(0, 2, 1, 3).reshape(ef * rep, d_, f_ // rep)
        wo = wo.reshape(ef, rep, f_ // rep, d_).reshape(ef * rep, f_ // rep, d_)

    tok_spec = P(daxes if len(daxes) > 1 else (daxes[0] if daxes else None))
    fn = partial(_local_moe, e_local=e_local, rep=rep, cap=cap, k=k)
    return jax.shard_map(
        fn,
        mesh=mesh,
        in_specs=(
            P(*tok_spec, None), P(*tok_spec, None), P(*tok_spec, None),
            P("model", None, None), P("model", None, None), P("model", None, None),
        ),
        out_specs=P(*tok_spec, None),
        check_vma=False,
    )(xt, gates, eidx, wg, wu, wo)
