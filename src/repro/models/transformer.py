"""Composable LM: decoder-only / encoder-decoder stacks over heterogeneous
block types (attention, MoE, Mamba-2, mLSTM, sLSTM, Zamba shared-attention).

Layers of one kind are *stacked* (leading L dim) and executed with
``jax.lax.scan`` so compile time is O(#block kinds), not O(#layers) — a hard
requirement for 61-96-layer configs.  Hybrid archs (Zamba2) split their runs
at shared-attention boundaries, so the weight-shared block is applied between
scans without unrolling the backbone.

The same apply code serves three modes:
  * train   — full-sequence causal, no cache
  * prefill — full-sequence causal, cache written and returned
  * decode  — one token against the cache/state
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig
from . import layers, moe, ssm

Params = Dict[str, Any]

#: activation rematerialization for the layer scans: None (save everything)
#: or "block" (save only the residual stream between layers; recompute the
#: block interior in the backward pass).
_REMAT = {"mode": None}


def set_remat(mode: Optional[str]) -> None:
    assert mode in (None, "block")
    _REMAT["mode"] = mode


def _maybe_remat(fn):
    if _REMAT["mode"] == "block":
        return jax.checkpoint(fn, prevent_cse=False)
    return fn


# ---------------------------------------------------------------------------
# per-block init / apply
# ---------------------------------------------------------------------------
def _init_block(key, kind: str, cfg: ArchConfig, dtype, dense_mlp: bool) -> Params:
    ks = jax.random.split(key, 4)
    if kind in ("attn", "moe"):
        p: Params = {
            "ln1": layers.init_norm(cfg.d_model, dtype, cfg.norm),
            "ln2": layers.init_norm(cfg.d_model, dtype, cfg.norm),
        }
        if cfg.attention == "mla":
            p["attn"] = layers.init_mla(ks[0], cfg, dtype)
        else:
            p["attn"] = layers.init_attention(ks[0], cfg, dtype)
        if kind == "moe":
            p["moe"] = moe.init_moe(ks[1], cfg, dtype)
        else:
            p["mlp"] = layers.init_mlp(ks[1], cfg.d_model, cfg.d_ff, cfg.mlp, dtype)
        return p
    if kind == "mamba2":
        return {
            "ln1": layers.init_norm(cfg.d_model, dtype, cfg.norm),
            "mixer": ssm.init_mamba2(ks[0], cfg, dtype),
        }
    if kind == "mlstm":
        return {
            "ln1": layers.init_norm(cfg.d_model, dtype, cfg.norm),
            "mixer": ssm.init_mlstm(ks[0], cfg, dtype),
        }
    if kind == "slstm":
        return {
            "ln1": layers.init_norm(cfg.d_model, dtype, cfg.norm),
            "mixer": ssm.init_slstm(ks[0], cfg, dtype),
        }
    raise ValueError(kind)


def _apply_block(
    p: Params,
    x: jnp.ndarray,
    kind: str,
    cfg: ArchConfig,
    positions: jnp.ndarray,
    cache: Optional[Params],
    layer: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, Optional[Params], jnp.ndarray]:
    """Returns (x_out, new_cache, aux_loss).  With ``layer`` (a decode step
    inside the layer loop), ``cache`` is the group's whole layer stack, read
    in place, and new_cache only what layer ``layer`` changed: its pending
    token (models/layers.commit_tokens) or a recurrent layer's new state."""
    aux = jnp.zeros((), jnp.float32)
    if kind in ("attn", "moe"):
        h = layers.apply_norm(p["ln1"], x, cfg.norm)
        attn_cache = cache["attn"] if cache is not None else None
        attend = layers.mla_attention if cfg.attention == "mla" else layers.attention
        a, attn_cache = attend(p["attn"], h, cfg, positions, attn_cache, layer=layer)
        x = x + a
        h = layers.apply_norm(p["ln2"], x, cfg.norm)
        if kind == "moe":
            y, aux = moe.apply_moe(p["moe"], h, cfg)
        else:
            y = layers.apply_mlp(p["mlp"], h, cfg.mlp)
        x = x + y
        new_cache = {"attn": attn_cache} if cache is not None else None
        return x, new_cache, aux
    # recurrent mixers
    h = layers.apply_norm(p["ln1"], x, cfg.norm)
    mix_state = cache["mixer"] if cache is not None else None
    if layer is not None:
        mix_state = jax.tree.map(lambda a: a[layer], mix_state)
    fn = {"mamba2": ssm.mamba2_block, "mlstm": ssm.mlstm_block, "slstm": ssm.slstm_block}[kind]
    y, mix_state = fn(p["mixer"], h, cfg, mix_state)
    x = x + y
    new_cache = {"mixer": mix_state} if cache is not None else None
    return x, new_cache, aux


# ---------------------------------------------------------------------------
# caches
# ---------------------------------------------------------------------------
def _attn_cache(cfg: ArchConfig, batch: int, max_len: int, dtype, ragged=False):
    """One layer's cache.  Every leaf but ``index`` keeps its sequence axis
    last (models/layers.py says why)."""
    idx = jnp.zeros((batch,) if ragged else (), jnp.int32)
    if cfg.attention == "mla":
        return {
            "c_kv": jnp.zeros((batch, cfg.kv_lora_rank, max_len), dtype),
            "k_pe": jnp.zeros((batch, cfg.qk_rope_head_dim, max_len), dtype),
            "index": idx,
        }
    s = min(max_len, cfg.sliding_window) if cfg.sliding_window else max_len
    kv = (batch, cfg.n_kv_heads, cfg.head_dim_, s)
    if layers.kv_quant_enabled() and not cfg.sliding_window:
        # int8 KV + per-(token, head) scales (serving lever, §Perf C3)
        return {
            "k": jnp.zeros(kv, jnp.int8),
            "k_s": jnp.zeros((batch, cfg.n_kv_heads, s), jnp.float32),
            "v": jnp.zeros(kv, jnp.int8),
            "v_s": jnp.zeros((batch, cfg.n_kv_heads, s), jnp.float32),
            "index": idx,
        }
    return {"k": jnp.zeros(kv, dtype), "v": jnp.zeros(kv, dtype), "index": idx}


def _block_cache(kind: str, cfg: ArchConfig, batch: int, max_len: int, dtype,
                 ragged=False):
    if kind in ("attn", "moe"):
        return {"attn": _attn_cache(cfg, batch, max_len, dtype, ragged)}
    if kind == "mamba2":
        return {"mixer": ssm.init_mamba_state(cfg, batch, dtype)}
    if kind == "mlstm":
        return {"mixer": ssm.init_mlstm_state(cfg, batch)}
    if kind == "slstm":
        return {"mixer": ssm.init_slstm_state(cfg, batch)}
    raise ValueError(kind)


def _stack(n: int, tree):
    return jax.tree.map(lambda x: jnp.broadcast_to(x, (n,) + x.shape).copy(), tree)


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ArchConfig

    def _groups(self) -> Tuple[Tuple[str, int], ...]:
        """Layer runs, split at shared-attention boundaries for hybrids."""
        cfg = self.cfg
        runs = cfg.layer_groups()
        if not cfg.shared_attn_every:
            return runs
        out: List[Tuple[str, int]] = []
        for kind, count in runs:
            while count > 0:
                take = min(cfg.shared_attn_every, count)
                out.append((kind, take))
                count -= take
        return tuple(out)

    @property
    def n_shared_apps(self) -> int:
        cfg = self.cfg
        return cfg.n_layers // cfg.shared_attn_every if cfg.shared_attn_every else 0

    # ---- init ------------------------------------------------------------
    def init(self, key) -> Params:
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        keys = jax.random.split(key, 16)
        params: Params = {
            "embedding": layers.init_embedding(keys[0], cfg.vocab_size, cfg.d_model, dtype),
            "ln_f": layers.init_norm(cfg.d_model, dtype, cfg.norm),
        }
        if not cfg.tie_embeddings:
            params["lm_head"] = layers._dense_init(keys[1], cfg.d_model, cfg.vocab_size, dtype)
        if cfg.frontend:
            params["frontend"] = {
                "patch_proj": layers._dense_init(keys[2], cfg.frontend_dim, cfg.d_model, dtype)
            }
        groups = []
        gkeys = jax.random.split(keys[3], len(self._groups()))
        layer_idx = 0
        for gi, (kind, count) in enumerate(self._groups()):
            dense = layer_idx < cfg.n_dense_layers
            bkeys = jax.random.split(gkeys[gi], count)
            groups.append(jax.vmap(lambda k: _init_block(k, kind, cfg, dtype, dense))(bkeys))
            layer_idx += count
        params["groups"] = groups
        if cfg.shared_attn_every:
            params["shared_attn"] = _init_block(keys[4], "attn", cfg, dtype, True)
        if cfg.enc_dec:
            ekeys = jax.random.split(keys[5], cfg.n_encoder_layers)
            params["encoder"] = {
                "blocks": jax.vmap(lambda k: _init_block(k, "attn", cfg, dtype, True))(ekeys),
                "ln_f": layers.init_norm(cfg.d_model, dtype, cfg.norm),
            }
            ckeys = jax.random.split(keys[6], cfg.n_layers)
            params["cross"] = jax.vmap(
                lambda k: {
                    "ln": layers.init_norm(cfg.d_model, dtype, cfg.norm),
                    "attn": layers.init_cross_attention(k, cfg, dtype),
                }
            )(ckeys)
        if cfg.mtp_depth:
            params["mtp"] = {
                "proj": layers._dense_init(keys[7], 2 * cfg.d_model, cfg.d_model, dtype),
                "block": _init_block(keys[8], "attn", cfg, dtype, True),
                "ln": layers.init_norm(cfg.d_model, dtype, cfg.norm),
            }
        return params

    # ---- cache init --------------------------------------------------------
    def init_cache(
        self, batch: int, max_len: int, enc_len: int = 0, ragged: bool = False
    ) -> Params:
        """ragged=True gives every batch slot its own cache index — the
        continuous-batching decode state used by serving/engine.py."""
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        cache: Params = {
            "groups": [
                _stack(count, _block_cache(kind, cfg, batch, max_len, dtype, ragged))
                for kind, count in self._groups()
            ]
        }
        if cfg.shared_attn_every:
            cache["shared"] = _stack(
                self.n_shared_apps,
                _block_cache("attn", cfg, batch, max_len, dtype, ragged),
            )
        if cfg.enc_dec:
            cache["cross"] = {
                "k": jnp.zeros(
                    (cfg.n_layers, batch, enc_len, cfg.n_kv_heads, cfg.head_dim_), dtype
                ),
                "v": jnp.zeros(
                    (cfg.n_layers, batch, enc_len, cfg.n_kv_heads, cfg.head_dim_), dtype
                ),
            }
        return cache

    # ---- embedding + frontends ----------------------------------------------
    def _embed_inputs(self, params: Params, batch: Dict[str, jnp.ndarray]) -> jnp.ndarray:
        cfg = self.cfg
        x = layers.embed(params["embedding"], batch["tokens"])
        if cfg.frontend == "vit" and "patch_embeds" in batch:
            pe = batch["patch_embeds"] @ params["frontend"]["patch_proj"]
            npatch = min(pe.shape[1], x.shape[1])
            x = jnp.concatenate([pe[:, :npatch].astype(x.dtype), x[:, npatch:]], axis=1)
        return x

    def _encode(self, params: Params, batch: Dict[str, jnp.ndarray]) -> jnp.ndarray:
        """Audio encoder over precomputed frame embeddings (stub frontend)."""
        cfg = self.cfg
        frames = batch["frames"]
        h = frames @ params["frontend"]["patch_proj"] if cfg.frontend else frames
        h = h.astype(jnp.dtype(cfg.dtype))

        from ..kernels import ops as kops

        def body(x, bp):
            hh = layers.apply_norm(bp["ln1"], x, cfg.norm)
            hd = cfg.head_dim_
            b, s, _ = x.shape
            q = (hh @ bp["attn"]["wq"]).reshape(b, s, cfg.n_heads, hd)
            k = (hh @ bp["attn"]["wk"]).reshape(b, s, cfg.n_kv_heads, hd)
            v = (hh @ bp["attn"]["wv"]).reshape(b, s, cfg.n_kv_heads, hd)
            a = kops.flash_attention(q, k, v, causal=False)
            x = x + a.reshape(b, s, cfg.n_heads * hd) @ bp["attn"]["wo"]
            hh = layers.apply_norm(bp["ln2"], x, cfg.norm)
            x = x + layers.apply_mlp(bp["mlp"], hh, cfg.mlp)
            return x, None

        h, _ = jax.lax.scan(body, h, params["encoder"]["blocks"])
        return layers.apply_norm(params["encoder"]["ln_f"], h, cfg.norm)

    # ---- decoder trunk -------------------------------------------------------
    def _trunk(
        self,
        params: Params,
        x: jnp.ndarray,
        positions: jnp.ndarray,
        cache: Optional[Params],
        enc_out: Optional[jnp.ndarray],
    ) -> Tuple[jnp.ndarray, Optional[Params], jnp.ndarray]:
        cfg = self.cfg
        aux_total = jnp.zeros((), jnp.float32)

        if cfg.enc_dec:
            return self._trunk_encdec(params, x, positions, cache, enc_out)

        new_groups: List[Any] = []
        new_shared: List[Any] = []
        shared_cache = cache.get("shared") if cache is not None else None
        cum = 0
        shared_ct = 0
        for gi, (kind, count) in enumerate(self._groups()):
            gp = params["groups"][gi]
            gc = cache["groups"][gi] if cache is not None else None
            x, new_gc, aux = self._scan_group(gp, gc, x, kind, positions)
            aux_total = aux_total + aux
            new_groups.append(new_gc)
            cum += count
            if (
                cfg.shared_attn_every
                and cum % cfg.shared_attn_every == 0
                and shared_ct < self.n_shared_apps
            ):
                sc = (
                    jax.tree.map(lambda a: a[shared_ct], shared_cache)
                    if shared_cache is not None
                    else None
                )
                x, nsc, aux2 = _apply_block(
                    params["shared_attn"], x, "attn", cfg, positions, sc
                )
                aux_total = aux_total + aux2
                if nsc is not None:
                    new_shared.append(nsc)
                shared_ct += 1

        new_cache = None
        if cache is not None:
            new_cache = {"groups": new_groups}
            if new_shared:
                new_cache["shared"] = jax.tree.map(lambda *ls: jnp.stack(ls), *new_shared)
            elif "shared" in cache:
                new_cache["shared"] = cache["shared"]
        return x, new_cache, aux_total

    def _scan_group(self, gp, gc, x, kind: str, positions):
        cfg = self.cfg
        aux0 = jnp.zeros((), jnp.float32)
        if gc is not None and x.shape[1] == 1:
            # decode: each layer reads its cache in place from the group's
            # stack, which the loop never slices out or restacks, and hands
            # back only what it changed.  After the loop every layer's new
            # token lands in the stack in one pass (a recurrent group's new
            # states simply are its cache).
            def body_dec(carry, xs):
                xx, auxc = carry
                bp, li = xs
                xx, changed, aux = _apply_block(bp, xx, kind, cfg, positions, gc, layer=li)
                return (xx, auxc + aux), changed

            n = jax.tree.leaves(gp)[0].shape[0]
            (x, aux), changed = jax.lax.scan(body_dec, (x, aux0), (gp, jnp.arange(n)))
            if "attn" in changed:
                changed = {"attn": layers.commit_tokens(gc["attn"], changed["attn"])}
            return x, changed, aux
        if gc is not None:
            # prefill: every layer writes a whole fresh block into its cache
            def body(carry, xs):
                xx, auxc = carry
                bp, bc = xs
                xx, nbc, aux = _apply_block(bp, xx, kind, cfg, positions, bc)
                return (xx, auxc + aux), nbc

            (x, aux), new_gc = jax.lax.scan(_maybe_remat(body), (x, aux0), (gp, gc))
            return x, new_gc, aux

        def body_nc(carry, bp):
            xx, auxc = carry
            xx, _, aux = _apply_block(bp, xx, kind, cfg, positions, None)
            return (xx, auxc + aux), None

        (x, aux), _ = jax.lax.scan(_maybe_remat(body_nc), (x, aux0), gp)
        return x, None, aux

    def _trunk_encdec(self, params, x, positions, cache, enc_out):
        """Uniform decoder scan with interleaved cross-attention.

        prefill/train: enc_out given -> cross K/V computed, returned in cache.
        decode: enc_out None -> cross K/V read from cache.
        """
        cfg = self.cfg
        aux0 = jnp.zeros((), jnp.float32)
        gp = params["groups"][0]
        gc = cache["groups"][0] if cache is not None else None
        cross_p = params["cross"]
        cross_c = cache["cross"] if cache is not None else None

        def body(carry, xs):
            xx, auxc = carry
            if cache is not None:
                bp, cp, bc, cck, ccv = xs
            else:
                bp, cp = xs
                bc, cck, ccv = None, None, None
            xx, nbc, aux = _apply_block(bp, xx, "attn", cfg, positions, bc)
            h = layers.apply_norm(cp["ln"], xx, cfg.norm)
            if enc_out is not None:
                a, ckv = layers.attention(
                    cp["attn"], h, cfg, positions, cache={}, kv_x=enc_out
                )
                nck, ncv = ckv["k"], ckv["v"]
            else:
                a, _ = layers.attention(
                    cp["attn"], h, cfg, positions, cache={"k": cck, "v": ccv},
                    kv_x=jnp.zeros((xx.shape[0], 0, cfg.d_model), xx.dtype),
                )
                nck, ncv = cck, ccv
            xx = xx + a
            ys = (nbc, nck, ncv) if cache is not None else None
            return (xx, auxc + aux), ys

        if cache is not None:
            xs = (gp, cross_p, gc, cross_c["k"], cross_c["v"])
            (x, aux), (new_gc, nk, nv) = jax.lax.scan(body, (x, aux0), xs)
            new_cache = {"groups": [new_gc], "cross": {"k": nk, "v": nv}}
        else:
            (x, aux), _ = jax.lax.scan(body, (x, aux0), (gp, cross_p))
            new_cache = None
        return x, new_cache, aux

    # ---- public entry points -------------------------------------------------
    def forward(
        self,
        params: Params,
        batch: Dict[str, jnp.ndarray],
        cache: Optional[Params] = None,
        positions: Optional[jnp.ndarray] = None,
    ) -> Tuple[jnp.ndarray, Optional[Params], jnp.ndarray]:
        cfg = self.cfg
        x = self._embed_inputs(params, batch)
        b, s = batch["tokens"].shape
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
        enc_out = self._encode(params, batch) if (cfg.enc_dec and "frames" in batch) else None
        x, new_cache, aux = self._trunk(params, x, positions, cache, enc_out)
        x = layers.apply_norm(params["ln_f"], x, cfg.norm)
        head = params["embedding"] if cfg.tie_embeddings else params["lm_head"]
        logits = layers.lm_logits(head, x, cfg.tie_embeddings)
        return logits, new_cache, aux

    # ---- loss -----------------------------------------------------------------
    def loss(
        self, params: Params, batch: Dict[str, jnp.ndarray]
    ) -> Tuple[jnp.ndarray, Dict]:
        cfg = self.cfg
        logits, _, aux = self.forward(params, batch)
        tokens = batch["tokens"]
        targets = jnp.concatenate([tokens[:, 1:], tokens[:, :1]], axis=1)
        mask = jnp.ones_like(targets, jnp.float32).at[:, -1].set(0.0)
        ce = _xent(logits, targets, mask)
        total = ce + 0.01 * aux
        metrics = {"ce": ce, "aux": aux}
        if cfg.mtp_depth and "mtp" in params:
            mtp_loss = self._mtp_loss(params, batch)
            total = total + 0.3 * mtp_loss
            metrics["mtp"] = mtp_loss
        return total, metrics

    def _mtp_loss(self, params, batch) -> jnp.ndarray:
        """DeepSeek-V3 multi-token prediction (depth 1, simplified): an extra
        block over [emb(t) ; emb(t+1)] predicting token t+2."""
        cfg = self.cfg
        tokens = batch["tokens"]
        b, s = tokens.shape
        emb = layers.embed(params["embedding"], tokens)
        nxt = jnp.concatenate([emb[:, 1:], emb[:, :1]], axis=1)
        h = jnp.concatenate([emb, nxt], axis=-1) @ params["mtp"]["proj"]
        positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
        h, _, _ = _apply_block(params["mtp"]["block"], h, "attn", cfg, positions, None)
        h = layers.apply_norm(params["mtp"]["ln"], h, cfg.norm)
        head = params["embedding"] if cfg.tie_embeddings else params["lm_head"]
        logits = layers.lm_logits(head, h, cfg.tie_embeddings)
        t2 = jnp.roll(tokens, -2, axis=1)
        mask = jnp.ones_like(t2, jnp.float32).at[:, -2:].set(0.0)
        return _xent(logits, t2, mask)


def _xent(logits: jnp.ndarray, targets: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    nll = (logz - gold) * mask
    return nll.sum() / jnp.maximum(mask.sum(), 1.0)
