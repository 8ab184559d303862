"""ArchConfig -> runnable model bundle: init / loss / prefill / decode +
ShapeDtypeStruct input specs for every assigned input shape."""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ArchConfig, ShapeConfig
from .transformer import Model

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class ModelBundle:
    cfg: ArchConfig

    @property
    def model(self) -> Model:
        return Model(self.cfg)

    # ---- init --------------------------------------------------------------
    def init(self, key) -> Params:
        """Random parameters, built in one compiled program so each weight
        is drawn and cast to the model dtype without a float32 copy of the
        layer stack (chatglm3-6b's float32 stacks would not fit in HBM)."""
        return _jitted_init(self.cfg)(key)

    def param_shapes(self) -> Params:
        """ShapeDtypeStruct pytree without materializing anything."""
        return jax.eval_shape(self.init, jax.random.key(0))

    def param_count(self) -> int:
        import math

        return sum(math.prod(l.shape) for l in jax.tree.leaves(self.param_shapes()))

    def active_param_count(self) -> int:
        """Per-token active params (MoE: only routed-in experts count)."""
        import math

        cfg = self.cfg
        total = self.param_count()
        if not cfg.n_experts:
            return total
        shapes = self.param_shapes()
        expert_total = 0
        for g in shapes["groups"]:
            if "moe" in g:
                e = g["moe"]["experts"]
                expert_total += sum(math.prod(l.shape) for l in jax.tree.leaves(e))
        active_frac = cfg.experts_per_token / cfg.n_experts
        return int(total - expert_total * (1 - active_frac))

    # ---- steps --------------------------------------------------------------
    def loss_fn(self, params: Params, batch: Dict[str, jnp.ndarray]):
        return self.model.loss(params, batch)

    def prefill_fn(
        self, params: Params, batch: Dict[str, jnp.ndarray], max_len: int
    ) -> Tuple[jnp.ndarray, Params]:
        """Full-sequence forward that returns logits + a filled cache."""
        b, s = batch["tokens"].shape
        enc_len = self.cfg.frontend_len if self.cfg.enc_dec else 0
        cache = self.model.init_cache(b, max_len, enc_len)
        logits, cache, _ = self.model.forward(params, batch, cache=cache)
        return logits, cache

    def decode_fn(
        self,
        params: Params,
        cache: Params,
        tokens: jnp.ndarray,  # (B, 1)
        index: jnp.ndarray,  # scalar current position
    ) -> Tuple[jnp.ndarray, Params]:
        b = tokens.shape[0]
        positions = jnp.broadcast_to(index, (b, 1))
        logits, cache, _ = self.model.forward(
            params, {"tokens": tokens}, cache=cache, positions=positions
        )
        return logits, cache

    # ---- input specs ----------------------------------------------------------
    def input_specs(self, shape: ShapeConfig) -> Dict[str, Any]:
        """ShapeDtypeStruct stand-ins for the step function's inputs."""
        cfg = self.cfg
        b = shape.global_batch
        s = shape.seq_len
        i32 = jnp.int32

        def tok(bb, ss):
            return jax.ShapeDtypeStruct((bb, ss), i32)

        if shape.kind in ("train", "prefill"):
            batch: Dict[str, Any] = {"tokens": tok(b, s)}
            if cfg.frontend == "vit":
                batch["patch_embeds"] = jax.ShapeDtypeStruct(
                    (b, cfg.frontend_len, cfg.frontend_dim), jnp.dtype(cfg.dtype)
                )
            if cfg.enc_dec:
                batch["frames"] = jax.ShapeDtypeStruct(
                    (b, cfg.frontend_len, cfg.frontend_dim), jnp.dtype(cfg.dtype)
                )
            return {"batch": batch}

        # decode: one new token against a cache of size seq_len
        enc_len = cfg.frontend_len if cfg.enc_dec else 0
        cache = jax.eval_shape(lambda: self.model.init_cache(b, s, enc_len))
        return {
            "cache": cache,
            "tokens": tok(b, 1),
            "index": jax.ShapeDtypeStruct((), i32),
        }

    def supports_shape(self, shape: ShapeConfig) -> bool:
        """long_500k requires sub-quadratic decode (DESIGN.md table)."""
        if shape.name == "long_500k":
            return self.cfg.supports_long_decode
        return True


@functools.lru_cache(maxsize=None)
def _jitted_init(cfg: ArchConfig):
    return jax.jit(Model(cfg).init)


def bundle(cfg: ArchConfig) -> ModelBundle:
    return ModelBundle(cfg)
