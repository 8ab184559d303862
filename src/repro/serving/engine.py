"""Continuous-batching inference engine (iteration-level scheduling).

JetStream-style slot architecture on top of the model zoo:

  * a fixed decode batch of ``max_slots`` sequence slots shares one ragged
    cache (per-slot ``index`` lengths — see models/transformer.init_cache);
  * a new request is PREFILLED at batch 1 (padded to a power-of-two bucket
    for attention archs so jit shapes are reused; exact length for recurrent
    archs, whose state would otherwise be advanced through padding), then
    INSERTED into a free slot via kvcache.insert_prefix;
  * one ``step()`` = admit waiting requests into free slots + one ragged
    decode step advancing every active slot by one token;
  * finished sequences (EOS / max_new_tokens) release their slot — the next
    admission overwrites it, no cache zeroing needed.

This is the workload the paper places: one Engine == one model replica in a
MIG/pod partition.  serving/cluster.py binds engines to placements.

Telemetry (``repro.obs``, no-op unless enabled): ``replica.submit`` (rid),
``replica.step``, ``replica.prefill`` (rid, prompt_len, bucket; pad,
dispatch, first-token fetch) with its child ``replica.insert``, and
``replica.decode`` (n_active) with the children ``replica.decode.prepare``
(slot scan, device index pull, input arrays), ``replica.decode.fetch`` (the
wait for the sampled tokens) and ``replica.decode.commit`` (appends, retire).
"""
from __future__ import annotations

import collections
import dataclasses
import functools
from typing import Any, Deque, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..models.model_zoo import ModelBundle
from ..obs import get_telemetry
from .kvcache import insert_prefix

__all__ = ["Request", "Completion", "Engine", "EngineConfig", "decode_program"]


@dataclasses.dataclass
class Request:
    rid: str
    prompt: List[int]
    max_new_tokens: int = 16
    eos_id: Optional[int] = None
    #: extra prefill inputs (e.g. patch_embeds for VLM, frames for enc-dec)
    extras: Dict[str, Any] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class Completion:
    rid: str
    prompt: List[int]
    tokens: List[int]
    prefill_len: int
    finish_reason: str  # "eos" | "length"


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    max_slots: int = 4
    max_len: int = 256
    bucket_prefill: bool = True  # pad prompts to pow2 (attention archs only)


@dataclasses.dataclass
class _SlotState:
    req: Request
    generated: List[int]
    length: int  # true tokens in cache (prompt + generated)


def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


def decode_program(model):
    """The replica's jitted ragged decode step: feed one token per slot at
    its position, return each slot's greedy next token and the cache.  The
    cache is donated, and the step writes each slot's new K/V into it in
    place (models/transformer._scan_group)."""

    @functools.partial(jax.jit, donate_argnums=(1,))
    def _decode(params, cache, tokens, lengths):
        logits, cache, _ = model.forward(
            params, {"tokens": tokens}, cache=cache, positions=lengths[:, None]
        )
        return jnp.argmax(logits[:, -1, :], axis=-1), cache

    return _decode


class Engine:
    """One model replica serving requests with continuous batching."""

    def __init__(
        self,
        bundle: ModelBundle,
        params,
        cfg: EngineConfig = EngineConfig(),
        device: Optional[jax.Device] = None,
    ):
        """``device`` commits the replica's parameters and cache to one
        device, so its steps run there; None leaves them on the default
        device."""
        self.bundle = bundle
        self.model = bundle.model
        self.cfg = cfg
        mcfg = bundle.cfg
        self._recurrent = mcfg.is_recurrent
        enc_len = mcfg.frontend_len if mcfg.enc_dec else 0
        placed = {}
        if device is not None:
            placed["out_shardings"] = jax.sharding.SingleDeviceSharding(device)
            params = jax.device_put(params, device)
        self.params = params
        self.cache = jax.jit(
            lambda: self.model.init_cache(
                cfg.max_slots, cfg.max_len, enc_len, ragged=True
            ),
            **placed,
        )()
        self.queue: Deque[Request] = collections.deque()
        self.slots: List[Optional[_SlotState]] = [None] * cfg.max_slots
        self.completed: List[Completion] = []
        self.stats = {"prefills": 0, "decode_steps": 0, "tokens": 0}

        # --- jitted steps ----------------------------------------------------
        @jax.jit
        def _prefill(params, batch):
            logits, cache = bundle.prefill_fn(params, batch, max_len=cfg.max_len)
            return logits, cache

        self._prefill = _prefill
        self._decode = decode_program(self.model)

    # ------------------------------------------------------------------ API
    def submit(self, req: Request) -> None:
        with get_telemetry().tracer.span("replica.submit", rid=req.rid):
            if len(req.prompt) + req.max_new_tokens > self.cfg.max_len:
                raise ValueError(
                    f"{req.rid}: prompt+max_new={len(req.prompt)}+{req.max_new_tokens} "
                    f"exceeds max_len={self.cfg.max_len}"
                )
            self.queue.append(req)

    @property
    def n_active(self) -> int:
        return sum(s is not None for s in self.slots)

    @property
    def has_work(self) -> bool:
        return bool(self.queue) or self.n_active > 0

    def step(self) -> int:
        """Admit waiting requests, then advance all active slots one token.

        Returns the number of tokens produced this step (incl. the first
        token each admitted request gets from its prefill logits)."""
        with get_telemetry().tracer.span("replica.step"):
            produced = self._admit()
            return produced + self._decode_step()

    def run(self, max_steps: int = 100_000) -> List[Completion]:
        for _ in range(max_steps):
            if not self.has_work:
                break
            self.step()
        return self.completed

    # ------------------------------------------------------------- internals
    def _admit(self) -> int:
        produced = 0
        for slot_id, st in enumerate(self.slots):
            if st is not None or not self.queue:
                continue
            req = self.queue.popleft()
            first_tok = self._prefill_into(slot_id, req)
            self.slots[slot_id] = _SlotState(
                req=req, generated=[first_tok], length=len(req.prompt) + 1
            )
            self.stats["prefills"] += 1
            self.stats["tokens"] += 1
            produced += 1
            self._retire_if_done(slot_id)
        return produced

    def _prefill_into(self, slot_id: int, req: Request) -> int:
        plen = len(req.prompt)
        pad = (
            _next_pow2(plen)
            if (self.cfg.bucket_prefill and not self._recurrent)
            else plen
        )
        tracer = get_telemetry().tracer
        with tracer.span("replica.prefill", rid=req.rid, prompt_len=plen, bucket=pad):
            toks = np.zeros((1, pad), np.int32)
            toks[0, :plen] = req.prompt
            batch = {"tokens": jnp.asarray(toks), **req.extras}
            logits, prefix = self._prefill(self.params, batch)
            # first generated token: logits at the LAST TRUE prompt position
            first = int(jnp.argmax(logits[0, plen - 1, :]))
            with tracer.span("replica.insert"):
                self.cache = insert_prefix(
                    self.cache, prefix, jnp.int32(slot_id), jnp.int32(plen)
                )
        # account for the first token: it is appended by the next decode
        # step's write (its KV is not in the cache yet; decode writes it).
        return first

    def _decode_step(self) -> int:
        active = [i for i, s in enumerate(self.slots) if s is not None]
        if not active:
            return 0
        tracer = get_telemetry().tracer
        with tracer.span("replica.decode", n_active=len(active)):
            with tracer.span("replica.decode.prepare"):
                tokens = np.zeros((self.cfg.max_slots, 1), np.int32)
                lengths = np.zeros((self.cfg.max_slots,), np.int32)
                for i, st in enumerate(self.slots):
                    if st is not None:
                        tokens[i, 0] = st.generated[-1]
                        lengths[i] = st.length - 1  # position OF the fed token
                # inactive slots: keep device/host index agreement by feeding
                # their device-side index (the model bumps every slot's index
                # by 1).
                dev_idx = np.asarray(self._slot_indexes())
                for i in range(self.cfg.max_slots):
                    if self.slots[i] is None:
                        lengths[i] = dev_idx[i]
                tokens, lengths = jnp.asarray(tokens), jnp.asarray(lengths)
            nxt, self.cache = self._decode(self.params, self.cache, tokens, lengths)
            with tracer.span("replica.decode.fetch"):
                nxt = np.asarray(nxt)
            with tracer.span("replica.decode.commit"):
                produced = 0
                self.stats["decode_steps"] += 1
                for i in active:
                    st = self.slots[i]
                    st.generated.append(int(nxt[i]))
                    st.length += 1
                    produced += 1
                    self.stats["tokens"] += 1
                    self._retire_if_done(i)
        return produced

    def _slot_indexes(self) -> np.ndarray:
        """Device-side per-slot cache index (from the first attn leaf)."""
        leaf = None

        def find(path, x):
            nonlocal leaf
            last = path[-1]
            if getattr(last, "key", None) == "index" and leaf is None:
                leaf = x
            return x

        jax.tree_util.tree_map_with_path(find, self.cache)
        if leaf is None:  # pure-recurrent arch: no index leaves
            return np.zeros((self.cfg.max_slots,), np.int32)
        arr = np.asarray(leaf)
        return arr[0] if arr.ndim == 2 else np.broadcast_to(arr, (self.cfg.max_slots,))

    def _retire_if_done(self, slot_id: int) -> None:
        st = self.slots[slot_id]
        req = st.req
        done_eos = req.eos_id is not None and st.generated[-1] == req.eos_id
        done_len = len(st.generated) >= req.max_new_tokens
        if done_eos or done_len:
            self.completed.append(
                Completion(
                    rid=req.rid,
                    prompt=list(req.prompt),
                    tokens=list(st.generated),
                    prefill_len=len(req.prompt),
                    finish_reason="eos" if done_eos else "length",
                )
            )
            self.slots[slot_id] = None
