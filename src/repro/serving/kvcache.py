"""KV-cache state management for continuous-batching serving.

Two layers:

1. ``insert_prefix`` — JetStream-style decode-state surgery: a batch-1
   prefill cache is copied into one *slot* of the ragged decode cache
   (every non-index leaf has batch at axis 1 because layer stacks put the
   scan dim first; ``index`` leaves hold the per-slot valid length).

2. ``PagedKVCache`` — a paged cache substrate (block pool + block tables),
   the TPU analogue of vLLM's PagedAttention memory manager.  Pages remove
   the contiguous-max_len reservation per slot: HBM is allocated in
   fixed-size blocks and sequences map to scattered blocks via a table.
   ``gather`` linearizes a sequence's pages for the decode-attention kernel;
   the host-side ``BlockAllocator`` does alloc/free bookkeeping.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

Params = Dict[str, Any]

__all__ = ["insert_prefix", "live_kv_bytes", "BlockAllocator", "PagedKVCache"]


def live_kv_bytes(cache: Any) -> int:
    """Bytes held by a live KV-cache pytree (decode state or PagedKVCache).

    This is the *live-state* half of a migration's transfer size: when a
    replica moves between partitions with KV handoff, its decode cache rides
    along with the weights.  Works on any pytree of arrays (ragged decode
    caches, paged pools, ShapeDtypeStructs from ``jax.eval_shape``).
    """
    if isinstance(cache, PagedKVCache):
        cache = (cache.pool_k, cache.pool_v)
    return int(
        sum(
            leaf.size * leaf.dtype.itemsize
            for leaf in jax.tree_util.tree_leaves(cache)
            if hasattr(leaf, "dtype")
        )
    )


# ---------------------------------------------------------------------------
# decode-state slot insertion
# ---------------------------------------------------------------------------
def _is_index_leaf(path) -> bool:
    last = path[-1]
    key = getattr(last, "key", getattr(last, "name", None))
    return key == "index"


@functools.partial(jax.jit, donate_argnums=(0,))
def insert_prefix(
    decode_cache: Params, prefix_cache: Params, slot: jnp.ndarray, length: jnp.ndarray
) -> Params:
    """Copy a batch-1 prefill cache into ``slot`` of the ragged decode cache.

    ``length`` is the TRUE prompt length (excluding right-padding); the
    per-slot index is set to it, so padded-prefill KV beyond the prompt is
    masked out by the ragged decode mask and overwritten by later tokens.
    """

    def ins(path, dst, src):
        if _is_index_leaf(path):
            # dst (..., n_slots) per-slot lengths; src is the scalar-stacked
            # prefill index (includes padding) — use the host-passed length.
            return dst.at[..., slot].set(jnp.asarray(length, dst.dtype))
        # dst (stack, n_slots, ...) <- src (stack, 1, ...)
        return dst.at[:, slot].set(src[:, 0].astype(dst.dtype))

    return jax.tree_util.tree_map_with_path(ins, decode_cache, prefix_cache)


# ---------------------------------------------------------------------------
# paged KV cache
# ---------------------------------------------------------------------------
class BlockAllocator:
    """Host-side free-list allocator over a fixed pool of cache blocks."""

    def __init__(self, n_blocks: int):
        self.n_blocks = n_blocks
        self._free: List[int] = list(range(n_blocks - 1, -1, -1))
        self.tables: Dict[int, List[int]] = {}

    @property
    def n_free(self) -> int:
        return len(self._free)

    def allocate(self, seq_id: int, n: int = 1) -> List[int]:
        if n > len(self._free):
            raise MemoryError(
                f"paged cache exhausted: want {n} blocks, {len(self._free)} free"
            )
        got = [self._free.pop() for _ in range(n)]
        self.tables.setdefault(seq_id, []).extend(got)
        return got

    def free(self, seq_id: int) -> None:
        self._free.extend(reversed(self.tables.pop(seq_id, [])))

    def table(self, seq_id: int) -> List[int]:
        return self.tables.get(seq_id, [])


@dataclasses.dataclass
class PagedKVCache:
    """Block-pooled K/V storage for one attention layer group.

    pool_k/pool_v: (n_blocks, block_size, n_kv_heads, head_dim).
    A sequence of length L owns ceil(L / block_size) blocks; ``block_table``
    (max_blocks_per_seq,) int32 rows map logical block i -> pool block id.
    """

    pool_k: jnp.ndarray
    pool_v: jnp.ndarray
    block_size: int

    @classmethod
    def create(
        cls,
        n_blocks: int,
        block_size: int,
        n_kv_heads: int,
        head_dim: int,
        dtype=jnp.bfloat16,
    ) -> "PagedKVCache":
        shape = (n_blocks, block_size, n_kv_heads, head_dim)
        return cls(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype), block_size)

    # -- device ops ---------------------------------------------------------
    def append(
        self, block_id: jnp.ndarray, offset: jnp.ndarray,
        k: jnp.ndarray, v: jnp.ndarray,
    ) -> "PagedKVCache":
        """Write one token's (n_kv_heads, head_dim) K/V at (block, offset)."""
        pk = self.pool_k.at[block_id, offset].set(k.astype(self.pool_k.dtype))
        pv = self.pool_v.at[block_id, offset].set(v.astype(self.pool_v.dtype))
        return PagedKVCache(pk, pv, self.block_size)

    def append_batch(
        self, block_ids: jnp.ndarray, offsets: jnp.ndarray,
        k: jnp.ndarray, v: jnp.ndarray,
    ) -> "PagedKVCache":
        """Batched one-token append: block_ids/offsets (B,), k/v (B, Hkv, D)."""
        pk = self.pool_k.at[block_ids, offsets].set(k.astype(self.pool_k.dtype))
        pv = self.pool_v.at[block_ids, offsets].set(v.astype(self.pool_v.dtype))
        return PagedKVCache(pk, pv, self.block_size)

    def gather(self, block_table: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Linearize pages: (max_blocks,) table -> (max_blocks*bs, Hkv, D).

        Unused table entries should point at a zero block; the caller masks
        by true length, so stale contents there are never attended to.
        """
        k = self.pool_k[block_table]  # (nb, bs, H, D)
        v = self.pool_v[block_table]
        nb, bs, h, d = k.shape
        return k.reshape(nb * bs, h, d), v.reshape(nb * bs, h, d)

    def gather_batch(
        self, block_tables: jnp.ndarray
    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """(B, max_blocks) tables -> (B, max_blocks*bs, Hkv, D)."""
        k = self.pool_k[block_tables]  # (B, nb, bs, H, D)
        v = self.pool_v[block_tables]
        b, nb, bs, h, d = k.shape
        return k.reshape(b, nb * bs, h, d), v.reshape(b, nb * bs, h, d)


def paged_decode_attention(
    q: jnp.ndarray,  # (B, 1, Hq, D)
    cache: PagedKVCache,
    block_tables: jnp.ndarray,  # (B, max_blocks) int32
    lengths: jnp.ndarray,  # (B,) int32 true sequence lengths
) -> jnp.ndarray:
    """Decode attention over paged KV: gather pages, mask by true length."""
    from ..kernels import ops as kops

    k, v = cache.gather_batch(block_tables)
    # pages are token-major; decode attention reads (B, Hkv, D, S)
    return kops.decode_attention(
        q, jnp.moveaxis(k, 1, -1), jnp.moveaxis(v, 1, -1), length=lengths
    )
