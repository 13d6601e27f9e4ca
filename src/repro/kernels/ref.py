"""Pure-jnp oracles for every Pallas kernel in this package.

Each function is the semantic ground truth: tests sweep shapes/dtypes and
assert the kernels (run with ``interpret=True`` on CPU) match these.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


# ---------------------------------------------------------------------------
# bitset: packed-uint32 boolean combine + popcount
# ---------------------------------------------------------------------------


def _popcount_u32(v):
    v = v - ((v >> 1) & jnp.uint32(0x55555555))
    v = (v & jnp.uint32(0x33333333)) + ((v >> 2) & jnp.uint32(0x33333333))
    v = (v + (v >> 4)) & jnp.uint32(0x0F0F0F0F)
    return (v * jnp.uint32(0x01010101)) >> 24


@partial(jax.jit, static_argnames=("mode",))
def bitset_combine_ref(bitmaps, mode="and"):
    """bitmaps: (T, W) uint32.  Returns (combined (W,), total_popcount ())."""
    if mode == "and":
        combined = bitmaps[0]
        for i in range(1, bitmaps.shape[0]):
            combined = combined & bitmaps[i]
    elif mode == "or":
        combined = bitmaps[0]
        for i in range(1, bitmaps.shape[0]):
            combined = combined | bitmaps[i]
    else:
        raise ValueError(mode)
    return combined, _popcount_u32(combined).astype(jnp.int32).sum()


# ---------------------------------------------------------------------------
# decode_attn: single-new-token attention against a long KV cache
# ---------------------------------------------------------------------------


def decode_attn_ref(q, k, v, kv_len=None):
    """Grouped-query flash-decode oracle.

    q: (B, Hkv, G, D)   one new token, G query heads per KV head
    k: (B, Hkv, S, D)
    v: (B, Hkv, S, Dv)
    kv_len: optional (B,) valid lengths (positions >= kv_len are masked).
    returns (B, Hkv, G, Dv)
    """
    scale = 1.0 / jnp.sqrt(q.shape[-1]).astype(jnp.float32)
    logits = jnp.einsum(
        "bhgd,bhsd->bhgs", q.astype(jnp.float32), k.astype(jnp.float32)
    ) * scale
    if kv_len is not None:
        s = k.shape[2]
        mask = jnp.arange(s)[None, None, None, :] < kv_len[:, None, None, None]
        logits = jnp.where(mask, logits, -jnp.inf)
    w = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhgs,bhsd->bhgd", w, v.astype(jnp.float32))


# ---------------------------------------------------------------------------
# seg_embed_bag: EmbeddingBag (gather + segment-sum) — recsys hot path
# ---------------------------------------------------------------------------


def embedding_bag_ref(table, indices, offsets, mode="sum"):
    """table: (V, D); indices: (N,); offsets: (B+1,) bag boundaries.

    Equivalent of ``torch.nn.EmbeddingBag``: bag b reduces
    table[indices[offsets[b]:offsets[b+1]]].
    """
    rows = table[indices]
    seg_ids = jnp.cumsum(
        jnp.zeros(indices.shape[0], jnp.int32)
        .at[offsets[1:-1]]
        .add(1, mode="drop")
    )
    n_bags = offsets.shape[0] - 1
    out = jax.ops.segment_sum(rows, seg_ids, num_segments=n_bags)
    if mode == "mean":
        counts = (offsets[1:] - offsets[:-1]).astype(out.dtype)
        out = out / jnp.maximum(counts, 1)[:, None]
    return out
