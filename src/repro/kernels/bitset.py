"""Pallas TPU kernel: packed-bitmap boolean combine + popcount.

Lucene evaluates boolean filters over per-term document bitsets (FixedBitSet).
On TPU the natural layout is uint32 words in VMEM: AND/OR are VPU ops over
(8,128) tiles and popcount is 5 shift/mask steps — no table lookups, no
scalar loop.  The kernel fuses T-way combine with the cardinality reduction
so the bitmap traffic is read exactly once from HBM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.runtime import resolve_interpret

BLOCK_ROWS = 8
BLOCK_COLS = 128
BLOCK = BLOCK_ROWS * BLOCK_COLS  # uint32 words per grid step


def _popcount_u32(v):
    v = v - ((v >> 1) & jnp.uint32(0x55555555))
    v = (v & jnp.uint32(0x33333333)) + ((v >> 2) & jnp.uint32(0x33333333))
    v = (v + (v >> 4)) & jnp.uint32(0x0F0F0F0F)
    return (v * jnp.uint32(0x01010101)) >> 24


def _bitset_kernel(bits_ref, out_ref, cnt_ref, *, n_terms: int, conjunctive: bool):
    acc = bits_ref[0]
    for t in range(1, n_terms):
        acc = (acc & bits_ref[t]) if conjunctive else (acc | bits_ref[t])
    out_ref[...] = acc
    pc = _popcount_u32(acc).astype(jnp.int32)
    total = jnp.sum(pc)
    col = jax.lax.broadcasted_iota(jnp.int32, (1, BLOCK_COLS), 1)
    cnt_ref[...] = jnp.where(col == 0, total, 0)


def bitset_combine_blocks(bitmaps, mode="and", interpret=None):
    """bitmaps: (T, W) uint32 with W % 1024 == 0.

    Returns (combined (W,), per-block counts (NB,)).  ``interpret=None``
    auto-detects the execution mode (``repro.kernels.runtime``).
    """
    return _bitset_combine_blocks(
        bitmaps, mode=mode, interpret=resolve_interpret(interpret)
    )


@functools.partial(jax.jit, static_argnames=("mode", "interpret"))
def _bitset_combine_blocks(bitmaps, mode, interpret):
    t, w = bitmaps.shape
    assert w % BLOCK == 0, w
    nb = w // BLOCK
    b3 = bitmaps.reshape(t, nb * BLOCK_ROWS, BLOCK_COLS)

    combined, counts = pl.pallas_call(
        functools.partial(
            _bitset_kernel, n_terms=t, conjunctive=(mode == "and")
        ),
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((t, BLOCK_ROWS, BLOCK_COLS), lambda i: (0, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((BLOCK_ROWS, BLOCK_COLS), lambda i: (i, 0)),
            pl.BlockSpec((1, BLOCK_COLS), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nb * BLOCK_ROWS, BLOCK_COLS), jnp.uint32),
            jax.ShapeDtypeStruct((nb, BLOCK_COLS), jnp.int32),
        ],
        interpret=interpret,
        name="bitset_combine",
    )(b3)
    return combined.reshape(w), counts[:, 0]
