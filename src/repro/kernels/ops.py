"""Public jit'd wrappers around the Pallas kernels.

Each wrapper (a) pads/stages inputs to kernel-friendly tile shapes, (b)
resolves the execution mode via ``repro.kernels.runtime`` (compiled where a
Pallas backend exists, interpreted otherwise) so the same call sites run on
CPU (tests/benches) and compile to Mosaic/Triton on TPU/GPU, and (c)
performs the cheap XLA epilogues (count reduction, un-padding).
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.kernels import bitset as _bitset
from repro.kernels import decode_attn as _decode
from repro.kernels.runtime import resolve_interpret


def bitset_combine(bitmaps, mode="and"):
    """(T, W) uint32 -> (combined (W,), cardinality)."""
    t, w = bitmaps.shape
    pad = (-w) % _bitset.BLOCK
    if pad:
        fill = jnp.zeros((t, pad), jnp.uint32)
        if mode == "and":  # AND identity must not create phantom docs
            bitmaps = jnp.concatenate([bitmaps, fill], axis=1)
        else:
            bitmaps = jnp.concatenate([bitmaps, fill], axis=1)
    combined, counts = _bitset.bitset_combine_blocks(bitmaps, mode=mode)
    return combined[:w], counts.sum()


def decode_attention(q, k, v, kv_len=None, s_block=None):
    """Grouped flash-decode with automatic padding.

    q: (B, Hkv, G, D); k/v: (B, Hkv, S, D/Dv).  Pads S to the block size and
    D/Dv/G to TPU-friendly multiples; slices the result back.
    """
    bsz, hkv, g, d = q.shape
    s, dv = k.shape[2], v.shape[3]
    s_block = s_block or min(_decode.DEFAULT_S_BLOCK, max(128, s))

    def pad_axis(x, axis, mult):
        rem = (-x.shape[axis]) % mult
        if rem == 0:
            return x
        widths = [(0, 0)] * x.ndim
        widths[axis] = (0, rem)
        return jnp.pad(x, widths)

    if kv_len is None:
        kv_len = jnp.full((bsz,), s, jnp.int32)
    qp = pad_axis(pad_axis(q, 3, 128), 2, 8)
    kp = pad_axis(pad_axis(k, 3, 128), 2, s_block)
    vp = pad_axis(pad_axis(v, 3, 128), 2, s_block)
    out = _decode.decode_attn(
        qp,
        kp,
        vp,
        kv_len=kv_len,
        s_block=s_block,
        interpret=resolve_interpret(None),
        scale=float(1.0 / (d ** 0.5)),  # true scale, not the padded one
    )
    return out[:, :, :g, :dv]
