"""Pallas TPU kernel: grouped-query flash-decode attention.

Serving is where the paper's segment model meets the LM architectures: the
KV cache is managed as immutable segments + a volatile tail (see
``repro.serve``), and the decode hot loop streams those segments once.
This kernel computes one new token's attention against a long KV cache with
online softmax, never materializing the (G, S) score matrix in HBM.

Memory hierarchy mapping (HBM -> VMEM -> VREG):
  * K/V stream HBM->VMEM in (S_BLOCK, D) tiles chosen so q, k-tile, v-tile
    and the (G, S_BLOCK) score tile all fit VMEM,
  * the MXU does the (G,D)x(D,S_BLOCK) and (G,S_BLOCK)x(S_BLOCK,D) matmuls,
  * running max / normalizer / accumulator live in VMEM scratch across the
    sequence-block grid dimension.

Handles GQA natively: q is (B, Hkv, G, D) so K/V are read once per KV head
regardless of the query-group fan-out G (MQA: Hkv=1; MLA after absorption:
Hkv=1, D = r_kv + d_rope).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_S_BLOCK = 512


def _decode_attn_kernel(
    kvlen_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
    *, s_block: int, scale: float
):
    j = pl.program_id(1)
    nb = pl.num_programs(1)

    q = q_ref[0]  # (G, D)
    k = k_ref[0]  # (S_BLOCK, D)
    v = v_ref[0]  # (S_BLOCK, Dv)
    g = q.shape[0]
    s = jax.lax.dot_general(
        q.astype(jnp.float32),
        k.astype(jnp.float32),
        (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale  # (G, S_BLOCK)

    # mask beyond the live KV length
    kv_len = kvlen_ref[0, 0]
    pos = j * s_block + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(pos < kv_len, s, -jnp.inf)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    m_prev = m_ref[:, :1]  # (G, 1)
    l_prev = l_ref[:, :1]
    m_blk = jnp.max(s, axis=1, keepdims=True)
    m_cur = jnp.maximum(m_prev, m_blk)
    # guard: fully-masked prefix keeps m at -inf; exp(-inf - -inf) -> nan
    m_safe = jnp.where(jnp.isfinite(m_cur), m_cur, 0.0)
    alpha = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - m_safe), 0.0)
    p = jnp.where(jnp.isfinite(s), jnp.exp(s - m_safe), 0.0)  # (G, S_BLOCK)

    l_cur = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
    pv = jax.lax.dot_general(
        p,
        v.astype(jnp.float32),
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # (G, Dv)
    acc_ref[...] = acc_ref[...] * alpha + pv
    m_ref[...] = jnp.broadcast_to(m_cur, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_cur, l_ref.shape)

    @pl.when(j == nb - 1)
    def _finish():
        l = l_ref[:, :1]
        o_ref[0] = (acc_ref[...] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("s_block", "interpret", "scale")
)
def decode_attn(
    q, k, v, kv_len=None, s_block=DEFAULT_S_BLOCK, interpret=True, scale=None
):
    """q: (B, Hkv, G, D); k: (B, Hkv, S, D); v: (B, Hkv, S, Dv); kv_len: (B,).

    Returns (B, Hkv, G, Dv) in fp32.  S must be a multiple of ``s_block``.
    ``scale`` defaults to 1/sqrt(D) of the (possibly padded) q — callers that
    pad D must pass the true scale.
    """
    bsz, hkv, g, d = q.shape
    if scale is None:
        scale = float(1.0 / (d ** 0.5))
    s = k.shape[2]
    dv = v.shape[3]
    assert s % s_block == 0, (s, s_block)
    nb = s // s_block

    if kv_len is None:
        kv_len = jnp.full((bsz,), s, jnp.int32)
    kv_len2 = jnp.repeat(kv_len.astype(jnp.int32), hkv).reshape(bsz * hkv, 1)

    qf = q.reshape(bsz * hkv, g, d)
    kf = k.reshape(bsz * hkv, s, d)
    vf = v.reshape(bsz * hkv, s, dv)

    out = pl.pallas_call(
        functools.partial(_decode_attn_kernel, s_block=s_block, scale=scale),
        grid=(bsz * hkv, nb),
        in_specs=[
            pl.BlockSpec((1, 1), lambda i, j: (i, 0), memory_space=pltpu.SMEM),
            pl.BlockSpec((1, g, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, s_block, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, s_block, dv), lambda i, j: (i, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, g, dv), lambda i, j: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((bsz * hkv, g, dv), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((g, 128), jnp.float32),  # running max
            pltpu.VMEM((g, 128), jnp.float32),  # running normalizer
            pltpu.VMEM((g, dv), jnp.float32),  # output accumulator
        ],
        interpret=interpret,
        name="decode_attn",
    )(kv_len2, qf, kf, vf)
    return out.reshape(bsz, hkv, g, dv)
