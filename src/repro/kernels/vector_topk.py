"""Pallas TPU kernels: dense-vector similarity top-k (+ hybrid fusion).

The dense-retrieval analogue of ``fused_exec``: one kernel scores a whole
batch of query vectors against a segment's device-resident (ND_pad, D_pad)
vector column — dot or cosine similarity, live masking, and blockwise
top-k in a single ``pallas_call`` — and a second kernel fuses a dense BM25
column into the same pass for hybrid BM25 ⊕ vector queries.

Layout contract (same doc-space tiling as ``fused_exec``):

  * vector column: (ND_pad, D_pad) float32 with ND_pad % 1024 == 0 and
    D_pad % 128 == 0 (row padding = dead docs, column padding = zero
    components — both are exact no-ops for dot and cosine);
  * doc-space blocks: the doc axis reshapes to (NB*8, 128) and the grid
    walks (B, NB) with (8, 128, D_pad) vector blocks;
  * query vectors: (B, 1, D_pad), one (1, 1, D_pad) block per query (the
    unit axis makes the block's trailing dims the array's full extent);
    hybrid alphas sit in SMEM;
  * per-block winners: (B, NB, 128) vals/idx, entries past k are -inf/-1,
    hit counts in lane 0 of a (B, NB, 128) int32 output — identical to the
    ``fused_exec`` output contract, so the same hierarchical XLA top-k
    epilogue merges the blocks.

Scoring parity: the similarity is the same trailing-axis reduce as the
oracle's ``exec._similarity`` (zero padding folds in exactly), and block
selection uses the same k unrolled max-extractions with smallest-flat-index
(== smallest doc) tie-breaks, so the merged result is bit-identical to the
brute-force ``search_single`` path.
"""

from __future__ import annotations

import functools

import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.fused_exec import (
    _SMEM,
    BLOCK,
    BLOCK_COLS,
    BLOCK_ROWS,
    _SHARED_TILE,
    _TILE,
    _block_topk,
    _blocks_call,
    _store_block,
)
from repro.kernels.runtime import resolve_interpret

#: vector components per lane tile (the trailing dim pads to this multiple)
DIM_TILE = 128


def pad_dim(d: int) -> int:
    """Smallest DIM_TILE multiple >= d (zero columns are scoring no-ops)."""
    return -(-d // DIM_TILE) * DIM_TILE


def _sims_block(v, q, cosine: bool, dim: int):
    """(8, 128) similarities of one doc block against one query vector.

    ``v``: (8, 128, D_pad); ``q``: (D_pad,).  Same expression as the
    XLA oracle (``exec._similarity``): trailing-axis reduce, cosine
    guarded to 0 where a norm is zero (padding rows / vectorless docs).
    The reduce runs over the first ``dim`` components only — lane padding
    exists purely for layout; summing the zero lanes would change the
    reduction tree and cost the oracle's bit-parity a ULP.
    """
    v = v[..., :dim]
    q = q[:dim]
    sims = jnp.sum(v * q, axis=-1)
    if cosine:
        den = jnp.sqrt(jnp.sum(v * v, axis=-1)) * jnp.sqrt(jnp.sum(q * q))
        sims = jnp.where(den > 0, sims / den, 0.0)
    return sims


def _query_spec(dp: int):
    """(1, 1, D_pad) block of the (B, 1, D_pad) query-vector array."""
    return pl.BlockSpec((1, 1, dp), lambda q, i: (q, 0, 0))


def _vmat_spec(dp: int):
    """(8, 128, D_pad) vector rows of one doc block."""
    return pl.BlockSpec((BLOCK_ROWS, BLOCK_COLS, dp), lambda q, i: (i, 0, 0))


def _vector_kernel(q_ref, vmat_ref, live_ref, vals_ref, idx_ref, cnt_ref,
                   *, k: int, cosine: bool, dim: int):
    q = q_ref[0, 0]         # (D_pad,)
    v = vmat_ref[...]       # (8, 128, D_pad) vector rows of this doc block
    live = live_ref[...] > 0
    s = jnp.where(live, _sims_block(v, q, cosine, dim), -jnp.inf)
    vals, idxs = _block_topk(s, k)
    base = pl.program_id(1) * BLOCK  # doc-space blocks: flat idx == doc id
    # match-all-live semantics: every live doc is a hit
    _store_block(vals_ref, idx_ref, cnt_ref, vals, idxs, base,
                 jnp.sum(live.astype(jnp.int32)))


def vector_topk_tiles(vmat, live, qvecs, k, cosine=False, dim=None,
                      interpret=None):
    """vmat: (ND_pad, D_pad) float32 vector column; live: (ND_pad,) int32;
    qvecs: (B, D_pad); dim: true component count (D_pad lanes past it are
    layout padding).  Returns ((B, NB, 128) vals, (B, NB, 128) doc ids,
    (B, NB) live counts)."""
    interpret = resolve_interpret(interpret)
    nd, dp = vmat.shape
    assert nd % BLOCK == 0, nd
    assert dp % DIM_TILE == 0, dp
    nb = nd // BLOCK
    bsz = qvecs.shape[0]
    dim = dp if dim is None else dim
    v3 = vmat.reshape(nb * BLOCK_ROWS, BLOCK_COLS, dp)
    l3 = live.reshape(nb * BLOCK_ROWS, BLOCK_COLS)
    vals, idx, cnt = _blocks_call(
        functools.partial(_vector_kernel, k=k, cosine=cosine, dim=dim),
        bsz, nb, [_query_spec(dp), _vmat_spec(dp), _SHARED_TILE],
        interpret, "vector_topk",
    )(qvecs.reshape(bsz, 1, dp), v3, l3)
    return vals, idx, cnt[..., 0]


# ---------------------------------------------------------------------------
# hybrid: dense BM25 column ⊕ vector similarity, fixed normalizations
# ---------------------------------------------------------------------------


def _hybrid_kernel(q_ref, alpha_ref, dense_ref, vmat_ref, live_ref,
                   vals_ref, idx_ref, cnt_ref, *, k: int, cosine: bool,
                   dim: int):
    q = q_ref[0, 0]
    alpha = alpha_ref[pl.program_id(0)]
    dense = dense_ref[0]    # (8, 128) scatter-combined BM25 of this block
    v = vmat_ref[...]
    live = live_ref[...] > 0
    sims = _sims_block(v, q, cosine, dim)
    # fixed monotone normalizations (exec._hybrid_norms, verbatim): fusion
    # must commute with sharding, so no per-result-set min/max
    tnorm = dense / (dense + 1.0)
    if cosine:
        vnorm = (sims + 1.0) * 0.5
    else:
        vnorm = sims / (1.0 + jnp.abs(sims))
    s = alpha * tnorm + (1.0 - alpha) * vnorm
    s = jnp.where(live, s, -jnp.inf)
    vals, idxs = _block_topk(s, k)
    base = pl.program_id(1) * BLOCK
    _store_block(vals_ref, idx_ref, cnt_ref, vals, idxs, base,
                 jnp.sum(live.astype(jnp.int32)))


def hybrid_topk_tiles(dense, vmat, live, qvecs, alphas, k, cosine=False,
                      dim=None, interpret=None):
    """dense: (B, ND_pad) scatter-combined BM25 scores; vmat: (ND_pad,
    D_pad); live: (ND_pad,) int32; qvecs: (B, D_pad); alphas: (B,)."""
    interpret = resolve_interpret(interpret)
    bsz, nd = dense.shape
    dp = vmat.shape[1]
    assert nd % BLOCK == 0, nd
    assert dp % DIM_TILE == 0, dp
    nb = nd // BLOCK
    dim = dp if dim is None else dim
    d3 = dense.reshape(bsz, nb * BLOCK_ROWS, BLOCK_COLS)
    v3 = vmat.reshape(nb * BLOCK_ROWS, BLOCK_COLS, dp)
    l3 = live.reshape(nb * BLOCK_ROWS, BLOCK_COLS)
    vals, idx, cnt = _blocks_call(
        functools.partial(_hybrid_kernel, k=k, cosine=cosine, dim=dim),
        bsz, nb,
        [_query_spec(dp), _SMEM, _TILE, _vmat_spec(dp),
         _SHARED_TILE],
        interpret, "hybrid_topk",
    )(qvecs.reshape(bsz, 1, dp), alphas.astype(jnp.float32), d3, v3, l3)
    return vals, idx, cnt[..., 0]
