"""Pallas TPU kernels: dense-vector similarity top-k (+ hybrid fusion).

The dense-retrieval analogue of ``fused_exec``: one kernel scores a whole
batch of query vectors against a segment's device-resident (ND_pad, D_pad)
vector column — dot or cosine similarity, live masking, and blockwise
top-k in a single ``pallas_call`` — and a second kernel fuses a dense BM25
column into the same pass for hybrid BM25 ⊕ vector queries.

Layout contract (same doc-space tiling as ``fused_exec``):

  * vector column: (ND_pad, D_pad) float32 with ND_pad % 1024 == 0 and
    D_pad % 128 == 0 (row padding = dead docs, column padding = zero
    components — both are exact no-ops for dot and cosine); the doc axis
    reshapes to (NB*8, 128), so a doc block is (8, 128, D_pad);
  * dense kernel (``vector_topk``): grid (NB,).  Each step holds one
    column block in VMEM and a loop scores every query of the wave
    against it, so a wave reads the column from HBM once, not once per
    query (a batch past ``MAX_WAVE`` queries makes one pass per
    ``MAX_WAVE``).  The (B, D_pad) query matrix is one whole block, a
    query's row loaded at a dynamic sublane offset.  Winners come
    out NB-major, (NB, B, 128) in (1, B, 128) blocks, one query's row
    stored at ``pl.ds(j, 1)``; each block's live count is one (1, 1, 128)
    lane-0 row.  The jitted epilogue transposes them to the query-major
    contract below;
  * hybrid kernel (``hybrid_topk``): still the ``fused_exec`` grid (B, NB)
    with one (1, 1, D_pad) query block and (8, 128, D_pad) vector blocks,
    so it re-reads the column for every query; alphas sit in SMEM;
  * per-block winners returned by both: (B, NB, 128) vals/idx, entries
    past k are -inf/-1, and (B, NB) hit counts — identical to the
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

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.fused_exec import (
    _SMEM,
    BLOCK,
    BLOCK_COLS,
    BLOCK_ROWS,
    OUT_K,
    _SHARED_TILE,
    _TILE,
    _block_topk,
    _blocks_call,
    _lane0,
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


#: queries scored per pass over the column; a larger batch makes one pass
#: per this many, which bounds the VMEM the query block and winners take
MAX_WAVE = 256


def _vector_wave_kernel(q_ref, vmat_ref, live_ref, vals_ref, idx_ref,
                        cnt_ref, *, k: int, cosine: bool, dim: int):
    """One doc block against every query of the wave: the (8, 128, D_pad)
    block stays in VMEM while a loop walks the queries, so the column
    crosses HBM once per wave.  Each query is scored, masked and selected
    by the same expressions as one step of a (B, NB) grid."""
    live = live_ref[...] > 0
    base = pl.program_id(0) * BLOCK  # doc-space blocks: flat idx == doc id
    # match-all-live semantics: every live doc is a hit, for every query
    cnt_ref[0] = _lane0(jnp.sum(live.astype(jnp.int32)))

    def one_query(j, carry):
        q = q_ref[j, :]     # (D_pad,)
        v = vmat_ref[...]   # (8, 128, D_pad) vector rows of this doc block
        s = jnp.where(live, _sims_block(v, q, cosine, dim), -jnp.inf)
        vals, idxs = _block_topk(s, k)
        row = pl.ds(j, 1)
        vals_ref[0, row, :] = vals
        idx_ref[0, row, :] = jnp.where(idxs >= 0, idxs + base, -1)
        return carry

    jax.lax.fori_loop(0, q_ref.shape[0], one_query, 0)


def _vmem_limit(bsz: int, dp: int) -> int:
    """Scoped VMEM for the wave kernel: every operand and output block
    double-buffered, and as much again for the compiler's own scratch."""
    rows = -(-bsz // BLOCK_ROWS) * BLOCK_ROWS  # sublane padding
    words = (BLOCK * dp           # column block
             + rows * dp          # queries
             + 2 * rows * OUT_K   # winners: vals, idx
             + 2 * BLOCK)         # live block, (8, 128)-padded count row
    return 2 * 2 * 4 * words      # 4-byte words


def vector_topk_tiles(vmat, live, qvecs, k, cosine=False, dim=None,
                      interpret=None):
    """vmat: (ND_pad, D_pad) float32 vector column; live: (ND_pad,) int32;
    qvecs: (B, D_pad); dim: true component count (D_pad lanes past it are
    layout padding).  Returns ((B, NB, 128) vals, (B, NB, 128) doc ids,
    (B, NB) live counts).

    Grid (NB,): each step holds one column block and scores the whole
    wave against it; the winners come out NB-major, (NB, B, 128), and the
    epilogue turns them query-major."""
    interpret = resolve_interpret(interpret)
    nd, dp = vmat.shape
    assert nd % BLOCK == 0, nd
    assert dp % DIM_TILE == 0, dp
    nb = nd // BLOCK
    bsz = qvecs.shape[0]
    dim = dp if dim is None else dim
    if bsz > MAX_WAVE:
        passes = [
            vector_topk_tiles(vmat, live, qvecs[s:s + MAX_WAVE], k, cosine,
                              dim, interpret)
            for s in range(0, bsz, MAX_WAVE)
        ]
        return tuple(jnp.concatenate(p) for p in zip(*passes))
    v3 = vmat.reshape(nb * BLOCK_ROWS, BLOCK_COLS, dp)
    l3 = live.reshape(nb * BLOCK_ROWS, BLOCK_COLS)
    wave = pl.BlockSpec((1, bsz, OUT_K), lambda i: (i, 0, 0))
    block = pl.BlockSpec((1, 1, BLOCK_COLS), lambda i: (i, 0, 0))
    vals, idx, cnt = pl.pallas_call(
        functools.partial(_vector_wave_kernel, k=k, cosine=cosine, dim=dim),
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((bsz, dp), lambda i: (0, 0)),
            pl.BlockSpec((BLOCK_ROWS, BLOCK_COLS, dp), lambda i: (i, 0, 0)),
            pl.BlockSpec((BLOCK_ROWS, BLOCK_COLS), lambda i: (i, 0)),
        ],
        out_specs=[wave, wave, block],
        out_shape=[
            jax.ShapeDtypeStruct((nb, bsz, OUT_K), jnp.float32),
            jax.ShapeDtypeStruct((nb, bsz, OUT_K), jnp.int32),
            jax.ShapeDtypeStruct((nb, 1, BLOCK_COLS), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_vmem_limit(bsz, dp),
        ),
        interpret=interpret,
        name="vector_topk",
    )(qvecs, v3, l3)
    return (jnp.swapaxes(vals, 0, 1), jnp.swapaxes(idx, 0, 1),
            jnp.broadcast_to(cnt[:, 0, 0], (bsz, nb)))


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
