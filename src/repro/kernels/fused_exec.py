"""Pallas TPU kernels: fused per-family query execution.

One kernel per query family (term, bool, sort, range, facet), each doing the
whole per-segment plan stage — postings-block traversal, BM25 scoring,
live/filter masking, blockwise top-k (or histogram) — in a single
``pallas_call`` over CSR-tiled segment arrays.  ``repro.core.query.fused``
wraps these in jitted group entry points (device gather prologue, dense
scatter where a family needs doc-space combine, hierarchical XLA top-k
epilogue) so a whole FamilyGroup executes with zero host round-trips
between plan stages.

Layout contract (see ARCHITECTURE.md "fused execution"):

  * postings tiles: (B, P) gathered CSR rows with P % 1024 == 0, reshaped
    to (B, NB*8, 128) and walked with (1, 8, 128) blocks over grid (B, NB);
  * doc-space tiles: (B, ND_pad) dense arrays, same blocking, ND_pad is the
    segment's doc count padded to a 1024 multiple (padding docs are dead:
    live=0, freqs=0);
  * per-block winners: (B, NB, 128) vals/idx, entries past k are -inf/-1;
  * per-block hit counts ride in lane 0 of a (B, NB, 128) int32 output.

Mosaic legality (every block's trailing two dims are (8, 128) multiples or
the array's full extent):

  * per-query scalars (idf, range bounds, BM25 params) live in SMEM whole
    and are read at ``program_id(0)``;
  * the per-block outputs keep one query's (1, NB, 128) rows resident in
    VMEM across the block axis (index map ignores it) and each grid step
    stores its row at ``pl.ds(i, 1)``, so the axis is "arbitrary";
  * doc-side gathers never run in a kernel (Mosaic has no general gather):
    the jitted XLA prologue gathers the packed dl|live word per posting.

Selection parity: each block extracts its top-k by k unrolled max/argmax
steps with a smallest-flat-index tie-break, and flat index order is doc
order (postings are doc-sorted; doc-space blocks are doc-id order), so the
hierarchical merge reproduces ``jax.lax.top_k``'s lowest-index tie-break —
score descending, doc id ascending, Lucene's order.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_ROWS = 8
BLOCK_COLS = 128
BLOCK = BLOCK_ROWS * BLOCK_COLS  # postings/doc entries per grid step
OUT_K = 128  # top-k lane width per block (k <= 128 for the kernel path)


def _flat_iota():
    row = jax.lax.broadcasted_iota(jnp.int32, (BLOCK_ROWS, BLOCK_COLS), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (BLOCK_ROWS, BLOCK_COLS), 1)
    return row * BLOCK_COLS + col


def _block_topk(s, k: int):
    """Top-k of a scored (8,128) block by k unrolled max-extractions.

    Ties break to the smallest flat index (== smallest doc).  Returns
    ((1, OUT_K) vals, (1, OUT_K) in-block flat idx); entries past k are
    -inf / -1.  Mosaic-safe: reductions + selects only, no sort.
    """
    flat = _flat_iota()
    out_col = jax.lax.broadcasted_iota(jnp.int32, (1, OUT_K), 1)
    vals = jnp.full((1, OUT_K), -jnp.inf, jnp.float32)
    idxs = jnp.full((1, OUT_K), -1, jnp.int32)
    for j in range(k):
        m = jnp.max(s)
        pos = jnp.min(jnp.where(s == m, flat, BLOCK))
        vals = jnp.where(out_col == j, m, vals)
        idxs = jnp.where(out_col == j, pos, idxs)
        s = jnp.where(flat == pos, -jnp.inf, s)
    return vals, idxs


def _lane0(total):
    """(1, 128) int32 with ``total`` in lane 0 (reduction output layout)."""
    col = jax.lax.broadcasted_iota(jnp.int32, (1, BLOCK_COLS), 1)
    return jnp.where(col == 0, total, 0)


def _store_block(vals_ref, idx_ref, cnt_ref, vals, idxs, base, hits):
    """Store block ``program_id(1)``'s winners and hit count into its row
    of the query-resident (1, NB, 128) outputs; ``base`` offsets the
    in-block flat index to the flat position in the (B, P) row."""
    row = pl.ds(pl.program_id(1), 1)
    vals_ref[0, row, :] = vals
    idx_ref[0, row, :] = jnp.where(idxs >= 0, idxs + base, -1)
    cnt_ref[0, row, :] = _lane0(hits)


#: SMEM-resident whole array (per-query scalars, BM25 params)
_SMEM = pl.BlockSpec(memory_space=pltpu.SMEM)
#: (1, 8, 128) block of a (B, NB*8, 128) per-query tile array
_TILE = pl.BlockSpec((1, BLOCK_ROWS, BLOCK_COLS), lambda q, i: (q, i, 0))
#: (8, 128) block of an (NB*8, 128) doc-space array shared by the batch
_SHARED_TILE = pl.BlockSpec((BLOCK_ROWS, BLOCK_COLS), lambda q, i: (i, 0))


def _blocks_call(kernel, bsz, nb, in_specs, interpret, name):
    """``pallas_call`` named ``name`` over grid (B, NB) with the per-block
    winner outputs: ((B, NB, 128) vals, (B, NB, 128) flat idx, (B, NB, 128)
    lane-0 hit counts), each query's rows resident across the block
    axis."""
    resident = pl.BlockSpec((1, nb, OUT_K), lambda q, i: (q, 0, 0))
    return pl.pallas_call(
        kernel,
        grid=(bsz, nb),
        in_specs=in_specs,
        out_specs=[resident, resident, resident],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, nb, OUT_K), jnp.float32),
            jax.ShapeDtypeStruct((bsz, nb, OUT_K), jnp.int32),
            jax.ShapeDtypeStruct((bsz, nb, BLOCK_COLS), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
        name=name,
    )


# ---------------------------------------------------------------------------
# term: postings traversal + BM25 + live mask + top-k, all in-kernel
# ---------------------------------------------------------------------------


def _term_kernel(params_ref, idf_ref, g_ref, freqs_ref, vals_ref, idx_ref,
                 cnt_ref, *, k: int):
    avgdl = params_ref[0]
    k1 = params_ref[1]
    b = params_ref[2]
    idf = idf_ref[pl.program_id(0)]

    g = g_ref[0]  # (8,128) packed (doc_len << 1) | live of each posting
    freqs = freqs_ref[0]
    tf = freqs.astype(jnp.float32)
    dl = (g >> 1).astype(jnp.float32)
    valid = (freqs > 0) & ((g & 1) > 0)

    s = idf * (tf * (k1 + 1.0)) / (tf + k1 * (1.0 - b + b * dl / avgdl))
    s = jnp.where(valid, s, -jnp.inf)

    vals, idxs = _block_topk(s, k)
    base = pl.program_id(1) * BLOCK  # flat position within this (B,P) row
    _store_block(vals_ref, idx_ref, cnt_ref, vals, idxs, base,
                 jnp.sum(valid.astype(jnp.int32)))


def term_topk_tiles(g, freqs, idfs, avgdl, k1, b, k, interpret):
    """g: (B, P) packed (doc_len << 1) | live of each gathered posting
    (gathered by the caller's XLA prologue); freqs: (B, P), P % 1024 == 0;
    idfs: (B,).

    Returns per-block winners ((B, NB, 128) vals, (B, NB, 128) flat idx into
    the (B, P) row, (B, NB) hit counts)."""
    bsz, p = freqs.shape
    assert p % BLOCK == 0, p
    nb = p // BLOCK
    params = jnp.stack(
        [jnp.float32(avgdl), jnp.float32(k1), jnp.float32(b)]
    )
    g3 = g.reshape(bsz, nb * BLOCK_ROWS, BLOCK_COLS)
    f3 = freqs.reshape(bsz, nb * BLOCK_ROWS, BLOCK_COLS)
    vals, idx, cnt = _blocks_call(
        functools.partial(_term_kernel, k=k), bsz, nb,
        [_SMEM, _SMEM, _TILE, _TILE], interpret, "fused_term",
    )(params, idfs.astype(jnp.float32), g3, f3)
    return vals, idx, cnt[..., 0]


# ---------------------------------------------------------------------------
# bool: doc-space filter (count==T / count>0, live) + top-k over dense scores
# ---------------------------------------------------------------------------


def _bool_kernel(dense_ref, count_ref, live_ref, vals_ref, idx_ref, cnt_ref,
                 *, k: int, n_terms: int, conjunctive: bool):
    dense = dense_ref[0]
    count = count_ref[0]
    live = live_ref[...] > 0
    ok = (count == n_terms) if conjunctive else (count > 0)
    ok = ok & live
    s = jnp.where(ok, dense, -jnp.inf)
    vals, idxs = _block_topk(s, k)
    base = pl.program_id(1) * BLOCK  # doc-space blocks: flat idx == doc id
    _store_block(vals_ref, idx_ref, cnt_ref, vals, idxs, base,
                 jnp.sum(ok.astype(jnp.int32)))


def bool_topk_tiles(dense, count, live, k, n_terms, conjunctive, interpret):
    """dense/count: (B, ND_pad) scatter-combined scores and term counts;
    live: (ND_pad,) int32.  Returns ((B, NB, 128) vals, (B, NB, 128) doc
    ids, (B, NB) hit counts)."""
    bsz, nd = dense.shape
    assert nd % BLOCK == 0, nd
    nb = nd // BLOCK
    d3 = dense.reshape(bsz, nb * BLOCK_ROWS, BLOCK_COLS)
    c3 = count.reshape(bsz, nb * BLOCK_ROWS, BLOCK_COLS)
    l3 = live.reshape(nb * BLOCK_ROWS, BLOCK_COLS)
    vals, idx, cnt = _blocks_call(
        functools.partial(
            _bool_kernel, k=k, n_terms=n_terms, conjunctive=conjunctive
        ),
        bsz, nb, [_TILE, _TILE, _SHARED_TILE], interpret, "fused_bool",
    )(d3, c3, l3)
    return vals, idx, cnt[..., 0]


# ---------------------------------------------------------------------------
# sort: matched-doc mask + doc-values key + top-k (desc by dv)
# ---------------------------------------------------------------------------


def _sort_kernel(matched_ref, dv_ref, vals_ref, idx_ref, cnt_ref, *, k: int):
    m = matched_ref[0] > 0
    dv = dv_ref[...]  # (8,128) float32, shared across the batch
    s = jnp.where(m, dv, -jnp.inf)
    vals, idxs = _block_topk(s, k)
    base = pl.program_id(1) * BLOCK
    _store_block(vals_ref, idx_ref, cnt_ref, vals, idxs, base,
                 jnp.sum(m.astype(jnp.int32)))


def sort_topk_tiles(matched, dv, k, interpret):
    """matched: (B, ND_pad) int32; dv: (ND_pad,) float32."""
    bsz, nd = matched.shape
    assert nd % BLOCK == 0, nd
    nb = nd // BLOCK
    m3 = matched.reshape(bsz, nb * BLOCK_ROWS, BLOCK_COLS)
    v3 = dv.reshape(nb * BLOCK_ROWS, BLOCK_COLS)
    vals, idx, cnt = _blocks_call(
        functools.partial(_sort_kernel, k=k), bsz, nb,
        [_TILE, _SHARED_TILE], interpret, "fused_sort",
    )(m3, v3)
    return vals, idx, cnt[..., 0]


# ---------------------------------------------------------------------------
# range: doc-values window + live mask, constant score, lowest docs first
# ---------------------------------------------------------------------------


def _range_kernel(lo_ref, hi_ref, dv_ref, live_ref, vals_ref, idx_ref,
                  cnt_ref, *, k: int):
    lo = lo_ref[pl.program_id(0)]
    hi = hi_ref[pl.program_id(0)]
    dv = dv_ref[...]
    live = live_ref[...] > 0
    ok = (dv >= lo) & (dv <= hi) & live
    base = pl.program_id(1) * BLOCK
    # constant-score family: the selection key is -doc so the hierarchical
    # top-k surfaces the lowest doc ids first (Lucene order)
    gid = (base + _flat_iota()).astype(jnp.float32)
    s = jnp.where(ok, -gid, -jnp.inf)
    vals, idxs = _block_topk(s, k)
    _store_block(vals_ref, idx_ref, cnt_ref, vals, idxs, base,
                 jnp.sum(ok.astype(jnp.int32)))


def range_topk_tiles(dv, live, los, his, k, interpret):
    """dv: (ND_pad,) doc-values column; live: (ND_pad,) int32; los/his: (B,).

    Returned vals are the -doc selection keys (the caller maps finite keys
    to the constant score 1.0)."""
    bsz = los.shape[0]
    nd = dv.shape[0]
    assert nd % BLOCK == 0, nd
    nb = nd // BLOCK
    v3 = dv.reshape(nb * BLOCK_ROWS, BLOCK_COLS)
    l3 = live.reshape(nb * BLOCK_ROWS, BLOCK_COLS)
    vals, idx, cnt = _blocks_call(
        functools.partial(_range_kernel, k=k), bsz, nb,
        [_SMEM, _SMEM, _SHARED_TILE, _SHARED_TILE], interpret,
        "fused_range",
    )(los, his, v3, l3)
    return vals, idx, cnt[..., 0]


# ---------------------------------------------------------------------------
# facet: matched-doc histogram over a doc-values column (grid accumulation)
# ---------------------------------------------------------------------------


def _facet_kernel(matched_ref, bins_ref, hist_ref, cnt_ref, *, n_bins: int):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        hist_ref[...] = jnp.zeros(hist_ref.shape, jnp.float32)

    m = matched_ref[0] > 0
    # bincount parity: negative bins clip to 0, bins >= n_bins drop
    bins = jnp.maximum(bins_ref[...], 0)
    w = jnp.where(m & (bins < n_bins), 1.0, 0.0).astype(jnp.float32)
    nbp = hist_ref.shape[1]
    # per-lane partial histogram (bin on sublanes, doc lane on lanes): each
    # of the block's 8 rows broadcasts along sublanes against a bin iota,
    # so no lane<->sublane relayout; the caller reduces the 128 lanes
    bin_id = jax.lax.broadcasted_iota(jnp.int32, (nbp, BLOCK_COLS), 0)
    acc = hist_ref[0]
    for r in range(BLOCK_ROWS):
        acc = acc + jnp.where(bins[r : r + 1, :] == bin_id, w[r : r + 1, :], 0.0)
    hist_ref[0] = acc
    cnt_ref[0, pl.ds(pl.program_id(1), 1), :] = _lane0(
        jnp.sum(m.astype(jnp.int32))
    )


def facet_hist_tiles(matched, bins, n_bins, interpret):
    """matched: (B, ND_pad) int32; bins: (ND_pad,) int32.

    Returns ((B, n_bins) float32 counts, (B, NB) per-block match counts).
    The (1, nbp, 128) per-lane histogram block is revisited across the doc
    grid axis and accumulated in place (``pl.when`` zero-init on the first
    step); counts are integer-valued float32 sums (< 2^24), so neither the
    accumulation order nor the lane reduction can change the result vs
    ``jnp.bincount``.
    """
    bsz, nd = matched.shape
    assert nd % BLOCK == 0, nd
    nb = nd // BLOCK
    nbp = -(-n_bins // BLOCK_ROWS) * BLOCK_ROWS  # bins on sublanes
    m3 = matched.reshape(bsz, nb * BLOCK_ROWS, BLOCK_COLS)
    b3 = bins.reshape(nb * BLOCK_ROWS, BLOCK_COLS)
    hist, cnt = pl.pallas_call(
        functools.partial(_facet_kernel, n_bins=n_bins),
        grid=(bsz, nb),
        in_specs=[_TILE, _SHARED_TILE],
        out_specs=[
            pl.BlockSpec((1, nbp, BLOCK_COLS), lambda q, i: (q, 0, 0)),
            pl.BlockSpec((1, nb, BLOCK_COLS), lambda q, i: (q, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, nbp, BLOCK_COLS), jnp.float32),
            jax.ShapeDtypeStruct((bsz, nb, BLOCK_COLS), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
        name="fused_facet",
    )(m3, b3)
    return hist.sum(-1)[:, :n_bins], cnt[..., 0]
