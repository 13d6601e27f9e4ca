"""Fused group executors: one device dispatch per (FamilyGroup, segment).

This is the ``use_pallas`` data plane behind ``exec.execute_group``.  Where
the PR 1 batched executors stage padded (B, P) postings host-side and
re-upload them every batch, the fused path keeps each segment's CSR
device-resident (``cache.SegmentDeviceCache(tile=True)``) and ships only
(B,) start/length metadata (``plan.CsrTileMeta``); the gather, scoring,
masking and top-k all run inside ONE jitted program per segment — zero
host round-trips between plan stages.  Cross-segment merge stays on device
(``exec.merge_topk``); the single host fetch is the final trim.

Two selection backends live behind the same jit boundary:

  * ``use_kernel=True``: the Pallas kernels in ``kernels.fused_exec``
    (compiled on TPU/GPU, interpreted where forced via REPRO_FUSED_KERNEL).
    Doc-space families scatter dense scores in XLA first (scatter has no
    Mosaic lowering) and hand the kernel the filter+top-k half; the whole
    thing is still one dispatch.
  * ``use_kernel=False`` (CPU default): the exact vmapped ``_*_core``
    executors from ``exec.py`` — bit-identical oracles — inlined into the
    same fused program, so the zero-round-trip structure is preserved on
    hosts with no compiled Pallas backend.

Both backends produce bit-identical TopDocs: scores come from the same
elementwise expressions, and the kernels' per-block smallest-flat-index
tie-break composed with the hierarchical XLA top-k reproduces
``jax.lax.top_k``'s lowest-index (== ascending doc) tie-break.
"""

from __future__ import annotations

import os
from functools import partial
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.query import profile
from repro.core.query.exec import (
    _bool_core,
    _facet_core,
    _finalize_scored,
    _hybrid_core,
    _matched_core,
    _merge_segment_candidates,
    _range_core,
    _sort_core,
    _vector_core,
    bm25,
)
from repro.core.query.plan import (
    TILE,
    FamilyGroup,
    bucket_batch,
    bucket_batch_min2,
    stage_bool_meta,
    stage_term_meta,
)
from repro.core.query.cache import VEC_DIM_TILE
from repro.core.query.types import TopDocs
from repro.kernels import fused_exec as fk
from repro.kernels import vector_topk as vk
from repro.kernels.runtime import has_compiled_backend, resolve_interpret

assert TILE == fk.BLOCK, "plan.TILE must match kernels.fused_exec.BLOCK"
assert VEC_DIM_TILE == vk.DIM_TILE, (
    "cache.VEC_DIM_TILE must match kernels.vector_topk.DIM_TILE"
)

#: the kernels keep per-block winners in one 128-lane row
MAX_KERNEL_K = fk.OUT_K


def kernel_enabled(k: int = 1) -> bool:
    """Route through the Pallas kernels?  True on compiled backends (or
    when forced via REPRO_FUSED_KERNEL=1, e.g. interpret-mode parity
    tests); k > 128 always takes the jnp selection path."""
    if k > MAX_KERNEL_K:
        return False
    env = os.environ.get("REPRO_FUSED_KERNEL")
    if env is not None and env != "":
        return env not in ("0", "false", "no")
    return has_compiled_backend()


def _gather_rows(csr, starts, lengths, p):
    """Device-side CSR row gather: (..., ) starts/lengths -> (..., p) tiles.

    Out-of-row entries are (doc 0, freq 0) — exactly the host staging
    padding convention, so downstream masks treat them identically."""
    ar = jnp.arange(p, dtype=jnp.int32)
    idx = jnp.clip(starts[..., None] + ar, 0, csr.shape[0] - 1)
    return jnp.where(ar < lengths[..., None], csr[idx], 0)


def _hier_topk(blk_vals, blk_idx, k):
    """Merge (B, NB, 128) per-block winners: block-major flatten + XLA
    top-k.  Returns ((B, kk) vals, (B, kk) flat idx; -1 where empty)."""
    bsz = blk_vals.shape[0]
    flat_v = blk_vals.reshape(bsz, -1)
    flat_i = blk_idx.reshape(bsz, -1)
    kk = min(k, flat_v.shape[1])
    vals, pos = jax.lax.top_k(flat_v, kk)
    return vals, jnp.take_along_axis(flat_i, pos, axis=-1)


# ---------------------------------------------------------------------------
# jitted per-segment programs (static: tile width, k, backend selection)
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("ps", "k", "use_kernel", "interpret"))
def _fused_term_all(csr_docs_t, csr_freqs_t, dl_live_t, starts_t, lengths_t,
                    bases_t, idfs, avgdl, k1, b, ps, k, use_kernel,
                    interpret):
    """The whole term group — every segment's gather + score + filter +
    top-k AND the cross-segment merge — as ONE program / one dispatch.

    Both selection paths read the packed dl|live word with one doc-side
    gather here in XLA (the kernel takes the gathered words as a tile, so
    no gather runs in Mosaic).  The jnp path scores via the same
    elementwise ``bm25`` expression as ``exec._term_core`` and skips the
    padding mask on gathered doc ids: out-of-row lanes carry arbitrary doc
    ids but are dead via ``freqs == 0`` (score ``-inf``), so they can never
    surface in a finite result row.
    """
    per_v, per_i, per_h = [], [], []
    for i, p in enumerate(ps):
        ar = jnp.arange(p, dtype=jnp.int32)
        idx = jnp.clip(
            starts_t[i][:, None] + ar, 0, csr_docs_t[i].shape[0] - 1
        )
        inrow = ar < lengths_t[i][:, None]
        freqs = jnp.where(inrow, csr_freqs_t[i][idx], 0)
        docs = csr_docs_t[i][idx]
        g = dl_live_t[i][docs]
        if use_kernel:
            blk_v, blk_i, blk_c = fk.term_topk_tiles(
                g, freqs, idfs, avgdl, k1, b, k, interpret
            )
            vals, pidx = _hier_topk(blk_v, blk_i, k)
            ids = jnp.take_along_axis(
                docs, jnp.clip(pidx, 0, p - 1), axis=-1
            )
            ids = jnp.where(pidx >= 0, ids, -1)
            hits = blk_c.sum(-1)
        else:
            score = bm25(freqs, g >> 1, idfs[:, None], avgdl, k1, b)
            valid = (freqs > 0) & ((g & 1) > 0)
            score = jnp.where(valid, score, -jnp.inf)
            vals, pos = jax.lax.top_k(score, min(k, p))
            ids = jnp.take_along_axis(docs, pos, axis=-1)
            hits = valid.sum(-1)
        per_v.append(vals)
        per_i.append(ids + bases_t[i])
        per_h.append(hits)
    vals = jnp.concatenate(per_v, axis=1)
    ids = jnp.concatenate(per_i, axis=1)
    totals = per_h[0]
    for h in per_h[1:]:
        totals = totals + h
    # same merge expressions as exec.merge_topk / exec._concat_merge
    kk = min(k, vals.shape[1])
    order = jnp.lexsort((ids, -vals), axis=-1)[:, :kk]
    return (
        jnp.take_along_axis(vals, order, axis=-1),
        jnp.take_along_axis(ids, order, axis=-1),
        totals,
    )


@partial(
    jax.jit,
    static_argnames=("p", "k", "n_terms", "conjunctive", "use_kernel",
                     "interpret"),
)
def _fused_bool(csr_docs, csr_freqs, dl, live, starts, lengths, idfs,
                avgdl, k1, b, base, p, k, n_terms, conjunctive, use_kernel,
                interpret):
    docs = _gather_rows(csr_docs, starts, lengths, p)  # (B, T, p)
    freqs = _gather_rows(csr_freqs, starts, lengths, p)
    if use_kernel:
        ndp = live.shape[0]

        def scatter_one(d, f, i_):
            # same scatter-combine expressions as exec._bool_core, over the
            # TILE-padded doc space (padding docs receive no updates)
            score = bm25(f, dl[d], i_[:, None], avgdl, k1, b)
            valid = f > 0
            score = jnp.where(valid, score, 0.0)
            dense = (
                jnp.zeros(ndp, jnp.float32).at[d.ravel()].add(score.ravel())
            )
            count = (
                jnp.zeros(ndp, jnp.int32)
                .at[d.ravel()]
                .add(valid.ravel().astype(jnp.int32))
            )
            return dense, count

        dense, count = jax.vmap(scatter_one)(docs, freqs, idfs)
        blk_v, blk_i, blk_c = fk.bool_topk_tiles(
            dense, count, live, k, n_terms, conjunctive, interpret
        )
        vals, ids = _hier_topk(blk_v, blk_i, k)  # doc-space: idx == doc id
        return vals, ids + base, blk_c.sum(-1)
    vals, ids, hits = jax.vmap(
        lambda d, f, i: _bool_core(
            d, f, i, dl, live, avgdl, k1, b, k, conjunctive, n_terms
        )
    )(docs, freqs, idfs)
    return vals, ids + base, hits


@partial(jax.jit, static_argnames=("p", "k", "use_kernel", "interpret"))
def _fused_sort(csr_docs, csr_freqs, dv, live, starts, lengths, base, p, k,
                use_kernel, interpret):
    docs = _gather_rows(csr_docs, starts, lengths, p)
    freqs = _gather_rows(csr_freqs, starts, lengths, p)
    if use_kernel:
        ndp = live.shape[0]

        def matched_one(d, f):
            valid = (f > 0) & (live[d] > 0)
            # scatter-max: padding rows alias doc 0 (see exec._sort_core)
            return jnp.zeros(ndp, bool).at[d].max(valid, mode="drop")

        matched = jax.vmap(matched_one)(docs, freqs).astype(jnp.int32)
        blk_v, blk_i, blk_c = fk.sort_topk_tiles(
            matched, dv.astype(jnp.float32), k, interpret
        )
        vals, ids = _hier_topk(blk_v, blk_i, k)
        return vals, ids + base, blk_c.sum(-1)
    vals, ids, hits = jax.vmap(lambda d, f: _sort_core(d, f, dv, live, k))(
        docs, freqs
    )
    return vals, ids + base, hits


@partial(jax.jit, static_argnames=("k", "use_kernel", "interpret"))
def _fused_range(dv, live, los, his, base, k, use_kernel, interpret):
    if use_kernel:
        blk_v, blk_i, blk_c = fk.range_topk_tiles(
            dv, live, los, his, k, interpret
        )
        keys, ids = _hier_topk(blk_v, blk_i, k)
        vals = jnp.where(jnp.isfinite(keys), 1.0, -jnp.inf)
        return vals, ids + base, blk_c.sum(-1)
    vals, ids, hits = jax.vmap(
        lambda lo, hi: _range_core(dv, live, lo, hi, k)
    )(los, his)
    return vals, ids + base, hits


@partial(
    jax.jit,
    static_argnames=("p", "n_bins", "match_all", "use_kernel", "interpret"),
)
def _fused_facet(csr_docs, csr_freqs, live, dv, starts, lengths, p, n_bins,
                 match_all, use_kernel, interpret):
    bins = dv.astype(jnp.int32)
    live_b = live.astype(bool)
    if match_all:
        matched = live_b[None, :]  # one row; caller replicates host-side
    else:
        docs = _gather_rows(csr_docs, starts, lengths, p)
        freqs = _gather_rows(csr_freqs, starts, lengths, p)
        matched = jax.vmap(lambda d, f: _matched_core(d, f, live_b))(
            docs, freqs
        )
    if use_kernel:
        hist, blk_c = fk.facet_hist_tiles(
            matched.astype(jnp.int32), bins, n_bins, interpret
        )
        return hist, blk_c.sum(-1)
    counts = jax.vmap(lambda m: _facet_core(m, bins, n_bins))(matched)
    return counts, matched.sum(-1)


@partial(
    jax.jit, static_argnames=("k", "cosine", "dim", "use_kernel", "interpret")
)
def _fused_vector(vmat, live, qvecs, base, k, cosine, dim, use_kernel,
                  interpret):
    if use_kernel:
        blk_v, blk_i, blk_c = vk.vector_topk_tiles(
            vmat, live, qvecs, k, cosine, dim, interpret
        )
        vals, ids = _hier_topk(blk_v, blk_i, k)  # doc-space: idx == doc id
        return vals, ids + base, blk_c.sum(-1)
    vals, ids, hits = jax.vmap(
        lambda q: _vector_core(vmat, live, q, k, cosine)
    )(qvecs)
    return vals, ids + base, hits


@partial(
    jax.jit,
    static_argnames=("p", "k", "cosine", "dim", "use_kernel", "interpret"),
)
def _fused_hybrid(csr_docs, csr_freqs, dl, vmat, live, starts, lengths,
                  qvecs, idfs, alphas, avgdl, k1, b, base, p, k, cosine,
                  dim, use_kernel, interpret):
    """Hybrid BM25 ⊕ vector for one segment as ONE jitted combined program
    (no dedicated Pallas kernel for the BM25 scatter: scatter has no Mosaic
    lowering, so — as for bool/sort — XLA scatters the dense term scores
    and the ``vector_topk`` hybrid kernel fuses normalization, similarity,
    masking and top-k)."""
    docs = _gather_rows(csr_docs, starts, lengths, p)  # (B, p)
    freqs = _gather_rows(csr_freqs, starts, lengths, p)
    if use_kernel:
        ndp = live.shape[0]

        def scatter_one(d, f, i_):
            # same dense-BM25 expressions as exec._hybrid_core: one term
            # per row, docs unique per postings row -> one add per doc
            s = bm25(f, dl[d], i_, avgdl, k1, b)
            s = jnp.where(f > 0, s, 0.0)
            return jnp.zeros(ndp, jnp.float32).at[d].add(s)

        dense = jax.vmap(scatter_one)(docs, freqs, idfs)
        blk_v, blk_i, blk_c = vk.hybrid_topk_tiles(
            dense, vmat, live, qvecs, alphas, k, cosine, dim, interpret
        )
        vals, ids = _hier_topk(blk_v, blk_i, k)
        return vals, ids + base, blk_c.sum(-1)
    vals, ids, hits = jax.vmap(
        lambda d, f, q, i, a: _hybrid_core(
            d, f, dl, vmat, live, q, i, avgdl, k1, b, a, k, cosine
        )
    )(docs, freqs, qvecs, idfs, alphas)
    return vals, ids + base, hits


# ---------------------------------------------------------------------------
# group executors (signature-compatible with exec._exec_*)
# ---------------------------------------------------------------------------


def _seg_state(ctx, seg, use_kernel):
    """Device arrays for ``seg``; tiles lazily if the cache was built
    untiled."""
    st = ctx.device_cache.ensure_tiled(seg, fallback=ctx._transient_dev)
    if use_kernel:
        return st, st["tiled.doc_lens"], st["tiled.live"]
    return st, st["doc_lens"], st["live"]


def exec_term_fused(ctx, group: FamilyGroup, k: int) -> List[TopDocs]:
    n = len(group.queries)
    pad = bucket_batch(n) - n
    use_kernel = kernel_enabled(k)
    interpret = resolve_interpret(None)
    args = ([], [], [], [], [], [])  # per-seg arg tuples
    ps: List[int] = []
    with profile.span("exec.stage"):
        # metadata stays numpy: the pjit C++ dispatch converts (B,)-sized
        # args far cheaper than a Python-level device_put per segment
        idfs = np.asarray(
            [ctx.idf(q) for q in group.queries] + [0.0] * pad,
            dtype=np.float32,
        )
        for seg in ctx.segments:
            meta = stage_term_meta(
                seg, group.queries, pad_rows=pad, tile=use_kernel
            )
            if meta is None:
                continue
            st, _, _ = _seg_state(ctx, seg, use_kernel)
            for lst, v in zip(
                args,
                (st["csr.docs"], st["csr.freqs"], st["tiled.dl_live"],
                 meta.starts, meta.lengths, np.int32(seg.base_doc)),
            ):
                lst.append(v)
            ps.append(meta.p)
    if not ps:
        return _merge_segment_candidates([], n, k)
    with profile.span("exec.dispatch", program="_fused_term_all"):
        vals, ids, totals = _fused_term_all(
            *(tuple(a) for a in args), idfs, ctx.avgdl, ctx.k1, ctx.b,
            ps=tuple(ps), k=k, use_kernel=use_kernel, interpret=interpret,
        )
        profile.record("fused.term")  # the whole group: ONE dispatch
    return _finalize_scored(vals, ids, totals, n)


def exec_bool_fused(ctx, group: FamilyGroup, k: int) -> List[TopDocs]:
    n = len(group.queries)
    pad = bucket_batch(n) - n
    mode, n_terms = group.key[1], group.key[2]
    conj = mode == "and"
    with profile.span("exec.stage"):
        idfs = np.zeros((n + pad, n_terms), dtype=np.float32)
        for i, q in enumerate(group.queries):
            idfs[i] = [ctx.idf(t) for t in q.terms]
    use_kernel = kernel_enabled(k)
    interpret = resolve_interpret(None)
    per_seg = []
    for seg in ctx.segments:
        with profile.span("exec.stage"):
            meta = stage_bool_meta(
                seg, group.queries, pad_rows=pad, tile=use_kernel
            )
        if meta is None:
            continue
        st, dl, live = _seg_state(ctx, seg, use_kernel)
        with profile.span("exec.dispatch", program="_fused_bool"):
            vals, ids, hits = _fused_bool(
                st["csr.docs"], st["csr.freqs"], dl, live,
                meta.starts, meta.lengths, idfs,
                ctx.avgdl, ctx.k1, ctx.b, seg.base_doc,
                p=meta.p, k=k, n_terms=n_terms, conjunctive=conj,
                use_kernel=use_kernel, interpret=interpret,
            )
            profile.record("fused.bool")
        per_seg.append((vals, ids, hits))
    return _merge_segment_candidates(per_seg, n, k)


def exec_sort_fused(ctx, group: FamilyGroup, k: int) -> List[TopDocs]:
    n = len(group.queries)
    pad = bucket_batch(n) - n
    dv_field = group.key[1]
    terms = [q.term for q in group.queries]
    use_kernel = kernel_enabled(k)
    interpret = resolve_interpret(None)
    per_seg = []
    for seg in ctx.segments:
        with profile.span("exec.stage"):
            meta = stage_term_meta(seg, terms, pad_rows=pad, tile=use_kernel)
        if meta is None:
            continue
        st, _, live = _seg_state(ctx, seg, use_kernel)
        dv = st[f"tiled.dv.{dv_field}" if use_kernel else f"dv.{dv_field}"]
        with profile.span("exec.dispatch", program="_fused_sort"):
            vals, ids, hits = _fused_sort(
                st["csr.docs"], st["csr.freqs"], dv, live,
                meta.starts, meta.lengths, seg.base_doc,
                p=meta.p, k=k, use_kernel=use_kernel, interpret=interpret,
            )
            profile.record("fused.sort")
        per_seg.append((vals, ids, hits))
    return _merge_segment_candidates(per_seg, n, k)


def exec_range_fused(ctx, group: FamilyGroup, k: int) -> List[TopDocs]:
    n = len(group.queries)
    pad = bucket_batch(n) - n
    dv_field = group.key[1]
    with profile.span("exec.stage"):
        los = np.asarray(
            [q.lo for q in group.queries] + [0] * pad, dtype=np.int32
        )
        his = np.asarray(
            [q.hi for q in group.queries] + [-1] * pad, dtype=np.int32
        )
    use_kernel = kernel_enabled(k)
    interpret = resolve_interpret(None)
    per_seg = []
    for seg in ctx.segments:
        st, _, live = _seg_state(ctx, seg, use_kernel)
        dv = st[f"tiled.dv.{dv_field}" if use_kernel else f"dv.{dv_field}"]
        with profile.span("exec.dispatch", program="_fused_range"):
            vals, ids, hits = _fused_range(
                dv, live, los, his, seg.base_doc,
                k=k, use_kernel=use_kernel, interpret=interpret,
            )
            profile.record("fused.range")
        per_seg.append((vals, ids, hits))
    return _merge_segment_candidates(per_seg, n, k)


def exec_facet_fused(ctx, group: FamilyGroup, k: int) -> List[TopDocs]:
    n = len(group.queries)
    dv_field, n_bins, match_all = group.key[1], group.key[2], group.key[3]
    use_kernel = kernel_enabled()
    interpret = resolve_interpret(None)
    # device-side accumulation across segments: counts are integer-valued
    # float32 (< 2^24), so adding per-segment histograms on device is exact
    # — one host fetch at the end instead of one per segment
    counts_dev = None
    totals_dev = None
    for seg in ctx.segments:
        if match_all:
            meta = None
            starts = lengths = np.zeros(1, np.int32)
            p = TILE
        else:
            pad = bucket_batch(n) - n
            with profile.span("exec.stage"):
                meta = stage_term_meta(
                    seg,
                    [q.term for q in group.queries],
                    pad_rows=pad,
                    tile=use_kernel,
                )
            if meta is None:
                continue
            starts = meta.starts
            lengths = meta.lengths
            p = meta.p
        st, _, live = _seg_state(ctx, seg, use_kernel)
        dv = st[f"tiled.dv.{dv_field}" if use_kernel else f"dv.{dv_field}"]
        with profile.span("exec.dispatch", program="_fused_facet"):
            c, t = _fused_facet(
                st["csr.docs"], st["csr.freqs"], live, dv, starts, lengths,
                p=p, n_bins=n_bins, match_all=match_all,
                use_kernel=use_kernel, interpret=interpret,
            )
            profile.record("fused.facet")
        counts_dev = c if counts_dev is None else counts_dev + c
        totals_dev = t if totals_dev is None else totals_dev + t
    if counts_dev is None:
        counts = np.zeros((n, n_bins), dtype=np.float64)
        totals = np.zeros(n, dtype=np.int64)
    else:
        with profile.span("exec.fetch"):
            counts = np.asarray(counts_dev, dtype=np.float64)
            totals = np.asarray(totals_dev, dtype=np.int64)
        if match_all:  # identical per query: replicate the single row
            counts = np.repeat(counts, n, axis=0)
            totals = np.repeat(totals, n)
        else:
            counts = counts[:n]
            totals = totals[:n]
    out = []
    for i in range(n):
        order = np.argsort(-counts[i], kind="stable")[:k]
        out.append(
            TopDocs(
                int(totals[i]),
                order.astype(np.int64),
                counts[i][order].astype(np.float32),
                facets=counts[i],
            )
        )
    return out


def _vector_group_inputs(group, pad: int, dim: int, use_kernel: bool):
    """(B+pad, D) query-vector matrix, lane-padded for the kernel path
    (zero components are exact scoring no-ops)."""
    dimp = vk.pad_dim(dim) if use_kernel else dim
    qvecs = np.zeros((len(group.queries) + pad, dimp), dtype=np.float32)
    return qvecs


def exec_vector_fused(ctx, group: FamilyGroup, k: int) -> List[TopDocs]:
    from repro.core.writer import VECTOR_FIELD

    n = len(group.queries)
    pad = bucket_batch(n) - n
    dim, metric = group.key[1], group.key[2]
    cosine = metric == "cosine"
    use_kernel = kernel_enabled(k)
    interpret = resolve_interpret(None)
    with profile.span("exec.stage"):
        qvecs = _vector_group_inputs(group, pad, dim, use_kernel)
        for i, q in enumerate(group.queries):
            qvecs[i, :dim] = q.vector
    per_seg = []
    for seg in ctx.segments:
        if VECTOR_FIELD not in seg.doc_values:
            continue  # no vector column here: contributes nothing
        st, _, live = _seg_state(ctx, seg, use_kernel)
        vmat = st[
            f"tiled.dv.{VECTOR_FIELD}" if use_kernel else f"dv.{VECTOR_FIELD}"
        ]
        with profile.span("exec.dispatch", program="_fused_vector"):
            vals, ids, hits = _fused_vector(
                vmat, live, qvecs, seg.base_doc,
                k=k, cosine=cosine, dim=dim, use_kernel=use_kernel,
                interpret=interpret,
            )
            profile.record("fused.vector")
        per_seg.append((vals, ids, hits))
    return _merge_segment_candidates(per_seg, n, k)


def exec_hybrid_fused(ctx, group: FamilyGroup, k: int) -> List[TopDocs]:
    from repro.core.writer import VECTOR_FIELD

    n = len(group.queries)
    # floor 2: the B=1 vmapped graph compiles to different blend rounding
    pad = bucket_batch_min2(n) - n
    dim, metric = group.key[1], group.key[2]
    cosine = metric == "cosine"
    use_kernel = kernel_enabled(k)
    interpret = resolve_interpret(None)
    terms = [q.term for q in group.queries]
    with profile.span("exec.stage"):
        qvecs = _vector_group_inputs(group, pad, dim, use_kernel)
        for i, q in enumerate(group.queries):
            qvecs[i, :dim] = q.vector.vector
        idfs = np.asarray(
            [ctx.idf(t) for t in terms] + [0.0] * pad, dtype=np.float32
        )
        alphas = np.asarray(
            [q.alpha for q in group.queries] + [0.0] * pad, dtype=np.float32
        )
    per_seg = []
    for seg in ctx.segments:
        if VECTOR_FIELD not in seg.doc_values:
            continue
        with profile.span("exec.stage"):
            meta = stage_term_meta(seg, terms, pad_rows=pad, tile=use_kernel)
        if meta is None:
            # match-all-live: the term scores nothing here, but the vector
            # half still ranks every live doc (dense BM25 sum = 0)
            starts = np.zeros(n + pad, dtype=np.int32)
            lengths = np.zeros(n + pad, dtype=np.int32)
            p = 8
        else:
            starts, lengths, p = meta.starts, meta.lengths, meta.p
        st, dl, live = _seg_state(ctx, seg, use_kernel)
        vmat = st[
            f"tiled.dv.{VECTOR_FIELD}" if use_kernel else f"dv.{VECTOR_FIELD}"
        ]
        with profile.span("exec.dispatch", program="_fused_hybrid"):
            vals, ids, hits = _fused_hybrid(
                st["csr.docs"], st["csr.freqs"], dl, vmat, live,
                starts, lengths, qvecs, idfs, alphas,
                ctx.avgdl, ctx.k1, ctx.b, seg.base_doc,
                p=p, k=k, cosine=cosine, dim=dim, use_kernel=use_kernel,
                interpret=interpret,
            )
            profile.record("fused.hybrid")
        per_seg.append((vals, ids, hits))
    return _merge_segment_candidates(per_seg, n, k)
