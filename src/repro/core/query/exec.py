"""Per-family batched executors + device-side cross-segment top-k merge.

The data plane under the paper's Fig 5 query families (§2.1: search is a
lock-free scan over immutable segments, merged across segments — and, in
the sharded layer, across shards via the same ``merge_topk``).

Layering (see ARCHITECTURE.md):

  plan.py   groups/pads a batch of queries      (host, numpy)
  exec.py   scores a whole same-family batch against each segment in ONE
            jitted dispatch (vmapped over the batch dim), then merges the
            per-segment candidates on device — replacing the per-query
            Python loop + heapq merge of the sequential path
  cache.py  owns the device residency of segment arrays

The unbatched jitted primitives live here too: they are both the oracle for
the batched path (exact BM25 + tie-break parity is asserted in tests) and
the reference semantics for the fused Pallas kernels in
``repro.kernels.fused_exec``.

Every score is computed by the *same* elementwise expression in both paths
(the batch kernels are ``jax.vmap`` of the same cores), so batched results
are bit-identical to sequential ones; candidate selection differs only in
shared padding, which contributes ``-inf`` rows that trim away.
"""

from __future__ import annotations

from functools import partial
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.query import profile
from repro.core.query.plan import (
    FamilyGroup,
    bucket_batch,
    bucket_batch_min2,
    stage_bool_postings,
    stage_term_postings,
)
from repro.core.query.types import (
    FacetQuery,
    RangeQuery,
    SortQuery,
    TermQuery,
    TopDocs,
    empty_topdocs,
)

# ---------------------------------------------------------------------------
# scoring cores (shared verbatim by the single and batched paths)
# ---------------------------------------------------------------------------


def bm25(tf, dl, idf, avgdl, k1, b):
    tf = tf.astype(jnp.float32)
    dl = dl.astype(jnp.float32)
    return idf * (tf * (k1 + 1.0)) / (tf + k1 * (1.0 - b + b * dl / avgdl))


def _term_core(docs, freqs, doc_lens, live, idf, avgdl, k1, b, k):
    """Single-term: top-k straight over the postings list."""
    dl = doc_lens[docs]
    score = bm25(freqs, dl, idf, avgdl, k1, b)
    valid = (freqs > 0) & live[docs]
    score = jnp.where(valid, score, -jnp.inf)
    vals, idx = jax.lax.top_k(score, min(k, score.shape[0]))
    return vals, docs[idx], valid.sum()


def _bool_core(
    docs, freqs, idfs, doc_lens, live, avgdl, k1, b, k, conjunctive, n_terms
):
    """Boolean over T terms: dense scatter-combine on the segment, then top-k.

    docs/freqs: (T, P) padded postings (freq 0 = padding).
    """
    n_docs = doc_lens.shape[0]
    dl = doc_lens[docs]
    score = bm25(freqs, dl, idfs[:, None], avgdl, k1, b)
    valid = freqs > 0
    score = jnp.where(valid, score, 0.0)
    dense = jnp.zeros(n_docs, jnp.float32).at[docs.ravel()].add(score.ravel())
    count = (
        jnp.zeros(n_docs, jnp.int32)
        .at[docs.ravel()]
        .add(valid.ravel().astype(jnp.int32))
    )
    ok = (count == n_terms) if conjunctive else (count > 0)
    ok = ok & live
    dense = jnp.where(ok, dense, -jnp.inf)
    vals, ids = jax.lax.top_k(dense, min(k, dense.shape[0]))
    return vals, ids, ok.sum()


def _sort_core(docs, freqs, dv, live, k):
    """Matches of one term ordered by a doc-values column (desc)."""
    n_docs = dv.shape[0]
    valid = (freqs > 0) & live[docs]
    # scatter-max, not set: padding rows alias doc 0 (docs=0, valid=False)
    # and an in-order .set would overwrite a real match of local doc 0
    matched = jnp.zeros(n_docs, bool).at[docs].max(valid, mode="drop")
    key = jnp.where(matched, dv.astype(jnp.float32), -jnp.inf)
    vals, ids = jax.lax.top_k(key, min(k, key.shape[0]))
    return vals, ids, matched.sum()


def _range_core(dv, live, lo, hi, k):
    n_docs = dv.shape[0]
    ok = (dv >= lo) & (dv <= hi) & live
    # constant-score; return lowest doc ids first (Lucene order)
    key = jnp.where(ok, -jnp.arange(n_docs, dtype=jnp.float32), -jnp.inf)
    vals, ids = jax.lax.top_k(key, min(k, key.shape[0]))
    return jnp.where(jnp.isfinite(vals), 1.0, -jnp.inf), ids, ok.sum()


def _similarity(vmat, qvec, cosine):
    """Shared similarity expression: dot or cosine of every row of the
    (n_docs, d) vector column against one (d,) query vector.  The single,
    batched, and Pallas paths all reduce the same trailing axis with the
    same values, so the float32 results are bit-identical (the parity tests
    pin this).  Docs without a vector are zero rows: dot 0, cosine guarded
    to 0 (den == 0)."""
    sims = jnp.sum(vmat * qvec, axis=-1)
    if cosine:
        den = jnp.sqrt(jnp.sum(vmat * vmat, axis=-1)) * jnp.sqrt(
            jnp.sum(qvec * qvec)
        )
        sims = jnp.where(den > 0, sims / den, 0.0)
    return sims


def _vector_core(vmat, live, qvec, k, cosine):
    """Brute-force exact top-k over the dense vector column (match-all-live
    semantics: every live doc is a candidate)."""
    score = jnp.where(live, _similarity(vmat, qvec, cosine), -jnp.inf)
    vals, ids = jax.lax.top_k(score, min(k, score.shape[0]))
    return vals, ids, live.sum()


def _hybrid_norms(dense_bm25, sims, alpha, cosine):
    """Fused score from a doc's BM25 sum and vector similarity.

    Normalizations are FIXED monotone maps (no per-result-set min/max), so
    fusion commutes with sharding: tnorm = s/(s+1) in [0,1); vnorm =
    (c+1)/2 for cosine (c in [-1,1]) and c/(1+|c|) for dot (unbounded c).
    """
    tnorm = dense_bm25 / (dense_bm25 + 1.0)
    if cosine:
        vnorm = (sims + 1.0) * 0.5
    else:
        vnorm = sims / (1.0 + jnp.abs(sims))
    return alpha * tnorm + (1.0 - alpha) * vnorm


def _hybrid_core(
    docs, freqs, doc_lens, vmat, live, qvec, idf, avgdl, k1, b, alpha, k,
    cosine,
):
    """BM25 ⊕ vector fusion over all live docs: the term's postings scatter
    BM25 into a dense column (docs without the term contribute 0), the
    vector similarity is dense already, and the fixed-normalization
    weighted sum ranks every live doc."""
    n_docs = doc_lens.shape[0]
    dl = doc_lens[docs]
    s = bm25(freqs, dl, idf, avgdl, k1, b)
    s = jnp.where(freqs > 0, s, 0.0)
    dense = jnp.zeros(n_docs, jnp.float32).at[docs].add(s)
    sims = _similarity(vmat, qvec, cosine)
    score = _hybrid_norms(dense, sims, alpha, cosine)
    score = jnp.where(live, score, -jnp.inf)
    vals, ids = jax.lax.top_k(score, min(k, score.shape[0]))
    return vals, ids, live.sum()


def _matched_core(docs, freqs, live):
    n_docs = live.shape[0]
    valid = freqs > 0
    # scatter-max for the same doc-0 padding-alias reason as _sort_core
    m = jnp.zeros(n_docs, bool).at[docs].max(valid, mode="drop")
    return m & live


def _facet_core(matched, dv_bins, n_bins):
    """Histogram of a doc-values column over matching docs (the columnar
    scan whose storage sensitivity the paper calls out).  bincount is the
    shared definition for both paths: negative bins clip to 0, bins >=
    n_bins drop."""
    return jnp.bincount(
        dv_bins, weights=matched.astype(jnp.float32), length=n_bins
    )


# -- unbatched jitted primitives (sequential/oracle path) -------------------

_term_topk = partial(jax.jit, static_argnames=("k",))(_term_core)
_bool_topk = partial(
    jax.jit, static_argnames=("k", "conjunctive", "n_terms")
)(_bool_core)
_sort_topk = partial(jax.jit, static_argnames=("k",))(_sort_core)
_range_topk = partial(jax.jit, static_argnames=("k",))(_range_core)
_facet_counts = partial(jax.jit, static_argnames=("n_bins",))(_facet_core)
_matched_from_postings = jax.jit(_matched_core)


def _vector_topk(vmat, live, qvec, k, cosine):
    """Single-query dense retrieval == the batched executor at B=1.

    Routed through ``_vector_topk_batch`` rather than jitting the core
    directly: XLA may reassociate the similarity/fusion arithmetic
    differently for the unbatched and vmapped graphs (observed as 1-ULP
    score drift), and the oracle contract is BIT-parity — so there is
    exactly one compiled definition of the score for every path.
    """
    vals, ids, hits = _vector_topk_batch(vmat, live, qvec[None], k, cosine)
    return vals[0], ids[0], hits[0]


def _hybrid_topk(
    docs, freqs, doc_lens, vmat, live, qvec, idf, avgdl, k1, b, alpha, k,
    cosine,
):
    """Single-query hybrid fusion == the batched executor at B=2.

    One real row + one inert row, NOT B=1: XLA squeezes a B=1 vmapped
    graph and re-fuses the blend arithmetic a ULP differently than every
    B >= 2 graph (which agree bitwise) — same reason the batched hybrid
    executors pad with ``bucket_batch_min2``."""
    vals, ids, hits = _hybrid_topk_batch(
        jnp.stack([docs, jnp.zeros_like(docs)]),
        jnp.stack([freqs, jnp.zeros_like(freqs)]),
        doc_lens,
        vmat,
        live,
        jnp.stack([qvec, jnp.zeros_like(qvec)]),
        jnp.asarray([idf, 0.0], jnp.float32),
        avgdl,
        k1,
        b,
        jnp.asarray([alpha, 0.0], jnp.float32),
        k,
        cosine,
    )
    return vals[0], ids[0], hits[0]


# -- batched jitted executors (vmap of the same cores) ----------------------


@partial(jax.jit, static_argnames=("k",))
def _term_topk_batch(docs, freqs, doc_lens, live, idfs, avgdl, k1, b, k):
    """docs/freqs: (B, P); idfs: (B,).  One dispatch for the whole batch."""
    return jax.vmap(
        lambda d, f, i: _term_core(d, f, doc_lens, live, i, avgdl, k1, b, k)
    )(docs, freqs, idfs)


@partial(jax.jit, static_argnames=("k", "conjunctive", "n_terms"))
def _bool_topk_batch(
    docs, freqs, idfs, doc_lens, live, avgdl, k1, b, k, conjunctive, n_terms
):
    """docs/freqs: (B, T, P); idfs: (B, T)."""
    return jax.vmap(
        lambda d, f, i: _bool_core(
            d, f, i, doc_lens, live, avgdl, k1, b, k, conjunctive, n_terms
        )
    )(docs, freqs, idfs)


@partial(jax.jit, static_argnames=("k",))
def _sort_topk_batch(docs, freqs, dv, live, k):
    return jax.vmap(lambda d, f: _sort_core(d, f, dv, live, k))(docs, freqs)


@partial(jax.jit, static_argnames=("k",))
def _range_topk_batch(dv, live, los, his, k):
    return jax.vmap(lambda lo, hi: _range_core(dv, live, lo, hi, k))(los, his)


@partial(jax.jit, static_argnames=("k", "cosine"))
def _vector_topk_batch(vmat, live, qvecs, k, cosine):
    """qvecs: (B, d); one dispatch scores the whole batch."""
    return jax.vmap(lambda q: _vector_core(vmat, live, q, k, cosine))(qvecs)


@partial(jax.jit, static_argnames=("k", "cosine"))
def _hybrid_topk_batch(
    docs, freqs, doc_lens, vmat, live, qvecs, idfs, avgdl, k1, b, alphas, k,
    cosine,
):
    """docs/freqs: (B, P); qvecs: (B, d); idfs/alphas: (B,)."""
    return jax.vmap(
        lambda d, f, q, i, a: _hybrid_core(
            d, f, doc_lens, vmat, live, q, i, avgdl, k1, b, a, k, cosine
        )
    )(docs, freqs, qvecs, idfs, alphas)


@partial(jax.jit, static_argnames=("n_bins",))
def _facet_batch(docs, freqs, live, dv_bins, n_bins):
    """(B, P) postings -> (B, n_bins) counts + (B,) match totals."""

    def one(d, f):
        m = _matched_core(d, f, live)
        return _facet_core(m, dv_bins, n_bins), m.sum()

    return jax.vmap(one)(docs, freqs)


# ---------------------------------------------------------------------------
# device-side cross-segment merge (replaces the Python heapq merge)
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("k",))
def merge_topk(vals, ids, k):
    """Merge per-segment candidates: (B, C) -> (B, min(k, C)).

    Primary key: score descending; tie-break: global doc id ascending
    (Lucene's ordering — identical to the sequential heapq merge).
    """
    kk = min(k, vals.shape[1])
    order = jnp.lexsort((ids, -vals), axis=-1)[:, :kk]
    return (
        jnp.take_along_axis(vals, order, axis=-1),
        jnp.take_along_axis(ids, order, axis=-1),
    )


def _finalize_scored(
    vals: jnp.ndarray, ids: jnp.ndarray, totals: jnp.ndarray, n: int
) -> List[TopDocs]:
    """Trim -inf padding and box per-query TopDocs (rows beyond ``n`` are
    batch padding)."""
    with profile.span("exec.fetch"):
        vals_h = np.asarray(vals)
        ids_h = np.asarray(ids)
        totals_h = np.asarray(totals)
    out = []
    for i in range(n):
        m = np.isfinite(vals_h[i])
        out.append(
            TopDocs(
                int(totals_h[i]),
                ids_h[i][m].astype(np.int64),
                vals_h[i][m].astype(np.float32),
            )
        )
    return out


@partial(jax.jit, static_argnames=("k",))
def _concat_merge(vals_t, ids_t, hits_t, k):
    """Whole cross-segment merge in ONE program: concat + lexsort-top-k +
    hit totals (same expressions as ``merge_topk``; fusing them removes a
    handful of eager dispatches per group)."""
    vals = jnp.concatenate(vals_t, axis=1)
    ids = jnp.concatenate(ids_t, axis=1)
    totals = hits_t[0]
    for h in hits_t[1:]:
        totals = totals + h
    kk = min(k, vals.shape[1])
    order = jnp.lexsort((ids, -vals), axis=-1)[:, :kk]
    return (
        jnp.take_along_axis(vals, order, axis=-1),
        jnp.take_along_axis(ids, order, axis=-1),
        totals,
    )


def _merge_segment_candidates(
    per_seg: List[Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]],
    n: int,
    k: int,
) -> List[TopDocs]:
    if not per_seg:
        return [empty_topdocs() for _ in range(n)]
    with profile.span("exec.dispatch", program="_concat_merge"):
        vals, ids, totals = _concat_merge(
            tuple(v for v, _, _ in per_seg),
            tuple(i for _, i, _ in per_seg),
            tuple(h for _, _, h in per_seg),
            k=k,
        )
    return _finalize_scored(vals, ids, totals, n)


# ---------------------------------------------------------------------------
# group executors.  ``ctx`` is the Searcher (segments, cache, stats, knobs).
# ---------------------------------------------------------------------------


def _exec_term(ctx, group: FamilyGroup, k: int) -> List[TopDocs]:
    if ctx.use_pallas:
        from repro.core.query import fused

        return fused.exec_term_fused(ctx, group, k)
    n = len(group.queries)
    pad = bucket_batch(n) - n
    with profile.span("exec.stage"):
        idfs = np.asarray(
            [ctx.idf(q) for q in group.queries] + [0.0] * pad,
            dtype=np.float32,
        )
        idfs_dev = jnp.asarray(idfs)  # batch-constant: upload once
    per_seg = []
    for seg in ctx.segments:
        with profile.span("exec.stage"):
            staged = stage_term_postings(seg, group.queries, pad_rows=pad)
        if staged is None:
            continue
        docs, freqs = staged
        st = ctx._seg_dev(seg)
        with profile.span("exec.dispatch", program="_term_topk_batch"):
            vals, ids, hits = _term_topk_batch(
                jnp.asarray(docs),
                jnp.asarray(freqs),
                st["doc_lens"],
                st["live"],
                idfs_dev,
                ctx.avgdl,
                ctx.k1,
                ctx.b,
                k,
            )
            profile.record("vmap.term")
        per_seg.append((vals, ids + seg.base_doc, hits))
    return _merge_segment_candidates(per_seg, n, k)


def _exec_bool(ctx, group: FamilyGroup, k: int) -> List[TopDocs]:
    if ctx.use_pallas:
        from repro.core.query import fused

        return fused.exec_bool_fused(ctx, group, k)
    n = len(group.queries)
    pad = bucket_batch(n) - n
    mode, n_terms = group.key[1], group.key[2]
    conj = mode == "and"
    with profile.span("exec.stage"):
        idfs = np.zeros((n + pad, n_terms), dtype=np.float32)
        for i, q in enumerate(group.queries):
            idfs[i] = [ctx.idf(t) for t in q.terms]
        idfs_dev = jnp.asarray(idfs)
    per_seg = []
    for seg in ctx.segments:
        with profile.span("exec.stage"):
            staged = stage_bool_postings(seg, group.queries, pad_rows=pad)
        if staged is None:
            continue
        docs, freqs = staged
        st = ctx._seg_dev(seg)
        with profile.span("exec.dispatch", program="_bool_topk_batch"):
            vals, ids, hits = _bool_topk_batch(
                jnp.asarray(docs),
                jnp.asarray(freqs),
                idfs_dev,
                st["doc_lens"],
                st["live"],
                ctx.avgdl,
                ctx.k1,
                ctx.b,
                k,
                conj,
                n_terms,
            )
            profile.record("vmap.bool")
        per_seg.append((vals, ids + seg.base_doc, hits))
    return _merge_segment_candidates(per_seg, n, k)


def _exec_sort(ctx, group: FamilyGroup, k: int) -> List[TopDocs]:
    if ctx.use_pallas:
        from repro.core.query import fused

        return fused.exec_sort_fused(ctx, group, k)
    n = len(group.queries)
    pad = bucket_batch(n) - n
    dv_field = group.key[1]
    terms = [q.term for q in group.queries]
    per_seg = []
    for seg in ctx.segments:
        with profile.span("exec.stage"):
            staged = stage_term_postings(seg, terms, pad_rows=pad)
        if staged is None:
            continue
        docs, freqs = staged
        st = ctx._seg_dev(seg)
        with profile.span("exec.dispatch", program="_sort_topk_batch"):
            vals, ids, hits = _sort_topk_batch(
                jnp.asarray(docs),
                jnp.asarray(freqs),
                st[f"dv.{dv_field}"],
                st["live"],
                k,
            )
            profile.record("vmap.sort")
        per_seg.append((vals, ids + seg.base_doc, hits))
    return _merge_segment_candidates(per_seg, n, k)


def _exec_range(ctx, group: FamilyGroup, k: int) -> List[TopDocs]:
    if ctx.use_pallas:
        from repro.core.query import fused

        return fused.exec_range_fused(ctx, group, k)
    n = len(group.queries)
    pad = bucket_batch(n) - n
    dv_field = group.key[1]
    with profile.span("exec.stage"):
        los = jnp.asarray(
            [q.lo for q in group.queries] + [0] * pad, dtype=jnp.int32
        )
        his = jnp.asarray(
            [q.hi for q in group.queries] + [-1] * pad, dtype=jnp.int32
        )
    per_seg = []
    for seg in ctx.segments:
        st = ctx._seg_dev(seg)
        with profile.span("exec.dispatch", program="_range_topk_batch"):
            vals, ids, hits = _range_topk_batch(
                st[f"dv.{dv_field}"],
                st["live"],
                los,
                his,
                k,
            )
            profile.record("vmap.range")
        per_seg.append((vals, ids + seg.base_doc, hits))
    return _merge_segment_candidates(per_seg, n, k)


def _exec_facet(ctx, group: FamilyGroup, k: int) -> List[TopDocs]:
    if ctx.use_pallas:
        from repro.core.query import fused

        return fused.exec_facet_fused(ctx, group, k)
    n = len(group.queries)
    dv_field, n_bins, match_all = group.key[1], group.key[2], group.key[3]
    counts = np.zeros((n, n_bins), dtype=np.float64)
    totals = np.zeros(n, dtype=np.int64)
    for seg in ctx.segments:
        st = ctx._seg_dev(seg)
        dv_bins = st[f"dv.{dv_field}"].astype(jnp.int32)
        if match_all:
            # identical per query: one dispatch, replicated host-side
            with profile.span("exec.dispatch", program="_facet_counts"):
                c_dev = _facet_counts(st["live"], dv_bins, n_bins)
                t_dev = st["live"].sum()
                profile.record("vmap.facet")
            with profile.span("exec.fetch"):
                c = np.asarray(c_dev, dtype=np.float64)
                t = int(np.asarray(t_dev))
            counts += c[None, :]
            totals += t
        else:
            pad = bucket_batch(n) - n
            with profile.span("exec.stage"):
                staged = stage_term_postings(
                    seg, [q.term for q in group.queries], pad_rows=pad
                )
            if staged is None:
                continue
            docs, freqs = staged
            with profile.span("exec.dispatch", program="_facet_batch"):
                c, t = _facet_batch(
                    jnp.asarray(docs),
                    jnp.asarray(freqs),
                    st["live"],
                    dv_bins,
                    n_bins,
                )
                profile.record("vmap.facet")
            with profile.span("exec.fetch"):
                counts += np.asarray(c, dtype=np.float64)[:n]
                totals += np.asarray(t, dtype=np.int64)[:n]
    out = []
    for i, q in enumerate(group.queries):
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


def _exec_phrase(ctx, group: FamilyGroup, k: int) -> List[TopDocs]:
    with profile.span("exec.phrase", n=len(group.queries)):
        return _score_phrases(ctx, group, k)


def _score_phrases(ctx, group: FamilyGroup, k: int) -> List[TopDocs]:
    """Batched exact-phrase scorer: one vectorized pass per segment.

    Phrase verification is inherently a host-side positions merge (Lucene's
    exact phrase scorer is too), but it does not have to be a per-query
    loop over ``search_single``.  All queries in the group share each
    segment pass: candidate positions are encoded as
    ``global_candidate_rank * M + position`` (candidate ranks are disjoint
    across queries, so one key space serves the whole batch) and adjacency
    is verified with one ``np.isin`` chain per token step across every
    query at once.  Queries of different lengths finalize as their chains
    complete.  Scoring is vectorized float64 BM25 — elementwise IEEE
    doubles, bit-identical to ``search_single``'s Python-scalar math.
    """
    from repro.core.analyzer import term_hash
    from repro.core.query.types import PhraseQuery  # noqa: F401 (doc)

    n = len(group.queries)
    qs = group.queries
    hashes_q = [[term_hash(q.field, t) for t in q.tokens] for q in qs]
    idf_q = np.asarray(
        [
            sum(ctx.idf(TermQuery(q.field, t)) for t in q.tokens)
            for q in qs
        ],
        dtype=np.float64,
    )
    n_tok = np.asarray([len(h) for h in hashes_q], dtype=np.int64)
    max_ntok = int(n_tok.max())
    k1, b, avgdl = float(ctx.k1), float(ctx.b), float(ctx.avgdl)
    per_seg_q: List[List[Tuple[np.ndarray, np.ndarray]]] = [
        [] for _ in range(n)
    ]
    totals = np.zeros(n, dtype=np.int64)
    for seg in ctx.segments:
        # conjunctive doc-id intersection per query (cheap int set ops);
        # the expensive positions traffic below is shared across the batch
        cands: List[np.ndarray] = []
        for hs in hashes_q:
            psets = []
            for th in hs:
                d, _ = seg.postings(th)
                if len(d) == 0:
                    psets = None
                    break
                psets.append(d)
            if psets is None:
                cands.append(np.zeros(0, np.int64))
                continue
            c = psets[0]
            for d in psets[1:]:
                c = np.intersect1d(c, d, assume_unique=True)
            c = c[seg.live[c]]
            cands.append(c.astype(np.int64))
        lens = np.asarray([len(c) for c in cands], dtype=np.int64)
        if lens.sum() == 0:
            continue
        all_cand = np.concatenate(cands)
        q_of = np.repeat(np.arange(n), lens)
        offs = np.zeros(n + 1, np.int64)
        np.cumsum(lens, out=offs[1:])
        # key stride: position + token step never reaches M, so keys from
        # different candidates (and hence different queries) cannot collide
        M = int(seg.doc_lens.max()) + max_ntok + 1

        def step_keys(t: int) -> np.ndarray:
            """grank*M+pos keys of token ``t`` for every still-active query
            (one concatenated array; one positions gather per step)."""
            parts = []
            for qi in range(n):
                if n_tok[qi] <= t or lens[qi] == 0:
                    continue
                slot = seg.term_slot(hashes_q[qi][t])
                s_ = int(seg.postings_offsets[slot])
                e_ = int(seg.postings_offsets[slot + 1])
                rows = s_ + np.searchsorted(
                    seg.postings_docs[s_:e_], cands[qi]
                )
                starts = seg.pos_offsets[rows].astype(np.int64)
                counts = (
                    seg.pos_offsets[rows + 1] - seg.pos_offsets[rows]
                ).astype(np.int64)
                total = int(counts.sum())
                # vectorized ragged gather (replaces the per-row concat)
                cum = np.cumsum(counts) - counts
                idx = np.repeat(starts - cum, counts) + np.arange(total)
                flat = seg.positions[idx].astype(np.int64)
                grank = offs[qi] + np.repeat(
                    np.arange(lens[qi], dtype=np.int64), counts
                )
                parts.append(grank * M + flat)
            if parts:
                return np.concatenate(parts)
            return np.zeros(0, np.int64)

        match = step_keys(0)
        phrase_tf = np.zeros(len(all_cand), np.int64)
        for t in range(1, max_ntok):
            g = match // M
            fin = n_tok[q_of[g]] <= t  # these chains are complete
            if fin.any():
                np.add.at(phrase_tf, g[fin], 1)
                match = match[~fin]
            if len(match) == 0:
                break
            match = match[np.isin(match + t, step_keys(t))]
        if len(match):
            np.add.at(phrase_tf, match // M, 1)
        hit = phrase_tf > 0
        if not hit.any():
            continue
        g_hit = np.nonzero(hit)[0]
        docs_hit = all_cand[g_hit]
        q_hit = q_of[g_hit]
        tf = phrase_tf[g_hit].astype(np.float64)
        dl = seg.doc_lens[docs_hit].astype(np.float64)
        idf = idf_q[q_hit]
        s = (
            idf
            * (tf * (k1 + 1))
            / (tf + k1 * (1 - b + b * dl / avgdl))
        )
        base = seg.base_doc
        for qi in range(n):
            mask = q_hit == qi
            if not mask.any():
                continue
            dq = docs_hit[mask] + base
            sq = s[mask]
            totals[qi] += int(mask.sum())
            order = np.lexsort((dq, -sq))[:k]  # score desc, doc asc
            per_seg_q[qi].append(
                (sq[order].astype(np.float32), dq[order].astype(np.int64))
            )
    out = []
    for qi in range(n):
        ids, scores = ctx._merge(per_seg_q[qi], k)
        out.append(TopDocs(int(totals[qi]), ids, scores))
    return out


def _seg_vector(ctx, seg):
    """Device handle of a segment's dense vector column, or None when the
    segment has no vectors (it then contributes nothing to the family)."""
    from repro.core.writer import VECTOR_FIELD

    if VECTOR_FIELD not in seg.doc_values:
        return None
    return ctx._seg_dev(seg)[f"dv.{VECTOR_FIELD}"]


def _exec_vector(ctx, group: FamilyGroup, k: int) -> List[TopDocs]:
    if ctx.use_pallas:
        from repro.core.query import fused

        return fused.exec_vector_fused(ctx, group, k)
    n = len(group.queries)
    pad = bucket_batch(n) - n
    dim, metric = group.key[1], group.key[2]
    cosine = metric == "cosine"
    with profile.span("exec.stage"):
        qvecs = np.zeros((n + pad, dim), dtype=np.float32)
        for i, q in enumerate(group.queries):
            qvecs[i] = q.vector
        qdev = jnp.asarray(qvecs)
    per_seg = []
    for seg in ctx.segments:
        vmat = _seg_vector(ctx, seg)
        if vmat is None:
            continue
        st = ctx._seg_dev(seg)
        with profile.span("exec.dispatch", program="_vector_topk_batch"):
            vals, ids, hits = _vector_topk_batch(
                vmat, st["live"], qdev, k, cosine
            )
            profile.record("vmap.vector")
        per_seg.append((vals, ids + seg.base_doc, hits))
    return _merge_segment_candidates(per_seg, n, k)


def _exec_hybrid(ctx, group: FamilyGroup, k: int) -> List[TopDocs]:
    if ctx.use_pallas:
        from repro.core.query import fused

        return fused.exec_hybrid_fused(ctx, group, k)
    n = len(group.queries)
    # floor 2: the B=1 vmapped graph compiles to different blend rounding
    pad = bucket_batch_min2(n) - n
    dim, metric = group.key[1], group.key[2]
    cosine = metric == "cosine"
    terms = [q.term for q in group.queries]
    with profile.span("exec.stage"):
        qvecs = np.zeros((n + pad, dim), dtype=np.float32)
        for i, q in enumerate(group.queries):
            qvecs[i] = q.vector.vector
        idfs = np.asarray(
            [ctx.idf(t) for t in terms] + [0.0] * pad, dtype=np.float32
        )
        alphas = np.asarray(
            [q.alpha for q in group.queries] + [0.0] * pad, dtype=np.float32
        )
        qdev = jnp.asarray(qvecs)
        idfs_dev = jnp.asarray(idfs)
        alphas_dev = jnp.asarray(alphas)
    per_seg = []
    for seg in ctx.segments:
        vmat = _seg_vector(ctx, seg)
        if vmat is None:
            continue
        st = ctx._seg_dev(seg)
        with profile.span("exec.stage"):
            staged = stage_term_postings(seg, terms, pad_rows=pad)
        if staged is None:
            # match-all-live semantics: no term postings here, but the
            # vector half still scores every live doc (BM25 sum = 0)
            docs = np.zeros((n + pad, 8), dtype=np.int32)
            freqs = np.zeros((n + pad, 8), dtype=np.int32)
        else:
            docs, freqs = staged
        with profile.span("exec.dispatch", program="_hybrid_topk_batch"):
            vals, ids, hits = _hybrid_topk_batch(
                jnp.asarray(docs),
                jnp.asarray(freqs),
                st["doc_lens"],
                vmat,
                st["live"],
                qdev,
                idfs_dev,
                ctx.avgdl,
                ctx.k1,
                ctx.b,
                alphas_dev,
                k,
                cosine,
            )
            profile.record("vmap.hybrid")
        per_seg.append((vals, ids + seg.base_doc, hits))
    return _merge_segment_candidates(per_seg, n, k)


_EXECUTORS = {
    "term": _exec_term,
    "bool": _exec_bool,
    "sort": _exec_sort,
    "range": _exec_range,
    "facet": _exec_facet,
    "phrase": _exec_phrase,
    "vector": _exec_vector,
    "hybrid": _exec_hybrid,
}


def execute_group(ctx, group: FamilyGroup, k: int) -> List[TopDocs]:
    return _EXECUTORS[group.kind](ctx, group, k)
