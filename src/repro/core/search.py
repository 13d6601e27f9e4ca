"""Searcher: the JAX data plane over immutable segments.

The query-execution machinery lives in ``repro.core.query``:

  * ``query.types``  — the six query dataclasses + ``TopDocs`` (re-exported
    here for compatibility),
  * ``query.plan``   — the batch planner (family grouping, shared
    power-of-two padding),
  * ``query.exec``   — per-family jitted/vmapped executors and the
    device-side cross-segment top-k merge,
  * ``query.cache``  — the persistent device-resident segment cache shared
    across Searcher generations.

``search_batch`` is the primary entry point: a heterogeneous batch of
queries is planned into family groups and each group is scored against
every segment in one dispatch.  ``search`` is a batch of one.  The original
per-query path survives as ``search_single`` — it is the oracle the batched
path must match bit-for-bit (same BM25 scores, same ascending-docid
tie-breaks); it runs the pure-jnp primitives only, never a Pallas kernel,
so it stays a valid reference for the fused kernels on every backend.

Scoring is Lucene's BM25 (k1=0.9, b=0.4 defaults) with global collection
statistics.  Postings are padded to power-of-two buckets so segments (and
batches) of similar size share compiled executables.
"""

from __future__ import annotations

import heapq
from typing import Dict, List, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from repro.core.analyzer import Analyzer, term_hash
from repro.core.lifecycle.infos import SegmentInfos
from repro.core.query.cache import SegmentDeviceCache
from repro.core.query.exec import (
    _bool_topk,
    _facet_counts,
    _hybrid_topk,
    _matched_from_postings,
    _range_topk,
    _sort_topk,
    _term_topk,
    _vector_topk,
    bm25,
    execute_group,
)
from repro.core.query.plan import bucket as _pow2_bucket
from repro.core.query.plan import plan_batch
from repro.core.query.types import (
    BooleanQuery,
    FacetQuery,
    HybridQuery,
    PhraseQuery,
    Query,
    RangeQuery,
    SortQuery,
    TermQuery,
    TopDocs,
    VectorQuery,
)
from repro.core.segment import Segment

K1_DEFAULT = 0.9
B_DEFAULT = 0.4

__all__ = [
    "Searcher",
    "TopDocs",
    "TermQuery",
    "BooleanQuery",
    "PhraseQuery",
    "RangeQuery",
    "SortQuery",
    "FacetQuery",
    "VectorQuery",
    "HybridQuery",
    "bm25",
    "K1_DEFAULT",
    "B_DEFAULT",
]


def _bucket(n: int) -> int:
    return _pow2_bucket(n)


class Searcher:
    """Point-in-time view over a list of immutable segments.

    Immutability means a Searcher never locks: new flushes create *new*
    segments and a *new* Searcher (see SearcherManager) — the paper's §2.1.
    Device residency is delegated to a ``SegmentDeviceCache``; passing the
    engine-owned cache lets consecutive Searcher generations share device
    buffers so an NRT reopen uploads only new segments.
    """

    def __init__(
        self,
        segments: "SegmentInfos | Sequence[Segment]",
        analyzer: Optional[Analyzer] = None,
        k1: float = K1_DEFAULT,
        b: float = B_DEFAULT,
        use_pallas: bool = False,
        device_cache: Optional[SegmentDeviceCache] = None,
        live=None,
    ) -> None:
        # a SegmentInfos IS the point-in-time contract: the writer only
        # publishes new snapshots, never mutates one this view holds
        if isinstance(segments, SegmentInfos):
            self.infos: Optional[SegmentInfos] = segments
            self.segments = list(segments.segments)
        else:
            self.infos = None
            self.segments = list(segments)
        self.analyzer = analyzer or Analyzer()
        self.k1, self.b = k1, b
        self.use_pallas = use_pallas
        self.total_docs = sum(s.n_docs for s in self.segments)
        tokens = sum(s.total_tokens for s in self.segments)
        # live buffer tail (a ``repro.core.query.live.LiveSnapshot``): its
        # docs/tokens fold into the collection statistics exactly like a
        # flushed segment's would, so BM25 comes out bit-identical to
        # flush-then-search (the cross-source merge CrossShardStats does
        # across shards, applied across committed/live here)
        self._live = live if (live is not None and live.n_docs) else None
        self._live_base = self.total_docs  # committed docs = tail's base
        if self._live is not None:
            self.total_docs += self._live.n_docs
            tokens += self._live.total_tokens
        self._local_tokens = tokens  # what CrossShardStats sums per shard
        self.avgdl = float(tokens) / max(self.total_docs, 1)
        # per-group mini segments over the tail + their device staging
        # (kept OUT of the shared SegmentDeviceCache: the transient tail
        # must not pollute its store or its pinned upload stats)
        self._live_segs: Dict[tuple, Segment] = {}
        self._live_dev_map: Optional[Dict[str, jnp.ndarray]] = None
        # explicit None check: an empty cache is falsy (it has __len__)
        # (fused searchers get a tiled cache so staging pre-tiles the CSR)
        self.device_cache = (
            device_cache
            if device_cache is not None
            else SegmentDeviceCache(tile=use_pallas)
        )
        # memo for segments evicted from the shared cache while this
        # point-in-time view still references them (post-merge stale reads)
        self._transient_dev: Dict[str, Dict[str, jnp.ndarray]] = {}
        # df memo: a Searcher is a point-in-time view over immutable
        # segments, so document frequencies never change under it
        self._df_cache: Dict[int, int] = {}

    # -- device residency ---------------------------------------------------
    def _seg_dev(self, seg: Segment) -> Dict[str, jnp.ndarray]:
        return self.device_cache.get(seg, fallback=self._transient_dev)

    def _live_dev(self, seg: Segment) -> Dict[str, jnp.ndarray]:
        """Device staging for the live tail's mini segments — private to
        this Searcher, never entered into the shared cache.  All minis of
        one snapshot share doc_lens/live/dv, so one dict serves them all."""
        if self._live_dev_map is None:
            from repro.core.query.live import _LiveDev

            self._live_dev_map = _LiveDev(self._live, seg)
        return self._live_dev_map

    def _live_segment_for(self, group) -> Segment:
        from repro.core.query import live as _lv

        hs = _lv.group_term_hashes(group)
        key = (tuple(sorted(set(hs))), group.kind == "phrase")
        seg = self._live_segs.get(key)
        if seg is None:
            seg = _lv.materialize_segment(
                self._live, key[0], with_positions=key[1],
                base_doc=self._live_base,
            )
            self._live_segs[key] = seg
        return seg

    def _live_segment_for_query(self, query: Query) -> Segment:
        from repro.core.query import live as _lv

        hs = _lv.query_term_hashes(query)
        key = (tuple(sorted(set(hs))), isinstance(query, PhraseQuery))
        seg = self._live_segs.get(key)
        if seg is None:
            seg = _lv.materialize_segment(
                self._live, key[0], with_positions=key[1],
                base_doc=self._live_base,
            )
            self._live_segs[key] = seg
        return seg

    # -- stats ----------------------------------------------------------------
    def doc_freq(self, q: TermQuery) -> int:
        th = term_hash(q.field, q.token)
        df = self._df_cache.get(th)
        if df is None:
            df = 0
            for seg in self.segments:
                i = seg.term_slot(th)
                if i >= 0:
                    df += int(seg.term_df[i])
            if self._live is not None:
                df += self._live.df(th)  # raw, like term_df (deleted incl.)
            self._df_cache[th] = df
        return df

    def idf(self, q: TermQuery) -> float:
        df = self.doc_freq(q)
        n = self.total_docs
        return float(np.log(1.0 + (n - df + 0.5) / (df + 0.5)))

    # -- postings staging -----------------------------------------------------
    def _padded_postings(self, seg: Segment, q: TermQuery, bucket: int):
        docs, freqs = seg.postings(term_hash(q.field, q.token))
        p = max(bucket, _bucket(len(docs)))
        d = np.zeros(p, dtype=np.int32)
        f = np.zeros(p, dtype=np.int32)
        d[: len(docs)] = docs
        f[: len(freqs)] = freqs
        return d, f, len(docs)

    # -- public API -----------------------------------------------------------
    def search(self, query: Query, k: int = 10) -> TopDocs:
        """Single query == a batch of one (same planner/executor path)."""
        return self.search_batch([query], k)[0]

    def search_batch(self, queries: Sequence[Query], k: int = 10) -> List[TopDocs]:
        """Score a heterogeneous batch: group by family, one vmapped dispatch
        per (family group, segment), device-side cross-segment merge."""
        plan = plan_batch(queries)
        results: List[Optional[TopDocs]] = [None] * plan.n_queries
        for group in plan.groups:
            for qi, td in zip(group.indices, self.execute_group(group, k)):
                results[qi] = td
        return results  # type: ignore[return-value]

    def execute_group(self, group, k: int) -> List[TopDocs]:
        """Execute one planned family group: committed segments, plus the
        live buffer tail when this view holds one (``query/live``)."""
        if self._live is None:
            return execute_group(self, group, k)
        from repro.core.query.live import run_group

        return run_group(self, group, k)

    def search_single(self, query: Query, k: int = 10) -> TopDocs:
        """The sequential per-query path (one dispatch per segment, heapq
        merge on host).  Kept as the oracle for the batched executors."""
        if self._live is not None:
            from repro.core.query.live import _CombinedView

            lseg = self._live_segment_for_query(query)
            view = _CombinedView(
                self, list(self.segments) + [lseg], lseg,
                use_pallas=self.use_pallas,
            )
            return view.search_single(query, k)
        if isinstance(query, TermQuery):
            return self._search_term(query, k)
        if isinstance(query, BooleanQuery):
            return self._search_bool(query, k)
        if isinstance(query, PhraseQuery):
            return self._search_phrase(query, k)
        if isinstance(query, SortQuery):
            return self._search_sort(query, k)
        if isinstance(query, RangeQuery):
            return self._search_range(query, k)
        if isinstance(query, FacetQuery):
            return self._search_facet(query, k)
        if isinstance(query, VectorQuery):
            return self._search_vector(query, k)
        if isinstance(query, HybridQuery):
            return self._search_hybrid(query, k)
        raise TypeError(f"unknown query type {type(query)}")

    # -- sequential per-family implementations (oracle path) -------------------
    def _merge(self, per_seg: List[Tuple[np.ndarray, np.ndarray]], k: int):
        # min-heap of (score, -doc): among equal scores the LARGEST doc id
        # is evicted first, preserving Lucene's ascending-docid tie-break
        heap: List[Tuple[float, int]] = []
        for scores, ids in per_seg:
            for s, d in zip(scores, ids):
                if np.isfinite(s):
                    heapq.heappush(heap, (float(s), -int(d)))
                    if len(heap) > k:
                        heapq.heappop(heap)
        out = sorted(((s, -d) for s, d in heap), key=lambda t: (-t[0], t[1]))
        return (
            np.asarray([d for _, d in out], dtype=np.int64),
            np.asarray([s for s, _ in out], dtype=np.float32),
        )

    def _search_term(self, q: TermQuery, k: int) -> TopDocs:
        idf = self.idf(q)
        total = 0
        per_seg = []
        for seg in self.segments:
            docs, freqs, n = self._padded_postings(seg, q, 8)
            if n == 0:
                continue
            st = self._seg_dev(seg)
            # the oracle never runs a kernel under test: always the jnp path
            vals, ids, hits = _term_topk(
                jnp.asarray(docs),
                jnp.asarray(freqs),
                st["doc_lens"],
                st["live"],
                idf,
                self.avgdl,
                self.k1,
                self.b,
                k,
            )
            total += int(hits)
            per_seg.append(
                (np.asarray(vals), np.asarray(ids) + seg.base_doc)
            )
        ids, scores = self._merge(per_seg, k)
        return TopDocs(total, ids, scores)

    def _search_bool(self, q: BooleanQuery, k: int) -> TopDocs:
        idfs = np.asarray([self.idf(t) for t in q.terms], dtype=np.float32)
        conj = q.mode == "and"
        total = 0
        per_seg = []
        for seg in self.segments:
            staged = [self._padded_postings(seg, t, 8) for t in q.terms]
            if conj and any(n == 0 for _, _, n in staged):
                continue
            if all(n == 0 for _, _, n in staged):
                continue
            p = max(d.shape[0] for d, _, _ in staged)
            docs = np.zeros((len(staged), p), dtype=np.int32)
            freqs = np.zeros((len(staged), p), dtype=np.int32)
            for i, (d, f, _) in enumerate(staged):
                docs[i, : d.shape[0]] = d
                freqs[i, : f.shape[0]] = f
            st = self._seg_dev(seg)
            vals, ids, hits = _bool_topk(
                jnp.asarray(docs),
                jnp.asarray(freqs),
                jnp.asarray(idfs),
                st["doc_lens"],
                st["live"],
                self.avgdl,
                self.k1,
                self.b,
                k,
                conj,
                len(q.terms),
            )
            total += int(hits)
            per_seg.append((np.asarray(vals), np.asarray(ids) + seg.base_doc))
        ids, scores = self._merge(per_seg, k)
        return TopDocs(total, ids, scores)

    def _search_phrase(self, q: PhraseQuery, k: int) -> TopDocs:
        """Exact phrase via positions: conjunctive candidates, then host-side
        adjacency verification (Lucene's exact-phrase scorer is also a CPU
        merge over positions)."""
        terms = [TermQuery(q.field, t) for t in q.tokens]
        hashes = [term_hash(q.field, t) for t in q.tokens]
        idfs = [self.idf(t) for t in terms]
        per_seg = []
        total = 0
        for seg in self.segments:
            posting_sets = []
            ok = True
            for th in hashes:
                docs, _ = seg.postings(th)
                if len(docs) == 0:
                    ok = False
                    break
                posting_sets.append(docs)
            if not ok:
                continue
            cand = posting_sets[0]
            for d in posting_sets[1:]:
                cand = np.intersect1d(cand, d, assume_unique=True)
            cand = cand[seg.live[cand]]
            if len(cand) == 0:
                continue
            # vectorized adjacency: encode positions of every candidate doc
            # as doc_rank * M + pos and chain np.isin checks (no per-doc loop)
            M = int(seg.doc_lens.max()) + len(hashes) + 1
            keysets = []
            for th in hashes:
                i = seg.term_slot(th)
                s_, e_ = (
                    int(seg.postings_offsets[i]),
                    int(seg.postings_offsets[i + 1]),
                )
                rows = s_ + np.searchsorted(seg.postings_docs[s_:e_], cand)
                counts = seg.pos_offsets[rows + 1] - seg.pos_offsets[rows]
                doc_rank = np.repeat(np.arange(len(cand)), counts)
                flat = np.concatenate(
                    [
                        seg.positions[
                            int(seg.pos_offsets[r]) : int(seg.pos_offsets[r + 1])
                        ]
                        for r in rows
                    ]
                ) if len(rows) else np.zeros(0, np.int64)
                keysets.append(doc_rank.astype(np.int64) * M + flat)
            match = keysets[0]
            for step, ks in enumerate(keysets[1:], start=1):
                match = match[np.isin(match + step, ks)]
                if len(match) == 0:
                    break
            hits = []
            if len(match):
                tf_per_doc = np.bincount(match // M, minlength=len(cand))
                idf = float(sum(idfs))
                for rank in np.nonzero(tf_per_doc)[0]:
                    doc = int(cand[rank])
                    tf = float(tf_per_doc[rank])
                    dl = float(seg.doc_lens[doc])
                    s = (
                        idf
                        * (tf * (self.k1 + 1))
                        / (tf + self.k1 * (1 - self.b + self.b * dl / self.avgdl))
                    )
                    hits.append((s, doc + seg.base_doc))
            total += len(hits)
            if hits:
                hits.sort(key=lambda t: (-t[0], t[1]))
                hits = hits[:k]
                per_seg.append(
                    (
                        np.asarray([h[0] for h in hits], np.float32),
                        np.asarray([h[1] for h in hits], np.int64),
                    )
                )
        ids, scores = self._merge(per_seg, k)
        return TopDocs(total, ids, scores)

    def _search_sort(self, q: SortQuery, k: int) -> TopDocs:
        total = 0
        per_seg = []
        for seg in self.segments:
            docs, freqs, n = self._padded_postings(seg, q.term, 8)
            if n == 0:
                continue
            st = self._seg_dev(seg)
            dv = st[f"dv.{q.dv_field}"]
            vals, ids, hits = _sort_topk(
                jnp.asarray(docs), jnp.asarray(freqs), dv, st["live"], k
            )
            total += int(hits)
            per_seg.append((np.asarray(vals), np.asarray(ids) + seg.base_doc))
        ids, scores = self._merge(per_seg, k)
        return TopDocs(total, ids, scores)

    def _search_range(self, q: RangeQuery, k: int) -> TopDocs:
        total = 0
        per_seg = []
        for seg in self.segments:
            st = self._seg_dev(seg)
            dv = st[f"dv.{q.dv_field}"]
            vals, ids, hits = _range_topk(dv, st["live"], q.lo, q.hi, k)
            total += int(hits)
            per_seg.append((np.asarray(vals), np.asarray(ids) + seg.base_doc))
        ids, scores = self._merge(per_seg, k)
        return TopDocs(total, ids, scores)

    def _search_facet(self, q: FacetQuery, k: int) -> TopDocs:
        counts = np.zeros(q.n_bins, dtype=np.float64)
        total = 0
        for seg in self.segments:
            st = self._seg_dev(seg)
            dv = st[f"dv.{q.dv_field}"]
            if q.term is None:
                matched = st["live"]
            else:
                docs, freqs, n = self._padded_postings(seg, q.term, 8)
                if n == 0:
                    continue
                matched = _matched_from_postings(
                    jnp.asarray(docs), jnp.asarray(freqs), st["live"]
                )
            c = _facet_counts(matched, dv.astype(jnp.int32), q.n_bins)
            counts += np.asarray(c, dtype=np.float64)
            total += int(np.asarray(matched.sum()))
        order = np.argsort(-counts, kind="stable")[:k]
        return TopDocs(
            total,
            order.astype(np.int64),
            counts[order].astype(np.float32),
            facets=counts,
        )

    def _seg_vmat(self, seg: Segment):
        """Device handle of a segment's dense vector column, or None when
        the segment carries no vectors (it contributes nothing then)."""
        from repro.core.writer import VECTOR_FIELD

        if VECTOR_FIELD not in seg.doc_values:
            return None
        return self._seg_dev(seg)[f"dv.{VECTOR_FIELD}"]

    def _search_vector(self, q: VectorQuery, k: int) -> TopDocs:
        """Brute-force exact dense retrieval: THE bit-parity oracle for the
        batched executor and the Pallas kernel path (same similarity
        expression, same tie-breaks)."""
        qvec = jnp.asarray(np.asarray(q.vector, dtype=np.float32))
        cosine = q.metric == "cosine"
        total = 0
        per_seg = []
        for seg in self.segments:
            vmat = self._seg_vmat(seg)
            if vmat is None:
                continue
            st = self._seg_dev(seg)
            vals, ids, hits = _vector_topk(vmat, st["live"], qvec, k, cosine)
            total += int(hits)
            per_seg.append((np.asarray(vals), np.asarray(ids) + seg.base_doc))
        ids, scores = self._merge(per_seg, k)
        return TopDocs(total, ids, scores)

    def _search_hybrid(self, q: HybridQuery, k: int) -> TopDocs:
        """BM25 ⊕ vector fusion oracle (same fixed normalizations as the
        batched/fused executors, so ranking is path- and shard-independent)."""
        qvec = jnp.asarray(np.asarray(q.vector.vector, dtype=np.float32))
        cosine = q.vector.metric == "cosine"
        idf = self.idf(q.term)
        total = 0
        per_seg = []
        for seg in self.segments:
            vmat = self._seg_vmat(seg)
            if vmat is None:
                continue
            docs, freqs, _n = self._padded_postings(seg, q.term, 8)
            st = self._seg_dev(seg)
            vals, ids, hits = _hybrid_topk(
                jnp.asarray(docs),
                jnp.asarray(freqs),
                st["doc_lens"],
                vmat,
                st["live"],
                qvec,
                idf,
                self.avgdl,
                self.k1,
                self.b,
                q.alpha,
                k,
                cosine,
            )
            total += int(hits)
            per_seg.append((np.asarray(vals), np.asarray(ids) + seg.base_doc))
        ids, scores = self._merge(per_seg, k)
        return TopDocs(total, ids, scores)
