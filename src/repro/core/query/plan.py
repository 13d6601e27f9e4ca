"""Batch query planner.

The paper's search measurements (§2.1 segments; Fig 5's luceneutil query
buckets) drive one query at a time through one searcher; serving heavy
traffic means amortizing dispatch across a *batch*.  This module is the
host-side half of that amortization.

``plan_batch`` groups a heterogeneous batch of queries into *family groups*
that a single jitted/vmapped executor dispatch can score together (see
``repro.core.query.exec``).  Two queries land in the same group when they
share an executor signature:

  term                         -> ("term",)
  boolean                      -> ("bool", mode, n_terms)
  phrase                       -> ("phrase",)           (host executor)
  sort                         -> ("sort", dv_field)
  range                        -> ("range", dv_field)
  facet                        -> ("facet", dv_field, n_bins, match_all)
  vector                       -> ("vector", dim, metric)
  hybrid                       -> ("hybrid", dim, metric)

Postings staging pads every query in a group to one *shared* power-of-two
bucket per segment, so same-family batches of similar size reuse compiled
executables instead of fanning out one XLA program per (query, segment).
The batch dimension is likewise padded to a power of two with inert rows
(empty postings / empty ranges) that score ``-inf`` everywhere and are
dropped at trim time.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core.analyzer import term_hash
from repro.core.query import profile
from repro.core.query.types import (
    BooleanQuery,
    FacetQuery,
    HybridQuery,
    PhraseQuery,
    Query,
    RangeQuery,
    SortQuery,
    TermQuery,
    VectorQuery,
)
from repro.core.segment import Segment


#: Postings/doc entries per fused-kernel grid step.  Must equal
#: ``repro.kernels.fused_exec.BLOCK`` (asserted in ``repro.core.query.fused``
#: at import time); plan.py stays jax-free so it re-declares the value.
TILE = 1024


def bucket(n: int, floor: int = 8) -> int:
    """Smallest power of two >= max(n, floor)."""
    b = floor
    while b < n:
        b <<= 1
    return b


def bucket_batch(n: int) -> int:
    """Power-of-two batch padding (floor 1: a batch of one stays a one)."""
    return bucket(n, floor=1)


def bucket_batch_min2(n: int) -> int:
    """Power-of-two batch padding with floor 2 (the hybrid executors).

    XLA squeezes the batch dimension out of a B=1 vmapped graph and then
    re-fuses the blend arithmetic differently (observed: 1-ULP drift of
    ``alpha * tnorm + (1-alpha) * vnorm`` vs any B >= 2, which are all
    mutually bit-identical) — so hybrid groups never execute at B=1; a
    lone query carries one inert padding row instead.
    """
    return bucket(n, floor=2)


def pad_width(longest: int, tile: bool) -> int:
    """Shared padded row width for a fused group.

    Kernel path (``tile``): a TILE multiple (the Pallas grid steps in TILE
    blocks; powers of two >= TILE are TILE multiples).  jnp path: powers of
    two up to TILE, then TILE/2 multiples — power-of-two bucketing wastes up
    to 2x compute on long postings rows, and the coarser executable-reuse
    argument stops mattering once rows span multiple tiles.  Width only
    changes how much inert padding is scored, never a result.
    """
    p = bucket(longest)
    if tile:
        return max(p, TILE)
    if p > TILE:
        half = TILE // 2
        return -(-longest // half) * half
    return p


def family_key(q: Query) -> Tuple:
    if isinstance(q, TermQuery):
        return ("term",)
    if isinstance(q, BooleanQuery):
        return ("bool", q.mode, len(q.terms))
    if isinstance(q, PhraseQuery):
        return ("phrase",)
    if isinstance(q, SortQuery):
        return ("sort", q.dv_field)
    if isinstance(q, RangeQuery):
        return ("range", q.dv_field)
    if isinstance(q, FacetQuery):
        return ("facet", q.dv_field, q.n_bins, q.term is None)
    if isinstance(q, VectorQuery):
        return ("vector", q.dim, q.metric)
    if isinstance(q, HybridQuery):
        return ("hybrid", q.vector.dim, q.vector.metric)
    raise TypeError(f"unknown query type {type(q)}")


@dataclasses.dataclass
class FamilyGroup:
    """Same-family queries scheduled for one executor."""

    key: Tuple
    indices: List[int]  # positions in the original batch
    queries: List[Query]

    @property
    def kind(self) -> str:
        return self.key[0]


@dataclasses.dataclass
class BatchPlan:
    groups: List[FamilyGroup]
    n_queries: int


def plan_batch(queries: Sequence[Query]) -> BatchPlan:
    with profile.span("plan.batch", n=len(queries)):
        order: List[Tuple] = []
        by_key: Dict[Tuple, FamilyGroup] = {}
        for i, q in enumerate(queries):
            key = family_key(q)
            g = by_key.get(key)
            if g is None:
                g = by_key[key] = FamilyGroup(key=key, indices=[], queries=[])
                order.append(key)
            g.indices.append(i)
            g.queries.append(q)
        return BatchPlan(groups=[by_key[k] for k in order],
                         n_queries=len(queries))


# ---------------------------------------------------------------------------
# Postings staging (host side): pad to shared buckets
# ---------------------------------------------------------------------------


def stage_term_postings(
    seg: Segment, terms: Sequence[TermQuery], pad_rows: int = 0
) -> Optional[Tuple[np.ndarray, np.ndarray]]:
    """(B+pad_rows, P) padded postings for one term per row, or None when no
    row has postings in this segment.  P is the shared power-of-two bucket."""
    posts = [seg.postings(term_hash(t.field, t.token)) for t in terms]
    longest = max((len(d) for d, _ in posts), default=0)
    if longest == 0:
        return None
    p = bucket(longest)
    rows = len(terms) + pad_rows
    docs = np.zeros((rows, p), dtype=np.int32)
    freqs = np.zeros((rows, p), dtype=np.int32)
    for i, (d, f) in enumerate(posts):
        docs[i, : len(d)] = d
        freqs[i, : len(f)] = f
    return docs, freqs


# ---------------------------------------------------------------------------
# CSR tile metadata (fused executors): instead of materializing padded
# (B, P) postings host-side and re-uploading them per batch, the fused path
# keeps the segment CSR device-resident (see ``query.cache``) and ships only
# this tiny per-row metadata — the kernels gather their tiles on device.
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class CsrTileMeta:
    """Per-row postings coordinates into a segment's device-resident CSR.

    ``starts``/``lengths`` are (R,) for term-shaped groups and (R, T) for
    boolean groups; absent terms are (0, 0) rows.  ``p`` is the shared
    padded row width: the power-of-two bucket of the longest row, raised to
    a ``TILE`` multiple when the kernel path will consume it (powers of two
    >= TILE are TILE multiples, so bucketing is preserved either way).
    """

    starts: np.ndarray
    lengths: np.ndarray
    p: int


def _row_coords(seg: Segment, terms: Sequence[TermQuery]):
    """Vectorized ``term_slot`` for a whole group: ONE searchsorted over the
    segment's sorted term table instead of a Python loop of scalar lookups
    (the loop showed up as a per-batch hotspot in the fused executors)."""
    ths = np.fromiter(
        (term_hash(t.field, t.token) for t in terms),
        dtype=np.int64,
        count=len(terms),
    )
    if seg.n_terms == 0 or len(terms) == 0:
        z = np.zeros(len(terms), dtype=np.int32)
        return z, z.copy()
    slots = np.searchsorted(seg.term_ids, ths)
    clipped = np.minimum(slots, seg.n_terms - 1)
    present = seg.term_ids[clipped] == ths
    starts = np.where(present, seg.postings_offsets[clipped], 0)
    ends = np.where(present, seg.postings_offsets[clipped + 1], 0)
    return starts.astype(np.int32), (ends - starts).astype(np.int32)


def stage_term_meta(
    seg: Segment,
    terms: Sequence[TermQuery],
    pad_rows: int = 0,
    tile: bool = False,
) -> Optional[CsrTileMeta]:
    """CSR coordinates for one term per row (+ inert padding rows), or None
    when no row has postings in this segment — the same skip condition as
    ``stage_term_postings``."""
    starts, lengths = _row_coords(seg, terms)
    longest = int(lengths.max()) if len(lengths) else 0
    if longest == 0:
        return None
    p = pad_width(longest, tile)
    if pad_rows:
        starts = np.concatenate([starts, np.zeros(pad_rows, np.int32)])
        lengths = np.concatenate([lengths, np.zeros(pad_rows, np.int32)])
    return CsrTileMeta(starts, lengths, p)


def stage_bool_meta(
    seg: Segment,
    queries: Sequence[BooleanQuery],
    pad_rows: int = 0,
    tile: bool = False,
) -> Optional[CsrTileMeta]:
    """(R, T) CSR coordinates for boolean groups, or None when nothing
    matches (same skip condition as ``stage_bool_postings``)."""
    n_terms = len(queries[0].terms)
    rows = len(queries) + pad_rows
    starts = np.zeros((rows, n_terms), dtype=np.int32)
    lengths = np.zeros((rows, n_terms), dtype=np.int32)
    for i, q in enumerate(queries):
        s, l = _row_coords(seg, q.terms)
        starts[i], lengths[i] = s, l
    longest = int(lengths.max()) if lengths.size else 0
    if longest == 0:
        return None
    return CsrTileMeta(starts, lengths, pad_width(longest, tile))


def stage_bool_postings(
    seg: Segment, queries: Sequence[BooleanQuery], pad_rows: int = 0
) -> Optional[Tuple[np.ndarray, np.ndarray]]:
    """(B+pad_rows, T, P) padded postings, or None when nothing matches."""
    n_terms = len(queries[0].terms)
    posts = [
        [seg.postings(term_hash(t.field, t.token)) for t in q.terms]
        for q in queries
    ]
    longest = max(
        (len(d) for row in posts for d, _ in row), default=0
    )
    if longest == 0:
        return None
    p = bucket(longest)
    rows = len(queries) + pad_rows
    docs = np.zeros((rows, n_terms, p), dtype=np.int32)
    freqs = np.zeros((rows, n_terms, p), dtype=np.int32)
    for i, row in enumerate(posts):
        for t, (d, f) in enumerate(row):
            docs[i, t, : len(d)] = d
            freqs[i, t, : len(f)] = f
    return docs, freqs
