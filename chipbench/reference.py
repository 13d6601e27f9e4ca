"""Plain references and the comparison that decides ``correct``.

Nothing here imports the system under test or takes anything it made.
The references read the benchmark's own documents (``corpus.Corpus``: the
token ids and doc values the benchmark generated and sent) and score them
with the textbook definitions the system documents:

* BM25 (Lucene's form, ``k1``/``b`` from the configuration) over every
  indexed field's length: ``idf = ln(1 + (N - df + 0.5) / (df + 0.5))``,
  collection statistics over the documents visible to the answer;
* boolean AND/OR: sum of the terms' BM25, over docs holding all / any;
* exact phrase: BM25 of the phrase frequency with the summed idf;
* range: constant score 1, lowest external id first;
* sort: the doc value as a float32 key, descending, lowest id on ties;
* facet: per-bin counts of the matching docs' doc values;
* dense vectors: exact top-k by dot product in float64.

The comparison is ``chip_smoke.py``'s: ``total_hits`` and facet counts
exact; scores within a relative tolerance (absolute below 1.0); ids may
swap only inside runs of reference scores tied within that tolerance, and
a run that reaches the cut may continue past it, with any id the
reference scores within that tolerance of its last score.  Where the scores are
exact keys (range, sort) the ids must match exactly.  Instead of raising,
it returns the numbers the run prints beside their limits.

``TextReference.control`` and ``VectorReference.answers(control=True)``
are the references put in the system's place at the next precision down
(bfloat16 for the stated float32); the comparison must find them wrong.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional, Sequence

import numpy as np

from chipbench.corpus import Corpus

#: families whose scores are exact keys: ids must match exactly
EXACT_KEY_FAMILIES = ("range", "sort")


@dataclasses.dataclass(frozen=True)
class Task:
    """One query as the benchmark describes it (the harness turns it into
    the system's query object; the reference reads it directly).

    ``family``: term | and | or | phrase | range | sort | facet | vector.
    ``terms``: body token ids.  ``field``: doc-values field.  ``lo``/``hi``:
    inclusive range.  ``n_bins``: facet bins.  ``vector``: row of the
    query-vector table.
    """

    category: str
    family: str
    terms: tuple = ()
    field: str = ""
    lo: int = 0
    hi: int = 0
    n_bins: int = 0
    vector: int = -1


@dataclasses.dataclass
class Answer:
    total_hits: int
    doc_ids: np.ndarray
    scores: np.ndarray
    facets: Optional[np.ndarray] = None
    #: the reference's score of any doc ids (NaN where a doc does not
    #: match): what an id past the cut is held to
    score_of: Optional[Callable[[np.ndarray], np.ndarray]] = None


def _lookup(docs: np.ndarray, s: np.ndarray) -> Callable:
    """``score_of`` over ascending ``docs`` and their scores ``s``."""
    def score_of(ids: np.ndarray) -> np.ndarray:
        if not len(docs):
            return np.full(len(ids), np.nan)
        i = np.minimum(np.searchsorted(docs, ids), len(docs) - 1)
        return np.where(docs[i] == ids, s[i], np.nan)
    return score_of


def _topk(ids: np.ndarray, keys: np.ndarray, k: int):
    """Top ``k`` by key descending, id ascending."""
    order = np.lexsort((ids, -keys.astype(np.float64)))[:k]
    return ids[order], keys[order]


class TextReference:
    """BM25 families over the first ``n_vis`` docs of ``corpus``."""

    def __init__(self, corpus: Corpus, k1: float, b: float) -> None:
        self.c = corpus
        self.k1, self.b = k1, b
        self.dl = corpus.doc_len().astype(np.float64)
        self._cum_dl = np.concatenate([[0.0], np.cumsum(self.dl)])
        self._pos: Dict[int, np.ndarray] = {}

    # -- postings by scan ---------------------------------------------------
    def _positions(self, t: int) -> np.ndarray:
        p = self._pos.get(t)
        if p is None:
            p = self._pos[t] = np.flatnonzero(self.c.tokens == t)
        return p

    def _doc_of(self, pos: np.ndarray) -> np.ndarray:
        return np.searchsorted(self.c.offsets, pos, side="right") - 1

    def postings(self, t: int, n_vis: int):
        """(doc ids ascending, term frequency) of body token ``t``."""
        pos = self._positions(t)
        pos = pos[pos < self.c.offsets[n_vis]]
        docs, tf = np.unique(self._doc_of(pos), return_counts=True)
        return docs, tf.astype(np.float64)

    def phrase_postings(self, terms: Sequence[int], n_vis: int):
        """(doc ids, phrase frequency) of the exact body phrase ``terms``."""
        end = self.c.offsets[n_vis]
        start = self._positions(terms[0])
        start = start[start < end]
        ok = np.ones(len(start), dtype=bool)
        doc = self._doc_of(start)
        for j, t in enumerate(terms[1:], 1):
            nxt = start + j
            inside = nxt < self.c.offsets[doc + 1]
            tok = np.where(inside, self.c.tokens[np.minimum(nxt, end - 1)], -1)
            ok &= inside & (tok == t)
        docs, tf = np.unique(doc[ok], return_counts=True)
        return docs, tf.astype(np.float64)

    # -- statistics ---------------------------------------------------------
    def stats(self, n_vis: int):
        return n_vis, self._cum_dl[n_vis] / n_vis

    def idf(self, t: int, n_vis: int) -> float:
        df = len(self.postings(t, n_vis)[0])
        return float(np.log(1.0 + (n_vis - df + 0.5) / (df + 0.5)))

    def bm25(self, tf, docs, idf, n_vis, dtype=np.float64):
        """Lucene's BM25 with every input and operation in ``dtype``."""
        _, avgdl = self.stats(n_vis)
        tf = np.asarray(tf, dtype)
        dl = np.asarray(self.dl[docs], dtype)
        idf = np.asarray(idf, dtype)
        avgdl = np.asarray(avgdl, dtype)
        k1 = np.asarray(self.k1, dtype)
        b = np.asarray(self.b, dtype)
        one = np.asarray(1.0, dtype)
        return idf * (tf * (k1 + one)) / (tf + k1 * (one - b + b * dl / avgdl))

    # -- families -----------------------------------------------------------
    def scored(self, task: Task, n_vis: int, dtype=np.float64):
        """(matching doc ids, scores as float64) before the top-k cut."""
        fam = task.family
        if fam == "term":
            docs, tf = self.postings(task.terms[0], n_vis)
            s = self.bm25(tf, docs, self.idf(task.terms[0], n_vis), n_vis,
                          dtype)
            return docs, np.asarray(s, np.float64)
        if fam in ("and", "or"):
            per = [self.postings(t, n_vis) for t in task.terms]
            docs = per[0][0]
            for d, _ in per[1:]:
                docs = (np.intersect1d(docs, d) if fam == "and"
                        else np.union1d(docs, d))
            total = None
            for t, (d, tf) in zip(task.terms, per):
                full = np.zeros(len(docs))
                sel = np.searchsorted(docs, d)
                keep = sel < len(docs)
                keep[keep] &= docs[sel[keep]] == d[keep]
                full[sel[keep]] = tf[keep]
                s = self.bm25(full, docs, self.idf(t, n_vis), n_vis, dtype)
                s = np.where(full > 0, s, np.asarray(0, dtype))
                total = s if total is None else total + s
            return docs, np.asarray(total, np.float64)
        if fam == "phrase":
            docs, tf = self.phrase_postings(task.terms, n_vis)
            idf = sum(self.idf(t, n_vis) for t in task.terms)
            s = self.bm25(tf, docs, idf, n_vis, dtype)
            return docs, np.asarray(s, np.float64)
        raise ValueError(f"not a scored text family: {fam}")

    def answer(self, task: Task, n_vis: int, k: int) -> Answer:
        fam = task.family
        if fam in ("term", "and", "or", "phrase"):
            docs, s = self.scored(task, n_vis)
            ids, keys = _topk(docs, s, k)
            return Answer(len(docs), ids, keys, score_of=_lookup(docs, s))
        if fam == "range":
            v = self.c.dv[task.field][:n_vis]
            docs = np.flatnonzero((v >= task.lo) & (v <= task.hi))
            return Answer(len(docs), docs[:k], np.ones(min(k, len(docs))))
        if fam == "sort":
            docs, _ = self.postings(task.terms[0], n_vis)
            key = self.c.dv[task.field][docs].astype(np.float32)
            ids, keys = _topk(docs, key, k)
            return Answer(len(docs), ids, keys.astype(np.float64))
        if fam == "facet":
            if task.terms:
                docs, _ = self.postings(task.terms[0], n_vis)
            else:
                docs = np.arange(n_vis)
            v = np.maximum(self.c.dv[task.field][docs], 0)
            v = v[v < task.n_bins]
            counts = np.bincount(v, minlength=task.n_bins).astype(np.float64)
            order = np.argsort(-counts, kind="stable")[:k]
            return Answer(len(docs), order, counts[order], counts)
        raise ValueError(f"no text reference for family {fam}")

    def control(self, task: Task, n_vis: int, k: int) -> Answer:
        """The reference in bfloat16: every BM25 input rounded to bfloat16
        and every operation's result rounded back (``ml_dtypes``, on the
        host).  The exact-key families have nothing to lose in precision
        and answer as the reference does."""
        if task.family not in ("term", "and", "or", "phrase"):
            return self.answer(task, n_vis, k)
        from ml_dtypes import bfloat16

        docs, s = self.scored(task, n_vis, dtype=bfloat16)
        ids, keys = _topk(docs, s, k)
        return Answer(len(docs), ids, keys)


class VectorReference:
    """Exact dense top-k by dot product over the first ``n_vis`` rows."""

    BLOCK = 32768

    def __init__(self, vectors: np.ndarray) -> None:
        self.v = vectors

    def answers(self, queries: np.ndarray, n_vis: int, k: int,
                control: bool = False) -> list:
        """One ``Answer`` per row of ``queries``.  ``control``: the same
        scan with the rows and queries in bfloat16 (float32 accumulation,
        JAX on the default device)."""
        nq = len(queries)
        best_s = np.full((nq, 0), -np.inf)
        best_i = np.zeros((nq, 0), dtype=np.int64)
        if control:
            import jax.numpy as jnp

            qd = jnp.asarray(queries, jnp.bfloat16)
        else:
            q64 = queries.astype(np.float64)
        for lo in range(0, n_vis, self.BLOCK):
            hi = min(n_vis, lo + self.BLOCK)
            if control:
                blk = jnp.asarray(self.v[lo:hi], jnp.bfloat16)
                s = np.asarray(
                    jnp.dot(qd, blk.T, preferred_element_type=jnp.float32),
                    np.float64,
                )
            else:
                s = q64 @ self.v[lo:hi].astype(np.float64).T
            ids = np.broadcast_to(np.arange(lo, hi), s.shape)
            cat_s = np.concatenate([best_s, s], axis=1)
            cat_i = np.concatenate([best_i, ids], axis=1)
            order = np.lexsort((cat_i, -cat_s), axis=1)[:, :k]
            best_s = np.take_along_axis(cat_s, order, axis=1)
            best_i = np.take_along_axis(cat_i, order, axis=1)
        if control:
            return [Answer(n_vis, best_i[j], best_s[j]) for j in range(nq)]
        return [Answer(n_vis, best_i[j], best_s[j],
                       score_of=self._score_of(queries[j], n_vis))
                for j in range(nq)]

    def _score_of(self, query: np.ndarray, n_vis: int) -> Callable:
        q64 = np.asarray(query, np.float64)

        def score_of(ids: np.ndarray) -> np.ndarray:
            ok = (ids >= 0) & (ids < n_vis)
            s = self.v[np.where(ok, ids, 0)].astype(np.float64) @ q64
            return np.where(ok, s, np.nan)
        return score_of


# ---------------------------------------------------------------------------
# comparison
# ---------------------------------------------------------------------------


def score_gap(got, ref: Answer) -> float:
    """Largest ``|got - ref| / max(|ref|, 1)`` over the ranks both hold."""
    n = min(len(got.scores), len(ref.scores))
    if n == 0:
        return 0.0
    a = np.asarray(got.scores[:n], np.float64)
    b = np.asarray(ref.scores[:n], np.float64)
    return float(np.max(np.abs(a - b) / np.maximum(np.abs(b), 1.0)))


def ids_differ(got, ref: Answer, family: str, tol: float) -> bool:
    """True where ``got``'s ids disagree with ``ref`` beyond ties."""
    g = np.asarray(got.doc_ids, np.int64)
    r = np.asarray(ref.doc_ids, np.int64)
    if len(g) != len(r) or len(set(g.tolist())) != len(g):
        return True
    if family in EXACT_KEY_FAMILIES:
        return not np.array_equal(g, r)
    s = np.asarray(ref.scores, np.float64)

    def tied(i: int) -> bool:
        return abs(s[i] - s[i - 1]) <= tol * max(abs(s[i - 1]), 1.0)

    start = 0
    for i in range(1, len(s)):
        if not tied(i):
            if set(g[start:i]) != set(r[start:i]):
                return True
            start = i
    # the run from ``start`` reaches the cut and may continue past it: an
    # id there that the reference did not return must score as its last
    # id does
    new = set(g[start:].tolist()) - set(r[start:].tolist())
    extra = np.array(sorted(new), np.int64)
    if not len(extra):
        return False
    if ref.score_of is None:
        return True
    got_s = np.asarray(ref.score_of(extra), np.float64)
    return not bool(np.all(
        np.abs(got_s - s[-1]) <= tol * max(abs(s[-1]), 1.0)))


@dataclasses.dataclass
class Tally:
    """What the comparison found over every checked answer."""

    checked: int = 0
    score_gap: float = 0.0
    hits_wrong: int = 0
    ids_wrong: int = 0
    facets_wrong: int = 0
    examples: list = dataclasses.field(default_factory=list)

    def add(self, got, ref: Answer, family: str, tol: float, what: str):
        self.checked += 1
        bad = []
        if int(got.total_hits) != int(ref.total_hits):
            self.hits_wrong += 1
            bad.append(f"total_hits {got.total_hits} != {ref.total_hits}")
        if ref.facets is not None:
            if got.facets is None or not np.array_equal(
                np.asarray(got.facets, np.float64), ref.facets
            ):
                self.facets_wrong += 1
                bad.append("facet counts differ")
        else:
            gap = score_gap(got, ref)
            self.score_gap = max(self.score_gap, gap)
            if ids_differ(got, ref, family, tol):
                self.ids_wrong += 1
                bad.append(
                    f"ids {np.asarray(got.doc_ids).tolist()} != "
                    f"{np.asarray(ref.doc_ids).tolist()}"
                )
            if gap > tol:
                bad.append(f"score gap {gap:.3g}")
        if bad and len(self.examples) < 3:
            self.examples.append(f"{what}: " + "; ".join(bad))
