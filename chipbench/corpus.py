"""Seeded document generator: the benchmark's own copy of the program's
``data/corpus.py`` statistics, drawn in bulk.

luceneutil's wikimedium line docs cannot ship here, so a corpus is drawn
with the statistics search cost depends on:

* Zipf(``zipf_a``) token ids folded into a ``vocab``-word vocabulary
  (postings skew; the fold gives the flat tail a real vocabulary has);
* log-normal document lengths with median ``mean_len`` (BM25 length norm);
* uniform ``month`` / ``dayOfYear`` / ``timestamp`` doc values (facet,
  sort and range).

Every token of a corpus comes from ONE inverse-CDF draw of the folded Zipf
law and every length from ONE ``lognormal`` call, so generation costs a
few seconds at 500k docs.
The token ids are kept beside the text: the reference scorer
(``chipbench.reference``) reads them directly and never re-parses the text
the system indexed.

Text follows the convention the system's StandardAnalyzer-alike expects:
lower-case alphanumeric words separated by single spaces, so the tokens a
reader of the text finds are exactly ``tokens``.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

#: extra body token ids start here; they are spelled ``"m<hex>"`` and can
#: never collide with a vocabulary word (those start with ``"w"``)
MARKER_BASE = 1 << 30


def word(i: int) -> str:
    """Vocabulary word ``i``: ``"w"`` + base-26 digits (same spelling as the
    program's generator, so task terms read the same in both)."""
    chars = "abcdefghijklmnopqrstuvwxyz"
    s = []
    i = int(i)
    while True:
        s.append(chars[i % 26])
        i //= 26
        if i == 0:
            break
    return "w" + "".join(s)


def marker(i: int) -> str:
    """Spelling of extra token ``MARKER_BASE + i`` (a batch marker)."""
    return f"m{int(i):x}"


def token_text(t: int) -> str:
    return marker(t - MARKER_BASE) if t >= MARKER_BASE else word(t)


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """Independent generator for one purpose of one run: ``seed`` is any
    integer (reduced mod 2**64), ``stream`` names the purpose."""
    ss = np.random.SeedSequence(int(seed) % (1 << 64), spawn_key=tuple(stream))
    return np.random.default_rng(ss)


def zipf_folded_cdf(zipf_a: float, vocab: int) -> np.ndarray:
    """CDF over ids ``0..vocab-1`` of ``numpy.random.zipf(zipf_a) % vocab``
    (the program generator's draw), exact by the Hurwitz zeta function:
    ``P(j) = vocab**-a * zeta(a, j/vocab) / zeta(a)`` for ``j >= 1`` and
    ``P(0) = vocab**-a``.  Sampling it by inverse CDF gives the same
    distribution in one vectorised pass."""
    from scipy.special import zeta

    j = np.arange(1, vocab, dtype=np.float64)
    p = np.empty(vocab, dtype=np.float64)
    p[0] = vocab ** -zipf_a
    p[1:] = vocab ** -zipf_a * zeta(zipf_a, j / vocab) / zeta(zipf_a)
    cdf = np.cumsum(p)
    return cdf / cdf[-1]


@dataclasses.dataclass
class Corpus:
    """``n`` documents as flat token ids plus per-doc columns."""

    tokens: np.ndarray  # (T,) int64 body token ids, doc after doc
    offsets: np.ndarray  # (n+1,) int64 body span of doc i
    title_len: np.ndarray  # (n,) int64: title = first title_len body tokens
    dv: Dict[str, np.ndarray]  # month / dayOfYear / timestamp, (n,) int64
    vectors: Optional[np.ndarray] = None  # (n, dim) float32 or None
    vocab: int = 0  # ids below this are words; MARKER_BASE + i are markers
    _spell: Optional[tuple] = dataclasses.field(default=None, repr=False)

    @property
    def n(self) -> int:
        return len(self.offsets) - 1

    def doc_len(self) -> np.ndarray:
        """Tokens per doc over every indexed field (body + title): the
        length BM25 normalises by."""
        return np.diff(self.offsets) + self.title_len

    def _spelling(self):
        """(byte table, byte length incl. the separating space, marker ids
        in table order): row ``t`` spells word ``t``; row ``vocab + j``
        spells the ``j``-th distinct marker."""
        if self._spell is None:
            marks = np.unique(self.tokens[self.tokens >= MARKER_BASE])
            spell = [word(i).encode() for i in range(self.vocab)]
            spell += [token_text(int(m)).encode() for m in marks]
            width = max(len(x) for x in spell) + 1
            table = np.full((len(spell), width), ord(" "), dtype=np.uint8)
            lens = np.empty(len(spell), dtype=np.int64)
            for j, x in enumerate(spell):
                table[j, : len(x)] = np.frombuffer(x, dtype=np.uint8)
                lens[j] = len(x) + 1
            self._spell = (table, lens, marks)
        return self._spell

    def texts(self, i0: int, i1: int) -> Tuple[List[str], List[str]]:
        """Body and title strings of docs ``[i0, i1)``, cut from one byte
        buffer (the title is a prefix of the body)."""
        table, lens, marks = self._spelling()
        lo, hi = int(self.offsets[i0]), int(self.offsets[i1])
        toks = self.tokens[lo:hi]
        row = np.where(toks >= MARKER_BASE,
                       self.vocab + np.searchsorted(marks, toks), toks)
        tok_len = lens[row]
        ends = np.cumsum(tok_len)
        starts = ends - tok_len
        total = int(ends[-1]) if len(ends) else 0
        col = np.arange(total, dtype=np.int64) - np.repeat(starts, tok_len)
        buf = table[np.repeat(row, tok_len), col].tobytes().decode("ascii")
        ends0 = np.concatenate([[0], ends])
        first = self.offsets[i0:i1 + 1] - lo
        cut = ends0[first]
        tcut = ends0[first[:-1] + self.title_len[i0:i1]]
        bodies = [buf[cut[j]:cut[j + 1] - 1] for j in range(i1 - i0)]
        titles = [buf[cut[j]:tcut[j] - 1] for j in range(i1 - i0)]
        return bodies, titles

    def docs(self, i0: int, i1: int, vector_field: Optional[str] = None
             ) -> List[Tuple[dict, dict]]:
        """``(fields, doc_values)`` pairs for docs ``[i0, i1)``, as the
        system's ``add_documents`` takes them (no title field where the
        corpus has none)."""
        bodies, titles = self.texts(i0, i1)
        cols = {k: v[i0:i1].tolist() for k, v in self.dv.items()}
        out = []
        for j, body in enumerate(bodies):
            fields = {"title": titles[j], "body": body} if titles[j] else {
                "body": body}
            dv = {k: c[j] for k, c in cols.items()}
            if vector_field is not None:
                dv[vector_field] = self.vectors[i0 + j]
            out.append((fields, dv))
        return out


def generate(n_docs: int, vocab: int, zipf_a: float, mean_len: int,
             rng: np.random.Generator, title: bool = True,
             doc_values: bool = True, dim: int = 0) -> Corpus:
    """Draw ``n_docs`` documents (see module docstring)."""
    lens = np.maximum(
        4, rng.lognormal(np.log(mean_len), 0.5, size=n_docs).astype(np.int64)
    )
    offsets = np.zeros(n_docs + 1, dtype=np.int64)
    np.cumsum(lens, out=offsets[1:])
    cdf = zipf_folded_cdf(zipf_a, vocab)
    tokens = np.searchsorted(cdf, rng.random(int(offsets[-1])), side="right")
    tokens = np.minimum(tokens, vocab - 1)
    title_len = (
        np.minimum(lens, np.maximum(2, lens // 20)) if title
        else np.zeros(n_docs, dtype=np.int64)
    )
    dv: Dict[str, np.ndarray] = {}
    if doc_values:
        dv = {
            "month": rng.integers(0, 12, size=n_docs),
            "dayOfYear": rng.integers(0, 365, size=n_docs),
            "timestamp": rng.integers(0, 1 << 30, size=n_docs),
        }
    vectors = None
    if dim:
        vectors = rng.standard_normal((n_docs, dim), dtype=np.float32)
    return Corpus(tokens.astype(np.int64), offsets, title_len, dv, vectors,
                  vocab=vocab)


def append_tokens(c: Corpus, extra: np.ndarray, per_doc: np.ndarray
                  ) -> Corpus:
    """Copy of ``c`` whose doc ``i`` ends with ``per_doc[i]`` more body
    tokens, taken in order from ``extra``.  The title keeps its original
    tokens."""
    n = c.n
    lens = np.diff(c.offsets)
    new_off = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(lens + per_doc, out=new_off[1:])
    toks = np.empty(int(new_off[-1]), dtype=np.int64)
    doc = np.repeat(np.arange(n), lens + per_doc)
    within = np.arange(len(toks)) - new_off[doc]
    orig = within < lens[doc]
    toks[orig] = c.tokens
    toks[~orig] = extra
    return Corpus(toks, new_off, c.title_len.copy(), dict(c.dv), c.vectors,
                  vocab=c.vocab)


def with_markers(c: Corpus, batch_docs: int, first_marker: int) -> Corpus:
    """Copy of ``c`` whose every doc ends with its batch's marker token:
    docs ``[b*batch_docs, (b+1)*batch_docs)`` carry marker
    ``first_marker + b``."""
    batch = np.arange(c.n) // batch_docs
    return append_tokens(c, MARKER_BASE + first_marker + batch,
                         np.ones(c.n, dtype=np.int64))


def with_ladder(c: Corpus, counts: Sequence[int], first_marker: int
                ) -> Corpus:
    """Copy of ``c`` where marker ``first_marker + j`` ends ``counts[j]``
    docs: a token of known document frequency for every entry of
    ``counts``.  Each token takes the next ``counts[j]`` docs round the
    corpus, so every doc carries about the same number of them."""
    counts = np.asarray(counts, dtype=np.int64)
    starts = (np.cumsum(counts) - counts) % c.n
    d = np.arange(c.n)[:, None]
    hold = (d - starts[None, :]) % c.n < counts[None, :]  # (n, len(counts))
    extra = (MARKER_BASE + first_marker
             + np.nonzero(hold)[1]).astype(np.int64)  # row-major: per doc
    return append_tokens(c, extra, hold.sum(axis=1))


def concat(parts: Sequence[Corpus]) -> Corpus:
    """Documents of ``parts`` in order (external id = position)."""
    offs = [np.zeros(1, dtype=np.int64)]
    base = 0
    for p in parts:
        offs.append(p.offsets[1:] + base)
        base += int(p.offsets[-1])
    vecs = [p.vectors for p in parts]
    return Corpus(
        np.concatenate([p.tokens for p in parts]),
        np.concatenate(offs),
        np.concatenate([p.title_len for p in parts]),
        {k: np.concatenate([p.dv[k] for p in parts]) for k in parts[0].dv},
        None if any(v is None for v in vecs) else np.concatenate(vecs),
        vocab=max(p.vocab for p in parts),
    )
