"""Synthetic wiki-like corpus for the engine benchmarks.

luceneutil indexes ``wikimedium500k`` (500k Wikipedia lines with title,
body, and doc-values fields like the month/day-of-year used by the
``BrowseMonthSSDVFacets`` test).  Offline we can't ship Wikipedia, so we
generate a corpus with the statistics the benchmarks depend on:

  * Zipfian token distribution (search perf depends on postings skew),
  * log-normal document lengths (BM25 length normalization),
  * uniform month/day-of-year/timestamp doc values (facet/sort/range).

Deterministic per seed.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Iterator, Tuple

import numpy as np


@dataclasses.dataclass(frozen=True)
class CorpusConfig:
    n_docs: int = 10_000
    vocab: int = 30_000
    zipf_a: float = 1.3
    mean_len: int = 80
    seed: int = 0


def _word(i: int) -> str:
    # compact deterministic token strings: w<base36>
    chars = "abcdefghijklmnopqrstuvwxyz"
    s = []
    i = int(i)
    while True:
        s.append(chars[i % 26])
        i //= 26
        if i == 0:
            break
    return "w" + "".join(s)


@functools.lru_cache(maxsize=4)
def _vocab_words(vocab: int) -> np.ndarray:
    """``_word(i)`` for every token id below ``vocab`` (object array, so a
    document's tokens map to strings with one fancy index)."""
    return np.array([_word(i) for i in range(vocab)], dtype=object)


def synthetic_corpus(cfg: CorpusConfig) -> Iterator[Tuple[Dict, Dict]]:
    """Yields (fields, doc_values) per document."""
    rng = np.random.default_rng(cfg.seed)
    words = _vocab_words(cfg.vocab)
    for i in range(cfg.n_docs):
        n = max(4, int(rng.lognormal(np.log(cfg.mean_len), 0.5)))
        toks = words[rng.zipf(cfg.zipf_a, size=n) % cfg.vocab].tolist()
        body = " ".join(toks)
        title = " ".join(toks[: max(2, n // 20)])
        dv = {
            "month": int(rng.integers(0, 12)),
            "dayOfYear": int(rng.integers(0, 365)),
            "timestamp": int(rng.integers(0, 1 << 30)),
        }
        yield {"title": title, "body": body}, dv
