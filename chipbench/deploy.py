"""Build one configuration's deployment from ``--seed``: generate its
documents, ingest them through the system's acked ``add_documents``, then
flush, commit and reopen, timing each phase as set-up.

The benchmark keeps every document it sent (``Corpus``), in external-id
order: the base corpus first, then the ingest stream (the live tail grown
before the window, then the window's marked batches).  The references
score exactly these documents.
"""

from __future__ import annotations

import dataclasses
import queue
import threading
import time
from typing import Callable, Dict, Optional, Sequence

import numpy as np

from chipbench import corpus as C
from chipbench.traffic import S_BASE, S_TAIL

#: marker ids of the warm-up ladder tokens (batch markers count from 0)
LADDER0 = 1 << 24


@dataclasses.dataclass
class Deployment:
    engine: object
    docs: C.Corpus  # every document the run sends, external id = position
    n_base: int  # committed before the window (flushed segments)
    n_tail: int  # acked into the live tail before the window
    first_batch: int  # external id of the first marked ingest doc
    batch_docs: int
    phases: Dict[str, float]
    #: warm-up ladder token ids per family (see ``ladder``)
    ladder: Dict[str, list] = dataclasses.field(default_factory=dict)


def _draw(cfg: dict, n: int, seed: int, stream: int) -> C.Corpus:
    c = cfg["corpus"]
    dim = cfg["vectors"]["dim"] if cfg.get("vectors") else 0
    return C.generate(
        n, c["vocab"], c["zipf_a"], c["mean_len"], C.rng_for(seed, stream),
        title=c["title"], doc_values=c["doc_values"], dim=dim,
    )


def _ingest(add: Callable, docs: C.Corpus, i0: int, i1: int, batch: int,
            vector_field: Optional[str]) -> None:
    """``add(batch)`` for docs ``[i0, i1)`` in order, the next batch's
    text built on a helper thread while the system acks this one."""
    q: "queue.Queue" = queue.Queue(maxsize=2)

    def produce() -> None:
        for lo in range(i0, i1, batch):
            q.put(docs.docs(lo, min(i1, lo + batch), vector_field))
        q.put(None)

    t = threading.Thread(target=produce, name="chipbench-docs", daemon=True)
    t.start()
    try:
        while (b := q.get()) is not None:
            add(b)
    finally:
        while t.is_alive():  # unblock the producer if ``add`` raised
            try:
                q.get(timeout=0.1)
            except queue.Empty:
                pass
        t.join()


def _bucket(n: float) -> int:
    """Power-of-two postings bucket of ``n`` (floor 8), as the planner
    pads a group's postings."""
    b = 8
    while b < n:
        b *= 2
    return b


def ladder(stream: C.Corpus, queries: Dict[str, Sequence[tuple]],
           n_shards: int, n_start: int, extra: Dict[str, Sequence[int]]
           ) -> Dict[str, list]:
    """Postings buckets the live tail's programs can meet in the window,
    per family: from the smallest a family's query reaches in one shard's
    tail when the window opens (``stream`` docs ``[0, n_start)``) to the
    largest it reaches by the window's end (all of ``stream``), with 10%
    room each way for the split between shards.

    The tail scores through programs whose shapes follow the power-of-two
    bucket of a group's busiest term, and it grows through the window.  A
    group's bucket is its busiest member's, so it lies in its family's
    range.  ``extra`` adds buckets a family also meets (the probes)."""
    terms = sorted({t for qs in queries.values() for q in qs for t in q})
    if not terms:
        return {}
    doc = np.repeat(np.arange(stream.n), np.diff(stream.offsets))
    hit = np.isin(stream.tokens, np.asarray(terms))
    pairs = np.unique(doc[hit] * (1 << 31) + stream.tokens[hit])
    pd, pt = pairs // (1 << 31), pairs % (1 << 31)
    idx = np.searchsorted(np.asarray(terms), pt)
    end = dict(zip(terms, np.bincount(idx, minlength=len(terms))))
    start = dict(zip(terms, np.bincount(idx[pd < n_start],
                                        minlength=len(terms))))
    out: Dict[str, list] = {}
    for fam, qs in queries.items():
        lo = min(_bucket(0.9 * max(start[t] for t in q) / n_shards)
                 for q in qs)
        hi = max(_bucket(1.1 * max(end[t] for t in q) / n_shards)
                 for q in qs)
        levels = set()
        p = lo
        while p <= hi:
            levels.add(p)
            p *= 2
        levels |= set(extra.get(fam, ()))
        out[fam] = sorted(levels)
    return out


def build(cfg: dict, mix: dict, seed: int, n_batches: int, workdir: str,
          log: Callable[[str], None],
          tail_queries: Optional[Dict[str, Sequence[tuple]]] = None,
          n_warm_batches: int = 0) -> Deployment:
    """Generate, ingest, flush, commit, pre-grow the live tail (carrying
    a warm-up ladder token for every postings bucket ``ladder`` finds for
    ``tail_queries``, whose window opens after ``n_warm_batches``), and
    reopen."""
    from repro.core.sharded import ShardedEngine
    from repro.core.writer import VECTOR_FIELD

    dep = cfg["deployment"]
    ing = mix.get("ingest") or {}
    batch_docs = ing.get("batch_docs", 1)
    n_tail = ing.get("tail_docs_at_start", 0)
    phases: Dict[str, float] = {}
    vec_field = VECTOR_FIELD if cfg.get("vectors") else None

    t0 = time.perf_counter()
    parts = [_draw(cfg, cfg["n_docs"], seed, S_BASE)]
    if n_tail or n_batches:
        stream = _draw(cfg, n_tail + n_batches * batch_docs, seed, S_TAIL)
        tail = C.Corpus(
            stream.tokens[: stream.offsets[n_tail]],
            stream.offsets[: n_tail + 1],
            stream.title_len[:n_tail],
            {k: v[:n_tail] for k, v in stream.dv.items()},
            None if stream.vectors is None else stream.vectors[:n_tail],
            vocab=stream.vocab,
        )
        lo = int(stream.offsets[n_tail])
        marked = C.with_markers(
            C.Corpus(
                stream.tokens[lo:],
                stream.offsets[n_tail:] - lo,
                stream.title_len[n_tail:],
                {k: v[n_tail:] for k, v in stream.dv.items()},
                None if stream.vectors is None else stream.vectors[n_tail:],
                vocab=stream.vocab,
            ),
            batch_docs, 0,
        )
        n_sh = dep["n_shards"]
        levels = ladder(
            stream, tail_queries or {}, n_sh,
            n_tail + n_warm_batches * batch_docs,
            {"term": [_bucket(1.1 * batch_docs / n_sh)]},  # the probes
        )
        buckets = sorted({p for ps in levels.values() for p in ps})
        if buckets:
            # the token of bucket P sits in 0.75 P docs of every shard
            tail = C.with_ladder(
                tail, [min(n_tail, round(0.75 * p * n_sh)) for p in buckets],
                LADDER0)
        token = {p: C.MARKER_BASE + LADDER0 + j for j, p in enumerate(buckets)}
        lad = {fam: [token[p] for p in ps] for fam, ps in levels.items()}
        parts += [tail, marked]
    else:
        lad = {}
    docs = C.concat(parts) if len(parts) > 1 else parts[0]
    phases["generate_s"] = time.perf_counter() - t0

    eng = ShardedEngine(
        dep["directory"], workdir, n_shards=dep["n_shards"],
        use_wal=dep["use_wal"], use_pallas=dep["use_pallas"],
        backend=dep["backend"],
    )
    try:
        n_base = cfg["n_docs"]
        t0 = time.perf_counter()
        _ingest(eng.add_documents, docs, 0, n_base, dep["ingest_batch_docs"],
                vec_field)
        phases["ingest_s"] = time.perf_counter() - t0
        t0 = time.perf_counter()
        eng.flush()
        phases["flush_s"] = time.perf_counter() - t0
        t0 = time.perf_counter()
        eng.commit()
        phases["commit_s"] = time.perf_counter() - t0
        if n_tail:
            t0 = time.perf_counter()
            _ingest(eng.add_documents, docs, n_base, n_base + n_tail,
                    dep["ingest_batch_docs"], vec_field)
            phases["tail_ingest_s"] = time.perf_counter() - t0
        t0 = time.perf_counter()
        eng.reopen()
        phases["reopen_s"] = time.perf_counter() - t0
    except BaseException:
        eng.close()
        raise
    log(
        f"deployment: {n_base} docs committed + {n_tail} in the live tail, "
        f"{dep['n_shards']} shards {dep['directory']} wal={dep['use_wal']} "
        f"backend={dep['backend']} use_pallas={dep['use_pallas']}"
    )
    return Deployment(eng, docs, n_base, n_tail, n_base + n_tail, batch_docs,
                      phases, lad)
