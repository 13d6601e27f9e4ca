"""Smoke run of the served search path on one TPU chip.

    python chip_smoke.py [--seed N]

Drives the system through the entry points a user calls, at a size users
would call real, and checks every answer against a reference computed on
the same chip:

* Phase A (luceneutil ``wikimedium500k`` scale, ``data/corpus.py``): a
  2-shard ``byte-pmem`` ``ShardedEngine`` with per-shard WALs, the
  ``processes`` ingest backend and ``use_pallas=True`` indexes 500k
  wikimedium-shaped documents in acked ``add_documents`` batches, flushes
  and commits.  A ``SearchFrontend`` over it serves term, AND/OR boolean,
  phrase, range, sort and facet queries, ingests more acked batches while
  serving, answers them from the live tail after the visibility-lag reopen
  (no flush), then commits.
* Phase B (MS MARCO passage width): a separate 100k-passage index with a
  768-d dot-product ``_vec`` column serves ``VectorQuery`` and
  ``HybridQuery`` through the same front end.

References: ``search_single`` (the sequential jnp oracle, run per shard on
the response's own snapshot and merged like the fan-out) for the BM25
families and hybrid, exact brute-force top-k in float64 on the host for
vectors.  ``total_hits`` and ids must match exactly; scores within
``RTOL`` (relative; absolute below 1.0); ids may swap only among scores
tied within that tolerance; facet counts exactly.

The script refuses to run anywhere but a TPU with compiled kernels: it
exits non-zero, without the final line, when JAX finds no TPU, when
``REPRO_FUSED_KERNEL`` is set, or when any phase fails.  Timings it prints
are a first chip reading, not a benchmark.  The last stdout line is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import numpy as np  # noqa: E402

from repro.core.query import profile  # noqa: E402
from repro.core.query.types import (  # noqa: E402
    BooleanQuery,
    FacetQuery,
    HybridQuery,
    PhraseQuery,
    RangeQuery,
    SortQuery,
    TermQuery,
    TopDocs,
    VectorQuery,
)
from repro.core.sharded import ShardedEngine  # noqa: E402
from repro.core.writer import VECTOR_FIELD  # noqa: E402
from repro.data.corpus import CorpusConfig, _word, synthetic_corpus  # noqa: E402
from repro.serve.search_frontend import SearchFrontend  # noqa: E402

#: luceneutil's wikimedium500k document count
N_DOCS = 500_000
#: MS MARCO passage embedding width (dot product)
N_PASSAGES = 100_000
DIM = 768
N_SHARDS = 2
K = 10
#: acked documents per add_documents batch (one WAL record per shard)
BATCH_DOCS = 2_000
#: score tolerance against the reference (relative; absolute below 1.0)
RTOL = 1e-5
#: token no generated document contains: marks the live-tail batches
FRESH = "freshmarker"


class ParityError(AssertionError):
    """A served answer disagreed with its reference."""


# ---------------------------------------------------------------------------
# references and comparison
# ---------------------------------------------------------------------------


def sharded_single(searcher, q, k: int) -> TopDocs:
    """``search_single`` on every shard view of a fan-out snapshot, merged
    the way the fan-out merges: facets summed; otherwise external ids,
    score descending then external id ascending."""
    tds = [(s, s.search_single(q, k)) for s in searcher.searchers]
    total = sum(td.total_hits for _, td in tds)
    if isinstance(q, FacetQuery):
        facets = sum(td.facets for _, td in tds)
        order = np.argsort(-facets, kind="stable")[:k]
        return TopDocs(total, order, facets[order].astype(np.float32), facets)
    ids = np.concatenate(
        [s.ext_ids[np.asarray(td.doc_ids, np.int64)] for s, td in tds]
    )
    scores = np.concatenate([np.asarray(td.scores, np.float32) for _, td in tds])
    order = np.lexsort((ids, -scores))[:k]
    return TopDocs(total, ids[order], scores[order])


def brute_force_topk(vecs: np.ndarray, q: np.ndarray, k: int) -> TopDocs:
    """Exact dot-product top-k over every vector (index == external id)."""
    s = vecs.astype(np.float64) @ q.astype(np.float64)
    order = np.lexsort((np.arange(len(s)), -s))[:k]
    return TopDocs(len(s), order, s[order])


def _close(a, b) -> np.ndarray:
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return np.abs(a - b) <= RTOL * np.maximum(np.abs(b), 1.0)


def check(got: TopDocs, ref: TopDocs, what: str) -> None:
    """Raise ``ParityError`` unless ``got`` matches ``ref`` (see module
    docstring for the tolerance rules)."""
    if got.total_hits != ref.total_hits:
        raise ParityError(f"{what}: total_hits {got.total_hits} != {ref.total_hits}")
    if ref.facets is not None:
        if got.facets is None or not np.array_equal(got.facets, ref.facets):
            raise ParityError(f"{what}: facet counts differ")
        return
    g_ids = np.asarray(got.doc_ids, np.int64)
    r_ids = np.asarray(ref.doc_ids, np.int64)
    if len(g_ids) != len(r_ids):
        raise ParityError(f"{what}: {len(g_ids)} hits returned, want {len(r_ids)}")
    if not _close(got.scores, ref.scores).all():
        raise ParityError(
            f"{what}: scores {np.asarray(got.scores).tolist()} != "
            f"{np.asarray(ref.scores).tolist()}"
        )
    # ids may differ only inside runs of reference scores tied within the
    # tolerance; a run reaching the cut may continue past it, so only its
    # scores are pinned
    r_s = np.asarray(ref.scores, np.float64)
    start = 0
    for i in range(1, len(r_s) + 1):
        if i < len(r_s) and _close(r_s[i], r_s[i - 1]):
            continue
        if i < len(r_s) and set(g_ids[start:i]) != set(r_ids[start:i]):
            raise ParityError(
                f"{what}: ids {g_ids.tolist()} != {r_ids.tolist()}"
            )
        start = i


def serve_and_check(fe: SearchFrontend, queries, ref_fn) -> dict:
    """Submit every query at once (they coalesce into waves), then compare
    each answer with ``ref_fn(ticket)``.  Returns per-family counts."""
    tickets = [fe.submit(q, K) for q in queries]
    counts: dict = {}
    for t in tickets:
        td = t.result(timeout=600)
        fam = type(t.query).__name__
        check(td, ref_fn(t), f"{fam} {t.query!r}"[:200])
        counts[fam] = counts.get(fam, 0) + 1
    return counts


def _single_ref(t):
    return sharded_single(t.searcher, t.query, K)


# ---------------------------------------------------------------------------
# phase A: wikimedium documents, BM25 families, live tail
# ---------------------------------------------------------------------------


def bm25_queries() -> list:
    """High/medium/low-frequency terms over every BM25 family."""
    hi = [_word(i) for i in (1, 2, 3)]
    med = [_word(i) for i in (40, 90)]
    low = [_word(i) for i in (1500, 7000)]
    t = lambda w: TermQuery("body", w)  # noqa: E731
    return (
        [t(w) for w in hi + med + low]
        + [BooleanQuery((t(a), t(b)), "and") for a, b in [(hi[0], med[0]), (med[1], low[0])]]
        + [BooleanQuery((t(a), t(b)), "or") for a, b in [(hi[1], hi[2]), (med[0], low[1])]]
        + [PhraseQuery("body", (hi[0], hi[1])), PhraseQuery("body", (med[0], hi[0]))]
        + [RangeQuery("dayOfYear", 100, 130), RangeQuery("month", 3, 5)]
        + [SortQuery(t(w), "timestamp") for w in (hi[2], med[1])]
        + [FacetQuery(None, "month", 12), FacetQuery(t(med[0]), "month", 12)]
    )


def _log(msg: str) -> None:
    print(msg, flush=True)


def phase_a(workdir: str, n_docs: int, seed: int, live_batches: int = 3,
            live_batch_docs: int = 500) -> dict:
    """Index ``n_docs`` wikimedium-shaped docs (2 shards, byte-pmem, WAL,
    processes backend, fused kernels), serve every BM25 family through
    ``SearchFrontend``, then ingest ``live_batches`` acked batches while
    serving and answer them from the live tail.  Raises on any miss."""
    rep: dict = {}
    t0 = time.perf_counter()
    docs = list(synthetic_corpus(CorpusConfig(n_docs=n_docs, seed=seed)))
    rep["corpus_s"] = time.perf_counter() - t0
    eng = ShardedEngine(
        "byte-pmem", os.path.join(workdir, "wiki"), n_shards=N_SHARDS,
        use_wal=True, use_pallas=True, backend="processes",
    )
    try:
        t0 = time.perf_counter()
        for i in range(0, n_docs, BATCH_DOCS):
            eng.add_documents(docs[i:i + BATCH_DOCS])  # returns at the ack
        rep["ingest_s"] = time.perf_counter() - t0
        t0 = time.perf_counter()
        eng.flush()
        rep["flush_s"] = time.perf_counter() - t0
        t0 = time.perf_counter()
        eng.commit()
        rep["commit_s"] = time.perf_counter() - t0
        t0 = time.perf_counter()
        eng.reopen()
        rep["reopen_s"] = time.perf_counter() - t0
        rep["worker_jax_platforms"] = sorted(
            {s["jax_platforms"] for s in eng.writer.stats()["per_shard"]}
        )
        if rep["worker_jax_platforms"] != ["cpu"]:
            raise RuntimeError(
                f"shard workers not host-only: {rep['worker_jax_platforms']}"
            )
        _log(
            f"phase A: indexed {n_docs} docs, {N_SHARDS} shards byte-pmem+WAL "
            f"processes backend (workers jax_platforms="
            f"{rep['worker_jax_platforms']}): corpus {rep['corpus_s']:.1f}s "
            f"ingest {rep['ingest_s']:.1f}s flush {rep['flush_s']:.1f}s "
            f"commit {rep['commit_s']:.2f}s reopen {rep['reopen_s']:.1f}s"
        )
        fe = SearchFrontend(eng, max_wave=64, reopen_lag_s=0.0)
        try:
            queries = bm25_queries()
            t0 = time.perf_counter()
            with profile.capture() as disp:
                counts = serve_and_check(fe, queries, _single_ref)
            rep["committed_s"] = time.perf_counter() - t0
            rep["committed"] = counts
            rep["dispatches"] = dict(disp)
            _log(
                f"phase A committed: parity ok {counts} in "
                f"{rep['committed_s']:.1f}s (first wave compiles); fused "
                f"dispatches {dict(disp)}"
            )
            # acked ingest while queries are in flight, then the live tail
            rng = np.random.default_rng(seed + 1)
            fresh = list(synthetic_corpus(CorpusConfig(
                n_docs=live_batches * live_batch_docs, seed=seed + 2)))
            acks, inflight = [], []
            for b in range(live_batches):
                batch = fresh[b * live_batch_docs:(b + 1) * live_batch_docs]
                batch = [
                    ({**f, "body": f"{f['body']} {FRESH}"}, dv)
                    if rng.random() < 0.5 else (f, dv)
                    for f, dv in batch
                ]
                acks.append((fe.submit_ingest(batch), batch))
                inflight += [fe.submit(q, K) for q in queries[:4]]
            n_fresh = 0
            for ticket, batch in acks:
                ticket.result(timeout=600)
                n_fresh += sum(FRESH in f["body"] for f, _ in batch)
            for t in inflight:
                check(t.result(timeout=600), _single_ref(t), f"in-flight {t.query!r}")
            live_q = [TermQuery("body", FRESH)] + queries
            t0 = time.perf_counter()
            counts = serve_and_check(fe, live_q, _single_ref)
            rep["live_s"] = time.perf_counter() - t0
            fresh_td = fe.search(TermQuery("body", FRESH), K)
            if fresh_td.total_hits != n_fresh:
                raise ParityError(
                    f"live tail: {fresh_td.total_hits} fresh hits, acked {n_fresh}"
                )
            rep["live"] = counts
            rep["live_docs"] = live_batches * live_batch_docs
            rep["frontend"] = {
                key: fe.stats()[key] for key in ("waves", "mean_wave", "reopens")
            }
            epoch = fe.commit()
            _log(
                f"phase A live tail: {rep['live_docs']} acked docs visible "
                f"without flush ({n_fresh} fresh hits), parity ok {counts} in "
                f"{rep['live_s']:.1f}s; in-flight {len(inflight)} ok; "
                f"commit epoch {epoch}; frontend {rep['frontend']}"
            )
        finally:
            fe.close()
    finally:
        eng.close()
    return rep


# ---------------------------------------------------------------------------
# phase B: passages with dense vectors, vector + hybrid families
# ---------------------------------------------------------------------------


def phase_b(workdir: str, n_passages: int, dim: int, seed: int) -> dict:
    """Index ``n_passages`` passages with a ``dim``-d dot-product vector
    column and serve ``VectorQuery``/``HybridQuery`` through the front end.
    Raises on any miss."""
    rep: dict = {}
    rng = np.random.default_rng(seed)
    vecs = rng.standard_normal((n_passages, dim)).astype(np.float32)
    texts = synthetic_corpus(CorpusConfig(n_docs=n_passages, mean_len=56, seed=seed))
    docs = [
        ({"body": f["body"]}, {VECTOR_FIELD: v})
        for (f, _), v in zip(texts, vecs)
    ]
    eng = ShardedEngine(
        "byte-pmem", os.path.join(workdir, "passages"), n_shards=N_SHARDS,
        use_wal=True, use_pallas=True, backend="processes",
    )
    try:
        t0 = time.perf_counter()
        for i in range(0, n_passages, BATCH_DOCS):
            eng.add_documents(docs[i:i + BATCH_DOCS])
        eng.flush()
        eng.commit()
        eng.reopen()
        rep["index_s"] = time.perf_counter() - t0
        # near-duplicate probes (a passage's own vector plus noise) and
        # free random directions
        probes = rng.choice(n_passages, 6, replace=False)
        qv = np.concatenate([
            vecs[probes] + 0.1 * rng.standard_normal((6, dim)),
            rng.standard_normal((4, dim)),
        ]).astype(np.float32)
        vq = [VectorQuery(tuple(map(float, v)), "dot") for v in qv]
        words = [_word(i) for i in (1, 7, 60, 400)]
        hq = [
            HybridQuery(TermQuery("body", w), q, a)
            for w, q, a in zip(words, vq, (0.3, 0.5, 0.7, 0.9))
        ]
        fe = SearchFrontend(eng, max_wave=64)
        try:

            def ref(t):
                if isinstance(t.query, VectorQuery):
                    return brute_force_topk(vecs, np.asarray(t.query.vector), K)
                return sharded_single(t.searcher, t.query, K)

            t0 = time.perf_counter()
            with profile.capture() as disp:
                counts = serve_and_check(fe, vq + hq, ref)
            rep["serve_s"] = time.perf_counter() - t0
            rep["counts"] = counts
            rep["dispatches"] = dict(disp)
        finally:
            fe.close()
    finally:
        eng.close()
    _log(
        f"phase B: {n_passages} passages x {dim}-d dot, indexed in "
        f"{rep['index_s']:.1f}s; parity ok {rep['counts']} in "
        f"{rep['serve_s']:.1f}s (first wave compiles); fused dispatches "
        f"{rep['dispatches']}"
    )
    return rep


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------


def _fail(msg: str) -> int:
    print(f"chip_smoke: FAILED: {msg}", file=sys.stderr, flush=True)
    return 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0, help="data seed")
    args = ap.parse_args(argv)

    if os.environ.get("REPRO_FUSED_KERNEL"):
        return _fail("REPRO_FUSED_KERNEL is set; the chip path must not be forced")
    import jax

    from repro.core.query.fused import kernel_enabled
    from repro.kernels.runtime import resolve_interpret, use_compile_cache

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        return _fail(f"JAX found no TPU (platform {dev.platform!r})")
    if not kernel_enabled(K) or resolve_interpret(None):
        return _fail("fused Pallas kernels are not compiled on this backend")
    cache_dir = use_compile_cache()  # before the first compile

    compile_s: dict = {}  # program name -> backend compile seconds
    n_compiles = [0]
    cache_hits = [0]

    def on_duration(event, secs, fun_name="?", **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compile_s[fun_name] = compile_s.get(fun_name, 0.0) + secs
            n_compiles[0] += 1

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            cache_hits[0] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    _log(
        f"chip_smoke: device {dev.platform} {dev.device_kind} x"
        f"{len(jax.devices())}; compile cache {cache_dir}; timings are a "
        f"first chip reading, not a benchmark"
    )
    workdir = tempfile.mkdtemp(prefix="chip-smoke-")
    try:
        phase_a(workdir, N_DOCS, args.seed)
        phase_b(workdir, N_PASSAGES, DIM, args.seed)
    except Exception as exc:  # noqa: BLE001 — any phase failure fails the run
        import traceback

        traceback.print_exc()
        return _fail(f"{type(exc).__name__}: {exc}")
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    stats = dev.memory_stats() or {}
    slowest = sorted(compile_s.items(), key=lambda kv: -kv[1])[:6]
    _log(
        f"compile: {sum(compile_s.values()):.1f}s backend compile over "
        f"{n_compiles[0]} programs, {cache_hits[0]} persistent-cache hits; "
        f"slowest {[(n, round(s, 1)) for n, s in slowest]}"
    )
    _log(
        f"HBM peak_bytes_in_use "
        f"{stats.get('peak_bytes_in_use', 'not reported')}"
    )
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": dev.platform,
            "kind": dev.device_kind,
            "count": len(jax.devices()),
        },
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
