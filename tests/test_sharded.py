"""Sharded indexing + fan-out search: parity, crash atomicity, NRT isolation.

Three contracts pinned here:

  1. **Bit-parity** — a sharded index with a fixed router returns results
     *identical* to one unsharded index over the same corpus (external-id
     space; cross-shard BM25 statistics), for every query family and every
     directory kind.  ``shards=1`` is the degenerate case whose doc ids
     coincide with the unsharded positional ids outright.
  2. **Cross-shard commit atomicity** — a crash between per-shard commits
     recovers every shard to the cross-shard manifest's single point in
     time (the early committers roll back).
  3. **Per-shard NRT isolation** — reopening one shard swaps only that
     shard's searcher; the other shards' point-in-time views and
     device-resident caches are untouched.
"""

from collections import Counter

import numpy as np
import pytest

from repro.core import (
    Analyzer,
    EXT_ID_FIELD,
    HashFieldRouter,
    SearchEngine,
    ShardedEngine,
)
from repro.core.search import (
    BooleanQuery,
    FacetQuery,
    PhraseQuery,
    RangeQuery,
    SortQuery,
    TermQuery,
)
from repro.data.corpus import CorpusConfig, synthetic_corpus

N_DOCS = 240
FLUSH_EVERY = 60
KINDS = ["ram", "fs-ssd", "byte-pmem"]


@pytest.fixture(scope="module")
def corpus():
    return list(synthetic_corpus(CorpusConfig(n_docs=N_DOCS, vocab=400, seed=7)))


def common_tokens(corpus, n):
    c = Counter()
    an = Analyzer()
    for fields, _ in corpus:
        c.update(set(an.tokenize(fields["body"])))
    return [t for t, _ in c.most_common(n)]


def all_family_batch(corpus):
    toks = common_tokens(corpus, 6)
    an = Analyzer()
    bigram = tuple(an.tokenize(corpus[0][0]["body"])[:2])
    return [
        TermQuery("body", toks[0]),
        TermQuery("body", toks[4]),
        BooleanQuery((TermQuery("body", toks[0]), TermQuery("body", toks[1])), "and"),
        BooleanQuery((TermQuery("body", toks[2]), TermQuery("body", toks[3])), "or"),
        PhraseQuery("body", bigram),
        RangeQuery("month", 3, 7),
        SortQuery(TermQuery("body", toks[0]), "timestamp"),
        FacetQuery(None, "month", 12),
        FacetQuery(TermQuery("body", toks[1]), "month", 12),
    ]


def build_unsharded(kind, path, corpus):
    """Reference engine; the external-id column is injected so results can
    be compared in external-id space (what the sharded engine reports)."""
    eng = SearchEngine(kind, path=str(path) if path else None)
    for i, (fields, dv) in enumerate(corpus):
        eng.add(fields, {**dv, EXT_ID_FIELD: i})
        if (i + 1) % FLUSH_EVERY == 0:
            eng.flush()
    eng.commit()
    eng.reopen()
    return eng


def build_sharded(kind, path, corpus, n_shards, router=None, backend=None,
                  use_wal=False):
    eng = ShardedEngine(
        kind, path=str(path) if path else None, n_shards=n_shards,
        router=router, backend=backend, use_wal=use_wal,
    )
    for j in range(0, len(corpus), FLUSH_EVERY):
        eng.add_documents(corpus[j : j + FLUSH_EVERY])
        eng.flush()
    eng.commit()
    eng.reopen()
    return eng


def ext_map(eng: SearchEngine) -> np.ndarray:
    return np.concatenate(
        [np.asarray(s.doc_values[EXT_ID_FIELD]) for s in eng.manager.infos.segments]
    )


def assert_results_identical(queries, ref, ref_ext, sharded_results):
    for q, ta, tb in zip(queries, ref, sharded_results):
        ctx = repr(q)
        assert ta.total_hits == tb.total_hits, ctx
        ids_a = ta.doc_ids if isinstance(q, FacetQuery) else ref_ext[ta.doc_ids]
        np.testing.assert_array_equal(ids_a, tb.doc_ids, err_msg=ctx)
        np.testing.assert_array_equal(ta.scores, tb.scores, err_msg=ctx)
        if isinstance(q, FacetQuery):
            np.testing.assert_array_equal(ta.facets, tb.facets, err_msg=ctx)


# ---------------------------------------------------------------------------
# 1. bit-parity: sharded == unsharded, all families x all kinds
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ["serial", "threads", "processes"])
@pytest.mark.parametrize("kind", KINDS)
def test_sharded_parity_all_families(kind, backend, tmp_path, corpus):
    ref = build_unsharded(kind, tmp_path / "ref" if kind != "ram" else None, corpus)
    sh = build_sharded(
        kind, tmp_path / "sh" if kind != "ram" else None, corpus, 3,
        backend=backend,
    )
    try:
        queries = all_family_batch(corpus)
        a = ref.search_batch(queries, k=10)
        b = sh.search_batch(queries, k=10)
        assert_results_identical(queries, a, ext_map(ref), b)
        # single-query path rides the same fan-out
        td = sh.search(queries[0], k=10)
        np.testing.assert_array_equal(td.doc_ids, b[0].doc_ids)
    finally:
        sh.close()


def test_sharded_parity_survives_merges(corpus):
    """Aggressive tiered merging (merge_factor=2 cascades on every commit)
    must not disturb parity: the external-id mapping depends on base_doc
    contiguity and doc-values row order surviving the merge remap, which is
    exactly what this pins.  Bitmap-only deletes ride along afterwards
    (no rewrite: df and merge timing stay identical on both sides)."""
    ref = SearchEngine("ram")
    ref.writer.merge_factor = 2
    sh = ShardedEngine("ram", n_shards=3)
    for w in sh.writer.writers:
        w.merge_factor = 2
    try:
        for j in range(0, len(corpus), 30):  # many small flushes -> cascades
            for i, (fields, dv) in enumerate(corpus[j : j + 30], start=j):
                ref.add(fields, {**dv, EXT_ID_FIELD: i})
            sh.add_documents(corpus[j : j + 30])
            ref.commit()
            sh.commit()
        ref.reopen()
        sh.reopen()
        assert all(len(w.infos) < 4 for w in sh.writer.writers)  # merges ran
        queries = all_family_batch(corpus)
        assert_results_identical(
            queries, ref.search_batch(queries, k=10), ext_map(ref),
            sh.search_batch(queries, k=10),
        )
        # deletes after the merging settled: bitmap clones only (no flush,
        # no rewrite), applied to merged segments on both sides
        tok = common_tokens(corpus, 2)[1]
        assert ref.delete("body", tok) == sh.delete("body", tok)
        ref.reopen()
        sh.reopen()
        assert_results_identical(
            queries, ref.search_batch(queries, k=10), ext_map(ref),
            sh.search_batch(queries, k=10),
        )
    finally:
        sh.close()


def test_shards1_degenerate_case_identical_doc_ids(corpus):
    """One shard, identity routing: even the *positional* doc ids coincide
    with the unsharded engine (external id == global id)."""
    ref = build_unsharded("ram", None, corpus)
    sh = build_sharded("ram", None, corpus, 1)
    try:
        for q in all_family_batch(corpus):
            a = ref.search(q, k=10)
            b = sh.search(q, k=10)
            assert a.total_hits == b.total_hits, q
            np.testing.assert_array_equal(a.doc_ids, b.doc_ids, err_msg=repr(q))
            np.testing.assert_array_equal(a.scores, b.scores, err_msg=repr(q))
    finally:
        sh.close()


def test_field_router_parity_and_colocation(corpus):
    """A field router changes placement, not results; all docs sharing the
    routing key land on one shard."""
    ref = build_unsharded("ram", None, corpus)
    router = HashFieldRouter(3, "title")
    sh = build_sharded("ram", None, corpus, 3, router=router)
    try:
        queries = all_family_batch(corpus)
        assert_results_identical(
            queries, ref.search_batch(queries, k=10), ext_map(ref),
            sh.search_batch(queries, k=10),
        )
        # colocation: every document's shard is the router's verdict
        for sid, s in enumerate(sh.manager.searcher.searchers):
            for ext in s.ext_ids:
                fields, dv = corpus[int(ext)]
                assert router.route(fields, dv, int(ext)) == sid
    finally:
        sh.close()


def test_sharded_delete_fans_out(corpus):
    """delete_by_term kills matching docs on every shard; parity with the
    unsharded engine holds when merges don't drop docs underneath."""
    ref = build_unsharded("ram", None, corpus)
    sh = build_sharded("ram", None, corpus, 3)
    try:
        tok = common_tokens(corpus, 1)[0]
        n_ref = ref.delete("body", tok)
        n_sh = sh.delete("body", tok)
        assert n_ref == n_sh
        ref.reopen()
        sh.reopen()
        assert sh.search(TermQuery("body", tok), k=10).total_hits == 0
        other = common_tokens(corpus, 5)[-1]
        q = TermQuery("body", other)
        a, b = ref.search(q, k=10), sh.search(q, k=10)
        assert a.total_hits == b.total_hits
        np.testing.assert_array_equal(ext_map(ref)[a.doc_ids], b.doc_ids)
        np.testing.assert_array_equal(a.scores, b.scores)
    finally:
        sh.close()


# ---------------------------------------------------------------------------
# 2. cross-shard commit atomicity
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kind", ["fs-ssd", "byte-pmem"])
def test_crash_between_shard_commits_recovers_one_point_in_time(
    kind, tmp_path, corpus
):
    eng = ShardedEngine(kind, path=str(tmp_path / "idx"), n_shards=3)
    eng.add_documents(corpus[:120])
    eng.commit()
    eng.reopen()
    q = TermQuery("body", common_tokens(corpus, 1)[0])
    before = eng.search(q, k=20)

    # second wave reaches only shard 0 before the power fails: shard 0 is
    # durable one generation ahead, shards 1-2 and the manifest are not
    eng.add_documents(corpus[120:])
    eng.flush()
    eng.writer.writers[0].commit({"epoch": 99}, gc=False)
    rec = eng.crash_and_recover()
    try:
        assert rec.writer.next_ext == 120
        assert sum(w.infos.total_docs for w in rec.writer.writers) == 120
        # per-shard latest commits all match the manifest's generations
        manifest = rec.shards.read_manifest()
        for d, gen in zip(rec.shards.dirs, manifest["gens"]):
            assert d.latest_commit()[0] == gen
        rec.reopen()
        after = rec.search(q, k=20)
        assert after.total_hits == before.total_hits
        np.testing.assert_array_equal(after.doc_ids, before.doc_ids)
        np.testing.assert_array_equal(after.scores, before.scores)
        # external ids continue from the recovered watermark
        assert rec.add(*corpus[120]) == 120
    finally:
        rec.close()


@pytest.mark.parametrize("kind", ["fs-ssd", "byte-pmem"])
def test_torn_wave_deletes_do_not_leak_into_rollback(kind, tmp_path, corpus):
    """A delete durably committed by ONE shard ahead of the manifest must
    roll back with the wave: the recovered point in time predates it.
    (On the file path this means pruning the wave's fsynced .liv
    generations, not just its segments_N manifest.)"""
    eng = ShardedEngine(kind, path=str(tmp_path / "idx"), n_shards=2)
    eng.add_documents(corpus[:120])
    eng.commit()
    eng.reopen()
    # a LOW-df token: the delete must stay under the deletes-pct rewrite
    # threshold so the segments survive and only .liv generations change
    an = Analyzer()
    counts = Counter()
    for fields, _ in corpus[:120]:
        counts.update(set(an.tokenize(fields["body"])))
    tok = next(t for t, c in counts.most_common() if c <= 4)
    q = TermQuery("body", tok)
    before = eng.search(q, k=20)
    assert before.total_hits > 0

    # the torn wave: a delete lands (below the rewrite threshold, so the
    # segments stay and only new .liv generations are written), shard 0
    # commits it durably, then power fails before the manifest
    eng.delete("body", tok)
    eng.writer.writers[0].commit({}, gc=False)
    rec = eng.crash_and_recover()
    try:
        rec.reopen()
        after = rec.search(q, k=20)
        assert after.total_hits == before.total_hits
        np.testing.assert_array_equal(after.doc_ids, before.doc_ids)
        np.testing.assert_array_equal(after.scores, before.scores)
    finally:
        rec.close()


@pytest.mark.parametrize("kind", ["fs-ssd", "byte-pmem"])
def test_crash_after_manifest_recovers_new_wave(kind, tmp_path, corpus):
    """Once the manifest is durable the whole wave survives, even if the
    crash preempts the deferred GC."""
    eng = ShardedEngine(kind, path=str(tmp_path / "idx"), n_shards=2)
    eng.add_documents(corpus[:80])
    eng.commit()
    eng.add_documents(corpus[80:160])
    eng.commit()  # wave 2 fully durable (manifest written, gc ran)
    rec = eng.crash_and_recover()
    try:
        assert rec.writer.next_ext == 160
        assert sum(w.infos.total_docs for w in rec.writer.writers) == 160
    finally:
        rec.close()


def test_crash_before_first_manifest_recovers_empty(tmp_path, corpus):
    """A torn FIRST wave (some shards committed, no manifest yet) recovers
    to the empty index, not to half a commit."""
    eng = ShardedEngine("fs-ssd", path=str(tmp_path / "idx"), n_shards=2)
    eng.add_documents(corpus[:40])
    eng.flush()
    eng.writer.writers[0].commit({}, gc=False)  # crash before the manifest
    rec = eng.crash_and_recover()
    try:
        assert rec.writer.next_ext == 0
        assert sum(w.infos.total_docs for w in rec.writer.writers) == 0
    finally:
        rec.close()


def test_torn_wave_rollback_without_crash_restores_live_bitmaps(corpus):
    """Recovery over a still-live ShardSet (no power loss — e.g. the
    coordinator died mid-wave): a delete one shard committed ahead of the
    manifest rolls back on EVERY kind, including ram, where the bitmaps
    live in process memory rather than .liv files."""
    from repro.core import ShardedWriter

    eng = build_sharded("ram", None, corpus[:120], 2)
    tok = common_tokens(corpus[:120], 1)[0]
    alive = eng.search(TermQuery("body", tok), k=20).total_hits
    assert alive > 0
    eng.delete("body", tok)
    eng.writer.writers[0].commit({}, gc=False)  # wave torn after shard 0
    eng.close()

    w2 = ShardedWriter(eng.shards)  # reopen WITHOUT crash
    from repro.core import ShardedSearcherManager

    mgr = ShardedSearcherManager(w2)
    td = mgr.searcher.search(TermQuery("body", tok), k=20)
    assert td.total_hits == alive  # the never-manifested delete rolled back
    w2.close()


def test_ram_crash_loses_everything_consistently(corpus):
    eng = build_sharded("ram", None, corpus, 3)
    rec = eng.crash_and_recover()
    try:
        assert rec.writer.next_ext == 0
        assert sum(w.infos.total_docs for w in rec.writer.writers) == 0
    finally:
        rec.close()


# ---------------------------------------------------------------------------
# 3. per-shard NRT reopen isolation
# ---------------------------------------------------------------------------


def test_per_shard_reopen_leaves_other_searchers_untouched(corpus):
    # field router: documents sharing a title co-locate, so new docs can be
    # steered at ONE shard through the public API
    router = HashFieldRouter(3, "title")
    sh = build_sharded("ram", None, corpus, 3, router=router)
    try:
        searchers = [m.searcher for m in sh.manager.managers]
        uploads = [c.stats.segment_uploads for c in sh.device_caches]

        target, fresh = None, []
        for fields, dv in corpus[:9]:
            sid = router.route(fields, dv, 0)
            if target is None:
                target = sid
            if sid == target:
                fresh.append((fields, dv))
        sh.add_documents(fresh)
        assert sh.writer.writers[target].buffered_docs == len(fresh) > 0

        sh.reopen(shard=target)
        now = [m.searcher for m in sh.manager.managers]
        for sid in range(3):
            if sid == target:
                assert now[sid] is not searchers[sid]
            else:
                assert now[sid] is searchers[sid]  # untouched point in time
                assert (
                    sh.device_caches[sid].stats.segment_uploads == uploads[sid]
                )
    finally:
        sh.close()


def test_retained_fanout_searcher_is_point_in_time(corpus):
    """A handed-out ShardedSearcher keeps bit-identical results while the
    writer ingests and shards reopen underneath it (the Searcher contract,
    lifted to the fan-out view: stats bindings are per-snapshot, never
    mutated in place)."""
    sh = build_sharded("ram", None, corpus[:180], 3)
    try:
        old = sh.searcher
        queries = all_family_batch(corpus[:180])
        before = old.search_batch(queries, k=10)
        # grow and refresh the index: new docs, per-shard + full reopens
        sh.add_documents(corpus[180:])
        sh.reopen(shard=0)
        sh.reopen()
        new = sh.searcher.search_batch(queries, k=10)
        after = old.search_batch(queries, k=10)  # the OLD view, re-asked
        for q, ta, tb in zip(queries, before, after):
            assert ta.total_hits == tb.total_hits, q
            np.testing.assert_array_equal(ta.doc_ids, tb.doc_ids, err_msg=repr(q))
            np.testing.assert_array_equal(ta.scores, tb.scores, err_msg=repr(q))
        # and the refreshed view actually moved (sanity: not vacuous)
        assert any(
            a.total_hits != b.total_hits for a, b in zip(before, new)
        )
    finally:
        sh.close()


def test_sharded_stats_aggregate(corpus):
    sh = build_sharded("ram", None, corpus, 3)
    try:
        st = sh.stats()
        assert st["shards"] == 3
        assert st["docs"] == N_DOCS
        assert len(st["per_shard"]) == 3
        assert st["segments"] == sum(s["segments"] for s in st["per_shard"])
        assert len(st["busy_s"]) == 3 and all(b > 0 for b in st["busy_s"])
    finally:
        sh.close()


# ---------------------------------------------------------------------------
# 4. processes backend: worker-crash fault injection (SIGKILL)
# ---------------------------------------------------------------------------
#
# A shard worker is SIGKILLed at the two dangerous points of the ingest
# lifecycle: mid-``add_documents`` (before any buffer/WAL mutation) and
# between phase 1 and phase 2 of the cross-shard commit (its shard durably
# one generation ahead of the manifest).  Recovery — a fresh coordinator
# over the same durable bytes — must roll every shard back to the
# manifest's single point in time, un-retire the WAL spans the torn wave
# retired, and replay the acked prefix bit-identically.


def _drive_acked(eng, corpus):
    """60-doc acked batches: two waves committed, two acked past the
    manifest (the WAL-held tail recovery must replay)."""
    eng.add_documents(corpus[:60])
    eng.add_documents(corpus[60:120])
    eng.commit()  # manifest at 120 docs
    eng.add_documents(corpus[120:180])
    eng.add_documents(corpus[180:240])  # acked, never committed
    return eng


def _assert_bit_identical(corpus, ref, rec):
    """Flush+reopen both sides, then compare every query family."""
    ref.reopen()
    rec.reopen()
    queries = all_family_batch(corpus)
    a = ref.search_batch(queries, k=20)
    b = rec.search_batch(queries, k=20)
    for q, ta, tb in zip(queries, a, b):
        assert ta.total_hits == tb.total_hits, repr(q)
        np.testing.assert_array_equal(ta.doc_ids, tb.doc_ids, err_msg=repr(q))
        np.testing.assert_array_equal(ta.scores, tb.scores, err_msg=repr(q))


@pytest.mark.parametrize("backend", ["processes"])
@pytest.mark.parametrize("kind", ["byte-pmem"])
def test_worker_sigkill_mid_add_recovers_acked_prefix(
    kind, backend, tmp_path, corpus
):
    """SIGKILL one shard's worker at the moment an add arrives (before any
    mutation): the un-acked batch is lost — everything acked before it
    replays bit-identically.  The batch is a single document routed AT the
    killed shard, so no sibling shard holds a durably-logged slice of it
    (per-shard WALs ack independently; a multi-shard batch would leave the
    survivor's half durable)."""
    eng = _drive_acked(
        ShardedEngine(kind, str(tmp_path / "s"), n_shards=2,
                      backend=backend, use_wal=True),
        corpus,
    )
    eng.writer.inject_fault(0, "kill_before_add")
    # next ext id is 240 -> HashIdRouter sends it to shard 240 % 2 == 0
    with pytest.raises(RuntimeError, match="worker died"):
        eng.add(*corpus[0])
    eng.close()  # teardown with a dead worker must reap the survivor too

    rec = ShardedEngine(kind, str(tmp_path / "s"), n_shards=2,
                        backend=backend, use_wal=True)
    ref = _drive_acked(
        ShardedEngine(kind, str(tmp_path / "r"), n_shards=2,
                      backend=backend, use_wal=True),
        corpus,
    )
    try:
        assert rec.writer.next_ext == N_DOCS  # the killed doc never acked
        _assert_bit_identical(corpus, ref, rec)
    finally:
        rec.close()
        ref.close()


@pytest.mark.parametrize("backend", ["processes"])
@pytest.mark.parametrize("kind", ["byte-pmem"])
def test_worker_sigkill_between_commit_phases_rolls_back_wave(
    kind, backend, tmp_path, corpus
):
    """SIGKILL one worker after its phase-1 commit is durable but before it
    reports: the coordinator never writes the manifest, so the whole wave
    is torn.  Recovery rolls EVERY shard back to the previous manifest
    (shards that committed are one generation ahead), un-retires the WAL
    spans that commit retired, and replays the acked tail — bit-identical
    to a reference that never attempted the torn commit."""
    eng = _drive_acked(
        ShardedEngine(kind, str(tmp_path / "s"), n_shards=2,
                      backend=backend, use_wal=True),
        corpus,
    )
    eng.writer.inject_fault(0, "kill_after_commit")
    with pytest.raises(RuntimeError, match="worker died"):
        eng.commit()  # phase 1 runs on both shards; the manifest never lands
    eng.close()

    rec = ShardedEngine(kind, str(tmp_path / "s"), n_shards=2,
                        backend=backend, use_wal=True)
    ref = _drive_acked(
        ShardedEngine(kind, str(tmp_path / "r"), n_shards=2,
                      backend=backend, use_wal=True),
        corpus,
    )
    try:
        assert rec.writer.epoch == 0  # the torn epoch-1 wave was rolled back
        assert rec.writer.next_ext == N_DOCS  # acked tail replayed
        _assert_bit_identical(corpus, ref, rec)
    finally:
        rec.close()
        ref.close()


@pytest.mark.parametrize("backend", ["processes"])
def test_process_workers_are_host_only(backend, monkeypatch):
    """One process holds the chip: shard workers run with JAX_PLATFORMS=cpu
    even when the coordinator's own environment does not set it."""
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    eng = ShardedEngine("ram", n_shards=2, backend=backend)
    try:
        per_shard = eng.writer.stats()["per_shard"]
        assert [s["jax_platforms"] for s in per_shard] == ["cpu", "cpu"]
    finally:
        eng.close()
