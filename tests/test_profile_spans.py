"""Host spans in ``repro.core.query.profile``: off by default and free
when off, nested and traced when on, bounded, and recorded at the served
path's layer boundaries (front end, NRT reopen, planner, fan-out,
executors, Python collections)."""

import collections
import gc
import threading

import pytest

from repro.core.query import profile
from repro.core.query.types import TermQuery, VectorQuery


@pytest.fixture
def spans_on():
    profile.enable(True)
    try:
        yield
    finally:
        profile.enable(False)


def _names(spans):
    return collections.Counter(s.name for s in spans)


def test_spans_off_share_one_noop_and_keep_nothing():
    profile.enable(False)
    before = len(profile.spans())
    a, b = profile.span("x"), profile.span("y", trace=3, k=1)
    assert a is b is profile._NOOP
    with a:
        with b:
            profile.add("z", 1, 2)
    assert len(profile.spans()) == before
    assert not profile.recording()
    assert profile._on_gc not in gc.callbacks
    assert not hasattr(profile, "reset")


def test_nesting_gives_parents_and_trace_ids(spans_on):
    with profile.span("frontend.wave", trace=7, wave=7):
        with profile.span("plan.batch"):
            pass
        with profile.span("sharded.shard", shard=1):
            with profile.span("exec.fetch"):
                pass
    with profile.span("frontend.ingest", kind="add"):
        pass
    by = {s.name: s for s in profile.spans()}
    wave = by["frontend.wave"]
    assert wave.parent_id is None and wave.trace_id == 7
    assert by["plan.batch"].parent_id == wave.span_id
    assert by["sharded.shard"].parent_id == wave.span_id
    assert by["exec.fetch"].parent_id == by["sharded.shard"].span_id
    for n in ("plan.batch", "sharded.shard", "exec.fetch"):
        assert by[n].trace_id == 7
        assert wave.start_ns <= by[n].start_ns <= by[n].end_ns <= wave.end_ns
    assert by["sharded.shard"].attrs == {"shard": 1}
    assert by["frontend.ingest"].trace_id is None
    ids = {s.span_id for s in profile.spans() if s.name != "host.gc"}
    assert len(ids) == 5


def test_trace_id_is_shared_across_threads_by_wave(spans_on):
    """A wave's trace id crosses threads by value; each thread nests its
    own spans."""
    done = threading.Event()

    def worker():
        with profile.span("sharded.shard", trace=11, shard=0):
            pass
        done.set()

    with profile.span("frontend.wave", trace=11):
        t = threading.Thread(target=worker)
        t.start()
        t.join(10)
    assert done.is_set() and not t.is_alive()
    by = {s.name: s for s in profile.spans()}
    assert by["sharded.shard"].trace_id == by["frontend.wave"].trace_id == 11
    assert by["sharded.shard"].thread != by["frontend.wave"].thread
    assert by["sharded.shard"].parent_id is None  # stacks are per thread


def test_gc_collections_are_spans(spans_on):
    assert profile._on_gc in gc.callbacks
    with profile.span("sharded.merge"):
        gc.collect()
    merge = next(s for s in profile.spans() if s.name == "sharded.merge")
    gcs = [s for s in profile.spans() if s.name == "host.gc"]
    assert gcs, "gc.collect() left no host.gc span"
    g = gcs[-1]
    assert g.attrs == {"generation": 2}
    assert g.parent_id == merge.span_id
    assert merge.start_ns <= g.start_ns <= g.end_ns <= merge.end_ns
    profile.enable(False)
    assert profile._on_gc not in gc.callbacks


def test_buffer_bound_counts_dropped(monkeypatch, spans_on):
    monkeypatch.setattr(profile, "MAX_SPANS", 8)
    monkeypatch.setattr(profile, "_buf", collections.deque(maxlen=8))
    gc.disable()  # no host.gc record among the counted ones
    try:
        for i in range(20):
            with profile.span(f"s{i}"):
                pass
    finally:
        gc.enable()
    kept = profile.spans()
    assert len(kept) == 8 and profile.dropped() == 12
    assert [s.name for s in kept] == [f"s{i}" for i in range(12, 20)]
    profile.enable(True)  # a fresh recording starts empty
    assert profile.spans() == [] and profile.dropped() == 0


def test_profiler_session_records_spans(tmp_path):
    """Spans are recorded while a JAX profiler session traces the host,
    with no call to ``enable``."""
    import jax

    profile.enable(False)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        assert profile.recording()
        with profile.span("marker.in_session"):
            pass
    finally:
        jax.profiler.stop_trace()
    assert not profile.recording()
    with profile.span("marker.after_session"):
        pass
    names = _names(profile.spans())
    assert names["marker.in_session"] == 1
    assert names["marker.after_session"] == 0


def _engine(n_docs=120):
    from repro.core.sharded import ShardedEngine

    eng = ShardedEngine("ram", n_shards=2, use_pallas=True)
    for i in range(n_docs):
        eng.add({"body": f"w{i % 5} w{i % 3} common"})
    eng.flush()
    eng.commit()
    eng.reopen()
    return eng


def test_reopen_times_gone_and_reopen_returns_latency(spans_on):
    eng = _engine()
    try:
        mgr = eng.manager
        assert not hasattr(mgr, "reopen_times")
        assert all(not hasattr(m, "reopen_times") for m in mgr.managers)
        profile.enable(True)
        eng.add({"body": "w1 fresh"})
        dt = eng.reopen()
        assert isinstance(dt, float) and dt >= 0.0
        reopens = [s for s in profile.spans() if s.name == "nrt.reopen"]
        assert sorted(s.attrs["shard"] for s in reopens) == [0, 1]
        assert dt * 1e9 >= max(s.end_ns - s.start_ns for s in reopens) * 0.5
    finally:
        eng.close()


def test_served_path_records_its_layer_spans(spans_on):
    """Queries from several client threads through ``SearchFrontend``:
    every wave's layer spans share its trace id, and each query's wait
    names the wave that picked it up."""
    from repro.serve.search_frontend import SearchFrontend

    eng = _engine()
    fe = SearchFrontend(eng, max_wave=4)
    try:
        profile.enable(True)
        tickets, lock = [], threading.Lock()

        def client(c):
            for i in range(6):
                t = fe.submit(TermQuery("body", f"w{(c + i) % 5}"))
                with lock:
                    tickets.append(t)
                t.result(60)

        threads = [threading.Thread(target=client, args=(c,))
                   for c in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        assert not any(t.is_alive() for t in threads)
        fe.reopen()
    finally:
        fe.close()
        eng.close()
    spans = profile.spans()
    names = _names(spans)
    for n in ("frontend.queue", "frontend.wave", "frontend.deliver",
              "frontend.ingest", "frontend.reopen", "nrt.reopen",
              "plan.batch", "sharded.shard", "sharded.merge", "exec.stage",
              "exec.dispatch", "exec.fetch"):
        assert names[n] > 0, n
    assert names["frontend.queue"] == len(tickets) == 18
    waves = {s.trace_id: s for s in spans if s.name == "frontend.wave"}
    assert len(waves) == names["frontend.wave"]
    by_id = {s.span_id: s for s in spans}
    for s in spans:
        if s.name in ("plan.batch", "sharded.shard", "sharded.merge",
                      "frontend.deliver"):
            assert s.trace_id in waves, s
            top = s
            while top.parent_id is not None:
                top = by_id[top.parent_id]
            assert top.name == "frontend.wave" and top.trace_id == s.trace_id
    for s in spans:
        if s.name == "frontend.queue":
            w = waves[s.attrs["wave"]]
            assert s.trace_id == s.attrs["seqno"]
            assert s.end_ns <= w.start_ns
    ingest = [s for s in spans if s.name == "frontend.ingest"]
    assert [s.attrs["kind"] for s in ingest] == ["reopen"]
    shards = [s for s in spans if s.name == "sharded.shard"]
    assert {s.attrs["shard"] for s in shards} == {0, 1}
    assert {s.attrs["family"] for s in shards} == {"term"}


def test_spans_off_on_the_served_path():
    """With spans off the served path keeps nothing and stamps no query."""
    from repro.serve.search_frontend import SearchFrontend

    profile.enable(False)
    before = len(profile.spans())
    eng = _engine()
    fe = SearchFrontend(eng, max_wave=4)
    try:
        ts = [fe.submit(TermQuery("body", "w1")) for _ in range(5)]
        for t in ts:
            t.result(60)
        assert all(t.t_submit_ns == 0 for t in ts)
    finally:
        fe.close()
        eng.close()
    assert len(profile.spans()) == before


def test_vector_and_phrase_executors_record_spans(spans_on):
    import numpy as np

    from repro.core.query.types import PhraseQuery
    from repro.core.sharded import ShardedEngine
    from repro.core.writer import VECTOR_FIELD

    rng = np.random.default_rng(5)
    eng = ShardedEngine("ram", n_shards=2, use_pallas=True)
    try:
        eng.add_documents([
            ({"body": f"alpha beta w{i % 4}"},
             {VECTOR_FIELD: rng.standard_normal(8).astype(np.float32)})
            for i in range(64)
        ])
        eng.flush()
        eng.commit()
        eng.reopen()
        profile.enable(True)
        q = VectorQuery(tuple(map(float, rng.standard_normal(8))), "dot")
        eng.search_batch([q, PhraseQuery("body", ("alpha", "beta"))], k=5)
    finally:
        eng.close()
    spans = profile.spans()
    names = _names(spans)
    assert names["exec.phrase"] == 2  # one per shard
    fused = [s for s in spans if s.name == "exec.dispatch"
             and s.attrs.get("program") == "_fused_vector"]
    assert len(fused) >= 2
    assert names["exec.stage"] >= 2 and names["exec.fetch"] >= 2
