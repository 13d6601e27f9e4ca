"""The program's spans read by the benchmark: self time, the four per-wave
readers, and idle gaps labelled by the host work that covered them."""

import json
from pathlib import Path

import numpy as np
import pytest

from chipbench import manifest as M
from chipbench import readings as RD
from chipbench import spans as SP
from chipbench import trace as T
from chipbench.tests.tiny import DENSE, run_tiny
from repro.core.query import profile
from repro.core.query.profile import Span

FIXTURE = Path(__file__).parent / "data" / "tpu_trace_events.json"
MS = 1_000_000
NEW = ("queue_wait_ms.closed", "plan_ms_per_wave.closed",
       "fanout_ms_per_wave.closed", "host_ms_per_wave.closed")


def _span(sid, name, a, b, parent=None, trace=None, thread=1, **attrs):
    return Span(name, sid, parent, trace, int(a), int(b), thread, attrs)


def _two_waves():
    """Two waves on one thread, in ms; wave 1's shard has an
    ``exec.fetch`` [7, 35] and a ``host.gc`` [30, 38] that overlap."""
    m = MS
    return [
        _span(1, "frontend.wave", 0, 100 * m, trace=1),
        _span(2, "plan.batch", 1 * m, 3 * m, 1, 1),
        _span(3, "sharded.shard", 3 * m, 40 * m, 1, 1, shard=0),
        _span(4, "exec.stage", 4 * m, 6 * m, 3, 1),
        _span(5, "exec.dispatch", 6 * m, 7 * m, 3, 1),
        _span(6, "exec.fetch", 7 * m, 35 * m, 3, 1),
        _span(7, "host.gc", 30 * m, 38 * m, 3, 1, generation=0),
        _span(8, "sharded.merge", 40 * m, 50 * m, 1, 1),
        _span(9, "exec.dispatch", 41 * m, 42 * m, 8, 1),
        _span(10, "exec.fetch", 42 * m, 45 * m, 8, 1),
        _span(11, "frontend.deliver", 50 * m, 52 * m, 1, 1),
        _span(12, "frontend.wave", 200 * m, 260 * m, trace=2),
        _span(13, "plan.batch", 201 * m, 202 * m, 12, 2),
        _span(14, "sharded.shard", 202 * m, 240 * m, 12, 2, shard=0),
        _span(15, "exec.fetch", 205 * m, 235 * m, 14, 2),
        _span(16, "sharded.merge", 240 * m, 250 * m, 12, 2),
        # per-request waits, recorded on the dispatcher beside the waves
        _span(17, "frontend.queue", -10 * m, 0, trace=41, seqno=41, wave=1),
        _span(18, "frontend.queue", -20 * m, 0, trace=42, seqno=42, wave=1),
        _span(19, "frontend.queue", 170 * m, 200 * m, trace=43, seqno=43,
              wave=2),
    ]


def test_self_time_excludes_the_union_of_overlapping_children():
    own = SP.self_ns(_two_waves())
    # shard 1 lasts 37 ms; its children cover [4, 38]: 34 ms
    assert own[3] == 3 * MS
    assert own[8] == 10 * MS - 4 * MS
    assert own[14] == 38 * MS - 30 * MS
    assert own[6] == 28 * MS  # a leaf keeps its whole duration
    # a child that sticks out of its parent counts only inside it
    out = SP.self_ns([_span(1, "a", 0, 10), _span(2, "b", 5, 20, 1)])
    assert out[1] == 5


def test_readers_on_a_synthetic_span_set(monkeypatch):
    monkeypatch.setattr(profile, "spans", _two_waves)
    r = RD.Readings(frontend={"waves": 2, "wave_queries": 3}, dispatches={},
                    commit_ms=[], trace={"busy_s": 0.1, "window_s": 0.3,
                                         "programs": {}, "gaps": []})
    vals = {n: M.reader(n)(r) for n in NEW}
    assert vals["queue_wait_ms.closed"] == pytest.approx(20.0)
    # plan.batch 2 + 1, exec.stage 2: 5 ms over 2 waves
    assert vals["plan_ms_per_wave.closed"] == pytest.approx(2.5)
    # shard self 3 + 8, merge self 6 + 10: 27 ms over 2 waves
    assert vals["fanout_ms_per_wave.closed"] == pytest.approx(13.5)
    # wave 1: 100 - (28 + 3) fetched; wave 2: 60 - 30
    assert vals["host_ms_per_wave.closed"] == pytest.approx(49.5)
    # spans are read only against a device trace
    r.trace = None
    assert all(M.reader(n)(r) is None for n in NEW)


def test_readers_find_nothing_in_a_program_without_spans(monkeypatch):
    monkeypatch.delattr(profile, "spans")
    r = RD.Readings(frontend={"waves": 2, "wave_queries": 3}, dispatches={},
                    commit_ms=[], trace={"busy_s": 1.0, "window_s": 2.0,
                                         "programs": {}, "gaps": []})
    assert all(M.reader(n)(r) is None for n in NEW)


def test_new_metrics_are_in_the_manifest_for_the_dense_cell():
    man = M.load_manifest()
    entries = {m["name"]: m for m in man["per_layer"]}
    for n in NEW:
        e = entries[n]
        assert e["workloads"] == [DENSE] and e["moves"] == "qps"
        assert e["source"] == "program_span"


def _writer_ops():
    """A window's dispatcher writer ops, in ms: two acked adds, a commit
    (an ingest op of another kind) and two reopens."""
    m = MS
    return [
        _span(1, "frontend.ingest", 0, 4 * m, trace=7, kind="add"),
        _span(2, "frontend.ingest", 10 * m, 16 * m, trace=8, kind="add"),
        _span(3, "frontend.ingest", 20 * m, 920 * m, trace=9, kind="commit"),
        _span(4, "frontend.reopen", 930 * m, 933 * m),
        _span(5, "nrt.reopen", 930 * m, 931 * m, 4, shard=0),
        _span(6, "frontend.reopen", 940 * m, 947 * m),
        _span(7, "frontend.wave", 950 * m, 960 * m, trace=1),
    ]


@pytest.mark.parametrize("name,want", [("ingest_ms.nrt", 5.0),
                                       ("reopen_ms.nrt", 5.0)])
def test_writer_op_readers_on_synthetic_spans(monkeypatch, name, want):
    """Only acked adds count as ingest (the commit is an ingest op too,
    and 180x longer); the reopen is the front end's whole cross-shard
    one, not a shard's part of it."""
    r = RD.Readings(frontend={}, dispatches={}, commit_ms=[],
                    trace={"busy_s": 0.1, "window_s": 1.0, "programs": {},
                           "gaps": []})
    monkeypatch.setattr(profile, "spans", _writer_ops)
    assert M.reader(name)(r) == pytest.approx(want)
    # a window with no such span reads nothing
    monkeypatch.setattr(profile, "spans", lambda: [
        s for s in _writer_ops() if s.name == "frontend.wave"])
    assert M.reader(name)(r) is None
    monkeypatch.setattr(profile, "spans", list)
    assert M.reader(name)(r) is None
    # spans are read only against a device trace
    monkeypatch.setattr(profile, "spans", _writer_ops)
    r.trace = None
    assert M.reader(name)(r) is None


def _fixture():
    rec = json.loads(FIXTURE.read_text())
    ev = T.events_from_records(rec["events"], rec["sync_ns"])
    t0, t1 = (x * 1e-9 for x in rec["sync_ns"])
    return ev, t0, t1


def test_idle_gaps_match_the_trace_reduction():
    ev, t0, t1 = _fixture()
    gaps = SP.idle_gaps(ev, t0, t1)
    red = T.reduce(ev, t0, t1)
    assert float((gaps[:, 1] - gaps[:, 0]).sum()) == pytest.approx(
        red["window_s"] - red["busy_s"])
    longest = sorted(gaps[:, 1] - gaps[:, 0])[::-1][:len(red["gaps"])]
    assert longest == pytest.approx([s for _, s in red["gaps"]])


def test_gaps_labelled_by_the_program_span_that_covered_them():
    """The recorded v5e trace with synthetic program spans: a merge covers
    the longest gap; a collection in a client thread covers most of the
    second, which the dispatcher spent staging (a collection holds the
    interpreter lock, so it, not the staging, held the device idle); the
    first gap lies before the wave began."""
    ev, t0, t1 = _fixture()
    gaps = SP.idle_gaps(ev, t0, t1)
    by_len = np.argsort(gaps[:, 0] - gaps[:, 1])
    g1, g2 = gaps[by_len[0]], gaps[by_len[1]]
    first = gaps[0]
    assert first[1] < g2[0] < g1[0], "fixture gaps out of the expected order"
    ns = lambda s: int(round(s * 1e9))  # noqa: E731
    spans = [
        _span(1, "frontend.wave", ns(first[1]), ns(g1[1]), trace=1),
        _span(2, "sharded.merge", ns(g1[0]), ns(g1[1]), 1, 1),
        _span(3, "exec.stage", ns(g2[0]), ns(g2[1]), 1, 1),
        _span(4, "host.gc", ns(g2[0]),
              ns(g2[0] + 0.8 * (g2[1] - g2[0])), thread=2),
        _span(5, "frontend.queue", ns(t0), ns(t1), trace=9),
    ]
    client = [("query", t0, t1)]
    out = SP.attribute_gaps(ev, t0, t1, client, spans, top=len(gaps))
    labels = dict((round(s, 9), lab) for lab, s in out["gaps"])
    assert labels[round(g1[1] - g1[0], 9)] == "wave:sharded.merge"
    assert labels[round(g2[1] - g2[0], 9)] == "wave:host.gc"
    assert labels[round(first[1] - first[0], 9)] == "wave"
    # spans are stamped in whole nanoseconds
    assert out["by_span"]["host.gc"] == pytest.approx(
        0.8 * (g2[1] - g2[0]), abs=1e-8)
    assert out["by_span"]["exec.stage"] == pytest.approx(
        0.2 * (g2[1] - g2[0]), abs=1e-8)
    # idle time inside the wave but under no other span is not attributed
    assert out["attributed_s"] == pytest.approx(
        (g1[1] - g1[0]) + (g2[1] - g2[0]), abs=1e-8)
    assert out["idle_s"] == pytest.approx(
        float((gaps[:, 1] - gaps[:, 0]).sum()))
    assert 0 < out["attributed_pct"] < 100
    # the label format extends the trace reduction's own
    red = T.reduce(ev, t0, t1, client)
    assert {lab for lab, _ in red["gaps"]} == {"wave"}
    # no program spans: the client labels stay as they are
    bare = SP.attribute_gaps(ev, t0, t1, client, [], top=3)
    assert [lab for lab, _ in bare["gaps"]] == ["wave"] * 3
    assert bare["attributed_s"] == 0.0


def test_traced_tiny_run_records_the_window_spans():
    """A traced run on the CPU: the program records its spans under the
    profiler session, with no call from the benchmark; with no device
    trace on the CPU the result line leaves the span metrics out, and the
    readers, given a trace, read all four from the window's spans."""
    profile.enable(True)  # empties the buffer
    profile.enable(False)
    out = run_tiny(DENSE, seed=2**31 + 99, trace=True)
    assert out["correct"] is True
    assert not set(NEW) & set(out["metrics"])
    spans = profile.spans()
    names = {s.name for s in spans}
    assert {"frontend.queue", "frontend.wave", "plan.batch", "sharded.shard",
            "sharded.merge", "exec.stage", "exec.dispatch",
            "exec.fetch"} <= names
    r = RD.Readings(frontend={}, dispatches={}, commit_ms=[],
                    trace={"busy_s": 1.0, "window_s": 1.5, "programs": {},
                           "gaps": []})
    vals = {n: M.reader(n)(r) for n in NEW}
    assert all(v is not None and v > 0 for v in vals.values()), vals
    assert vals["host_ms_per_wave.closed"] >= (
        vals["plan_ms_per_wave.closed"] + vals["fanout_ms_per_wave.closed"])
