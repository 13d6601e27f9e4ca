"""Required bytes, the trace reduction, the per-layer readers and the
knee sweep's verdict."""

import json
import time
from pathlib import Path

import pytest

from chipbench import manifest as M
from chipbench import readings as RD
from chipbench import trace as T
from chipbench.drive import Batch, Rec, Recording
from chipbench.roofline import vector_wave_bytes

FIXTURE = Path(__file__).parent / "data" / "tpu_trace_events.json"


def test_vector_wave_bytes_hand_count():
    # 3 rows x 2 floats x 4 bytes, plus one live byte for up to 8 rows
    assert vector_wave_bytes(3, 2) == 24 + 1
    assert vector_wave_bytes(9, 768) == 9 * 768 * 4 + 2
    # one read per wave: 250k x 768 f32 per shard, both shards
    assert vector_wave_bytes(500_000, 768) == 1_536_000_000 + 62_500


def _events():
    # chip 0: ops [1,2] and [1.5,3] overlap -> busy [1,3]; [6,7]; window
    # [0,10] -> busy 3 s, gaps [0,1] 1 s, [3,6] 3 s, [7,10] 3 s
    ops = [(1.0, 2.0, "fusion", 0), (1.5, 3.0, "fusion.1", 0),
           (6.0, 7.0, "custom", 0)]
    mods = [(1.0, 3.0, "jit__fused_vector(123)", 0),
            (6.0, 7.0, "jit__concat_merge(9)", 0)]
    return {"chips": 1, "ops": ops, "modules": mods}


def test_reduce_busy_programs_and_labelled_gaps():
    spans = [("commit", 3.2, 5.9), ("query", 2.5, 6.5), ("ingest", 8.0, 8.5)]
    red = T.reduce(_events(), 0.0, 10.0, spans)
    assert red["busy_s"] == pytest.approx(3.0)
    assert red["window_s"] == pytest.approx(10.0)
    assert red["programs"] == pytest.approx(
        {"_fused_vector": 2.0, "_concat_merge": 1.0})
    gaps = sorted((round(s, 6), lab) for lab, s in red["gaps"])
    # [0,1] nothing outstanding; [3,6] commit covers 2.7 s, queries 3 s;
    # [7,10] an ingest op covers 0.5 s
    assert gaps == [(1.0, "no request outstanding"), (3.0, "ingest op"),
                    (3.0, "wave")]
    bd = T.breakdown(red)
    assert bd["device_ops"][0] == ["_fused_vector", 2.0]
    assert len(bd["idle_gaps"]) == 3


def test_reduce_clips_to_window_and_averages_chips():
    ev = {"chips": 2,
          "ops": [(-1.0, 1.0, "a", 0), (0.0, 4.0, "b", 1)],
          "modules": [(-1.0, 1.0, "jit_f(1)", 0)]}
    red = T.reduce(ev, 0.0, 4.0)
    assert red["busy_s"] == pytest.approx((1.0 + 4.0) / 2)
    assert red["programs"] == pytest.approx({"f": 0.5})
    assert T.reduce({"chips": 1, "ops": [], "modules": []}, 0, 1) is None


def test_recorded_tpu_trace():
    """A small trace recorded on a TPU v5e: the device plane's ops and
    modules, reduced the way a traced run reduces its window."""
    rec = json.loads(FIXTURE.read_text())
    ev = T.events_from_records(rec["events"], rec["sync_ns"])
    t0, t1 = (x * 1e-9 for x in rec["sync_ns"])
    red = T.reduce(ev, t0, t1)
    assert ev["chips"] == 1
    assert 0 < red["busy_s"] < red["window_s"]
    assert set(rec["expect_programs"]) <= set(red["programs"])
    for name, s in red["programs"].items():
        assert 0 < s <= red["busy_s"] + 1e-9, name
    bd = T.breakdown(red)
    assert bd["device_ops"][0][0] in rec["expect_programs"]


def test_load_reads_a_cpu_trace(tmp_path):
    """``load`` on a real profiler file: finds the sync marks and no TPU
    plane, so a CPU trace yields no device reading."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x * 2).sum())
    x = jnp.ones((64,))
    f(x).block_until_ready()
    cap = T.Capture(str(tmp_path / "tr"))
    cap.start()
    f(x).block_until_ready()
    time.sleep(0.01)
    cap.stop()
    ev = T.load(cap.log_dir, cap.sync_ns)
    assert ev is not None and ev["chips"] == 0 and ev["ops"] == []
    assert len(cap.sync_ns) == 2


def test_readers_from_counters_and_trace():
    r = RD.Readings(
        frontend={"waves": 10, "wave_queries": 250, "queries": 250},
        dispatches={"fused.vector": 20, "fused.term": 5},
        commit_ms=[800.0, 1200.0],
        trace={"busy_s": 6.0, "window_s": 10.0,
               "programs": {"_fused_vector": 4.0}, "gaps": []},
        peaks={"hbm_bytes_per_s": 819e9},
        vector_waves=10, vector_rows=500_000, vector_dim=768,
    )
    names = [p.stem for p in (M.HERE / "metrics").glob("*.py")]
    # a split quantity's names share its reader
    names += [f"{q}.{split}" for q in ("mean_wave", "dispatches_per_query",
                                       "device_idle")
              for split in ("closed", "nrt")]
    vals = {n: M.reader(n)(r) for n in names}
    assert vals["mean_wave.closed"] == vals["mean_wave.nrt"] == 25.0
    assert vals["dispatches_per_query.nrt"] == pytest.approx(25 / 250)
    assert vals["device_idle.closed"] == pytest.approx(40.0)
    assert vals["commit_ms"] == pytest.approx(1000.0)
    need = 10 * vector_wave_bytes(500_000, 768)
    assert vals["vector_kernel_roofline"] == pytest.approx(
        100 * need / (4.0 * 819e9))
    # a reader that finds nothing to read returns nothing
    empty = RD.Readings(frontend={"waves": 0, "wave_queries": 0},
                        dispatches={}, commit_ms=[])
    for n in names:
        assert M.reader(n)(empty) is None, n


def test_every_manifest_metric_has_a_reader_and_unit():
    m = M.load_manifest()
    for e in m["per_layer"]:
        assert callable(M.reader(e["name"]))
        assert e["moves"] in {x["name"] for x in m["end_to_end"]}


def test_sweep_holds_a_growing_ack_queue_unsustained(monkeypatch):
    """Answers on time do not make a rate sustained while the acks fall
    behind: the last batch due in the window must be acked within 1 s of
    its close too."""
    monkeypatch.syspath_prepend(str(M.HERE))  # as the script runs
    import sweep

    rec = Recording(start=0.0, end=10.0)
    rec.queries = [Rec("q", i, float(i), sent=float(i), done=i + 1.5)
                   for i in range(10)]
    rec.probes = [Rec("p", 0, 3.2, sent=3.2, done=3.4)]
    rec.batches = [Batch(b, float(b), acked=b + 0.2) for b in range(10)]
    ok = {"failed_queries": 0, "failed_batches": 0}
    v = sweep.sustained(rec, ok)
    assert v["sustained"] and v["drain_after_close_s"] == pytest.approx(0.5)
    # queries 2 and 3 and the probe are outstanding together at 3.3 s
    assert v["max_outstanding"] == 3
    assert v["commit_arrivals"] == 0
    rec.batches[-1].acked = 12.0
    v = sweep.sustained(rec, ok)
    assert not v["sustained"]
    assert v["ack_drain_after_close_s"] == pytest.approx(2.0)


def test_sweep_counts_unanswered_queries_as_outstanding(monkeypatch):
    """Under overload a query that is never answered stays outstanding to
    the end of the grace period, and a shed one (done at its send) never
    counts; the queries due during the longest commit are counted."""
    monkeypatch.syspath_prepend(str(M.HERE))
    import sweep

    rec = Recording(start=0.0, end=10.0)
    rec.queries = ([Rec("q", i, float(i), sent=float(i), done=i + 0.5)
                    for i in range(10)]
                   + [Rec("q", 10 + i, 2.0 + i, sent=2.0 + i)
                      for i in range(3)]
                   + [Rec("q", 13, 6.1, sent=6.1, done=6.1)])
    rec.commits = [(1.0, 1.2, None), (4.0, 7.5, None)]
    assert sweep.max_outstanding(rec.queries, rec.end + 60.0) == 4
    assert sweep.commit_arrivals(rec) == 6  # 4, 5, 6, 7, the 4.0 and 6.1
