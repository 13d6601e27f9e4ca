"""Planted faults: a run over a system broken underneath its entry points
must come out ``correct: false``.  Each fault is planted after set-up and
warm-up, right before the window, the way a faulty change would show in
the timed path."""

import numpy as np
import pytest

from repro.core.query.types import TopDocs
from repro.core.sharded import ShardedSearcher, ShardedSearcherManager

from chipbench.tests.tiny import DENSE, NRT, run_tiny, tiny_cell


def _alter(td: TopDocs) -> TopDocs:
    if td.facets is not None:
        f = np.array(td.facets, dtype=np.float64)
        f[0] += 1
        return TopDocs(td.total_hits, td.doc_ids, td.scores, facets=f)
    return TopDocs(td.total_hits, td.doc_ids,
                   np.asarray(td.scores, np.float32) * np.float32(1.001))


def answer_altered(monkeypatch):
    """Every answer's scores (or facet counts) altered where produced."""
    orig = ShardedSearcher.search_batch

    def altered(self, queries, k=10):
        return [_alter(td) for td in orig(self, queries, k)]

    monkeypatch.setattr(ShardedSearcher, "search_batch", altered)


def last_id_altered(monkeypatch):
    """The last id of every answer replaced by a doc it did not hold, its
    score left as it was."""
    orig = ShardedSearcher.search_batch

    def altered(self, queries, k=10):
        out = []
        for td in orig(self, queries, k):
            ids = np.array(td.doc_ids, np.int64)
            if len(ids):
                ids[-1] = min(set(range(len(ids) + 1)) - set(ids.tolist()))
            out.append(TopDocs(td.total_hits, ids, td.scores,
                               facets=td.facets))
        return out

    monkeypatch.setattr(ShardedSearcher, "search_batch", altered)


def exchange_left_out(monkeypatch):
    """The cross-shard fan-out left out: every wave answers from shard 0."""
    orig = ShardedSearcher.search_batch

    def first_shard(self, queries, k=10):
        return orig(ShardedSearcher(self.searchers[:1], token=self.token),
                    queries, k)

    monkeypatch.setattr(ShardedSearcher, "search_batch", first_shard)


def half_batch_left_out(monkeypatch):
    """The second half of every wave answered with the first half's rows."""
    orig = ShardedSearcher.search_batch

    def half(self, queries, k=10):
        n = len(queries)
        first = orig(self, list(queries[: (n + 1) // 2]), k)
        return first + first[: n - len(first)]

    monkeypatch.setattr(ShardedSearcher, "search_batch", half)


def state_unchanged(monkeypatch):
    """NRT reopen returns without moving the snapshot: acked writes never
    become visible."""
    monkeypatch.setattr(ShardedSearcherManager, "maybe_reopen",
                        lambda self, shard=None, force_flush=False: 0.0)


#: the busy cell can outrun a loaded CPU: late answers are waited for
BUSY_GRACE_S = 20.0


def busy_nrt_cell() -> dict:
    """The tiny NRT cell with waves that hold several queries (150 q/s,
    waves of up to 16), as the cell's own waves do on the chip, and every
    answer checked: the tiny cell's 12 q/s leaves nearly every wave one
    query long, where a fault inside a wave cannot show."""
    cell = tiny_cell(NRT)
    cell["traffic"]["rate_qps"] = 150
    cell["traffic"]["frontend"]["max_wave"] = 16
    cell["traffic"]["check_sample"] = 10_000
    return cell


@pytest.mark.parametrize("name,fault", [
    (NRT, answer_altered),
    (NRT, last_id_altered),
    (NRT, exchange_left_out),
    (NRT, state_unchanged),
    (NRT, half_batch_left_out),
    (DENSE, answer_altered),
    (DENSE, last_id_altered),
    (DENSE, exchange_left_out),
    (DENSE, half_batch_left_out),
])
def test_planted_fault_fails_the_run(name, fault, monkeypatch):
    busy = name == NRT and fault is half_batch_left_out
    out = run_tiny(name, seed=2**31 + 29,
                   grace_s=BUSY_GRACE_S if busy else 2.0,
                   cell=busy_nrt_cell() if busy else None,
                   fault=lambda eng, fe: fault(monkeypatch))
    assert out["correct"] is False, out["checks"]
    broken = [k for k, (v, lim) in out["checks"].items() if v > lim]
    assert broken, out["checks"]


def test_busy_nrt_cell_is_sound_without_a_fault():
    """The busier tiny NRT cell that the half-wave fault runs in is
    correct when nothing is broken, so that fault's failure is its own."""
    out = run_tiny(NRT, seed=2**31 + 29, grace_s=BUSY_GRACE_S,
                   cell=busy_nrt_cell())
    assert out["correct"] is True, out["checks"]
