"""The traffic generator and the document generator: seeded, and the
shares the mixes state."""

import collections

import numpy as np
import pytest

from chipbench import corpus as C
from chipbench import manifest as M
from chipbench import traffic as TR
from chipbench.tests.tiny import DENSE, NRT, cell_files

#: Solr's shipped ``autoCommit`` ``maxTime`` (15,000 ms): the NRT mix's cadence
AUTO_COMMIT_S = 15.0


@pytest.fixture(scope="module")
def nrt():
    return cell_files(NRT)


def test_tasks_fixed_by_the_mix_and_inside_their_bands(nrt):
    mix, cfg = nrt["traffic"], nrt["config"]
    a = TR.build_tasks(mix, cfg["corpus"])
    assert a == TR.build_tasks(mix, cfg["corpus"])
    assert set(a) == set(mix["queries"]["categories"])
    terms = TR.band_terms(cfg["corpus"], mix["queries"]["bands"],
                          mix["queries"]["task_seed"])
    for cat, tasks in a.items():
        spec = mix["queries"]["categories"][cat]
        assert len(tasks) == mix["queries"]["tasks_per_category"]
        for t in tasks:
            assert t.family == spec["family"]
            for tok, band in zip(t.terms, spec.get("bands", ())):
                assert tok in terms[band]
            if t.family == "range":
                assert 0 <= t.lo < t.hi < spec["domain"]


def test_band_shares_match_a_drawn_corpus(nrt):
    """The expected document frequency the bands use agrees with a corpus
    the generator actually draws."""
    cfg = nrt["config"]["corpus"]
    c = C.generate(20000, cfg["vocab"], cfg["zipf_a"], cfg["mean_len"],
                   C.rng_for(3, 0))
    doc = np.repeat(np.arange(c.n), np.diff(c.offsets))
    pairs = np.unique(doc * cfg["vocab"] + c.tokens)
    share = np.bincount(pairs % cfg["vocab"], minlength=cfg["vocab"]) / c.n
    terms = TR.band_terms(cfg, {"mid": [0.01, 0.1]}, 0)["mid"]
    assert len(terms) > 50
    assert np.all((share[terms] > 0.008) & (share[terms] < 0.12))


def test_open_arrivals_seeded_with_equal_category_shares(nrt):
    mix, cfg = nrt["traffic"], nrt["config"]
    tasks = TR.build_tasks(mix, cfg["corpus"])
    a = TR.open_arrivals(mix, tasks, 30.0, 2**31 + 5)
    b = TR.open_arrivals(mix, tasks, 30.0, 2**31 + 5)
    c = TR.open_arrivals(mix, tasks, 30.0, 2**31 + 6)
    assert np.array_equal(a.at, b.at) and a.tasks == b.tasks
    assert not np.array_equal(a.at, c.at)
    n = int(round(mix["rate_qps"] * 30.0))
    assert len(a.at) == len(c.at) == n
    assert np.all(np.diff(a.at) >= 0) and a.at[0] >= 0 and a.at[-1] < 30.0
    shares = [collections.Counter(t.category for t in x.tasks)
              for x in (a, c)]
    for s in shares:
        assert set(s) == set(tasks)
        assert max(s.values()) - min(s.values()) <= 1
    # every seed sends the same work: per category the same count
    assert sorted(shares[0].values()) == sorted(shares[1].values())


def test_query_vectors_near_duplicate_share():
    mix = cell_files(DENSE)["traffic"]
    rng = np.random.default_rng(0)
    vecs = rng.standard_normal((5000, 32)).astype(np.float32)
    a = TR.query_vectors(mix, vecs, 9)
    assert np.array_equal(a, TR.query_vectors(mix, vecs, 9))
    assert a.shape == (mix["queries"]["pool"], 32) and a.dtype == np.float32
    # a near-duplicate's best match is its passage, far above the noise
    best = (a @ vecs.T).max(axis=1) / (np.linalg.norm(a, axis=1) ** 2)
    dup_share = np.mean(best > 0.9)
    assert abs(dup_share - mix["queries"]["near_dup_share"]) < 0.02


def test_ingest_plan_rate(nrt):
    plan = TR.ingest_plan(nrt["traffic"], 30.0)
    ing = nrt["traffic"]["ingest"]
    assert len(plan["at"]) == 30.0 * ing["docs_per_s"] / ing["batch_docs"]
    assert TR.ingest_plan(cell_files(DENSE)["traffic"], 30.0) is None


def test_corpus_seeded_and_text_matches_tokens():
    a = C.generate(400, 30000, 1.3, 80, C.rng_for(2**31 + 3, 0))
    b = C.generate(400, 30000, 1.3, 80, C.rng_for(2**31 + 3, 0))
    assert np.array_equal(a.tokens, b.tokens)
    for k in a.dv:
        assert np.array_equal(a.dv[k], b.dv[k])
    m = C.with_ladder(C.with_markers(a, 100, 3), [5, 50], 1 << 24)
    bodies, titles = m.texts(0, m.n)
    for i in (0, 1, 99, 100, 399):
        toks = m.tokens[m.offsets[i]:m.offsets[i + 1]]
        assert bodies[i] == " ".join(C.token_text(t) for t in toks)
        assert titles[i] == " ".join(
            C.token_text(t) for t in toks[: m.title_len[i]])
    marks = m.tokens[m.tokens >= C.MARKER_BASE]
    got = collections.Counter((marks - C.MARKER_BASE).tolist())
    assert got[3] == got[6] == 100 and got[1 << 24] == 5 and got[(1 << 24) + 1] == 50


def test_folded_zipf_law_matches_numpy_draw():
    cdf = C.zipf_folded_cdf(1.3, 30000)
    p = np.diff(np.concatenate([[0.0], cdf]))
    draw = np.random.default_rng(0).zipf(1.3, size=400_000) % 30000
    emp = np.bincount(draw, minlength=30000) / len(draw)
    assert np.abs(emp[:20] - p[:20]).max() < 3e-3


def _listed_cells():
    man = M.load_manifest()
    return man, [M.cell(man, w["name"]) for w in man["workloads"]]


def test_no_cell_in_the_manifest_holds_a_placeholder():
    _, cells = _listed_cells()
    for cell in cells:
        assert cell["traffic"].get("unmeasured", []) == [], cell["workload"]


def test_commits_fall_inside_the_window_at_the_stated_cadence():
    """Every commit offset lies inside a run's window, and the NRT mix
    commits every 15 s through the whole window, as Solr's autoCommit."""
    man, cells = _listed_cells()
    seconds = man["run_seconds"]
    for cell in cells:
        at = cell["traffic"].get("commit_at_s", [])
        assert all(0 < c < seconds for c in at), cell["workload"]
    at = cell_files(NRT)["traffic"]["commit_at_s"]
    assert np.allclose(np.diff(at), AUTO_COMMIT_S)
    assert at[0] < AUTO_COMMIT_S and seconds - at[-1] < AUTO_COMMIT_S


def test_nrt_tail_stays_in_one_bucket_per_shard_through_a_run():
    """The live tail at the window's start, the rehearsal's ingest and a
    whole window's stay under the same power of two per shard, so the
    tail's programs keep the shapes the warm-up compiled."""
    nrt = cell_files(NRT)
    mix, shards = nrt["traffic"], nrt["config"]["deployment"]["n_shards"]
    seconds = M.load_manifest()["run_seconds"]
    ing = mix["ingest"]
    start = ing["tail_docs_at_start"] / shards
    end = start + ing["docs_per_s"] * (
        seconds + mix["warmup"]["rehearsal_s"]) / shards
    bucket = 1 << int(np.ceil(np.log2(start)))
    assert start > bucket // 2 and end < bucket, (start, end, bucket)
