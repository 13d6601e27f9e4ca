"""The comparison that decides ``correct``: ids may swap only inside runs
of tied reference scores, and a run that reaches the cut may continue
past it only with ids the reference scores as its last."""

import numpy as np

from chipbench import reference as R


def _ref(ids, scores, all_scores=None):
    """An answer whose reference scores every doc by ``all_scores``."""
    table = np.asarray(all_scores if all_scores is not None else [], float)
    docs = np.arange(len(table))
    return R.Answer(len(ids), np.asarray(ids), np.asarray(scores, float),
                    score_of=R._lookup(docs, table))


def _got(ids, scores):
    return R.Answer(len(ids), np.asarray(ids), np.asarray(scores, float))


# doc d scores SCORES[d]: docs 1 and 2 tie, docs 3 and 5 tie at the cut
SCORES = [1.0, 9.0, 8.0, 8.0, 5.0, 5.0, 2.0]
REF = _ref([1, 2, 3, 4], [9.0, 8.0, 8.0, 5.0], SCORES)


def test_swaps_inside_a_tie_run_pass():
    assert not R.ids_differ(_got([1, 3, 2, 4], REF.scores), REF, "term", 1e-4)


def test_a_swap_across_runs_fails():
    assert R.ids_differ(_got([2, 1, 3, 4], REF.scores), REF, "term", 1e-4)


def test_last_rank_is_checked():
    # doc 6 was never returned and scores 2.0, not the last score 5.0
    assert R.ids_differ(_got([1, 2, 3, 6], REF.scores), REF, "term", 1e-4)
    # doc 0 likewise, and a doc the reference does not match at all
    assert R.ids_differ(_got([1, 2, 3, 0], REF.scores), REF, "term", 1e-4)
    assert R.ids_differ(_got([1, 2, 3, 99], REF.scores), REF, "term", 1e-4)


def test_a_tie_past_the_cut_passes():
    # doc 5 ties doc 4, the last the reference returned
    assert not R.ids_differ(_got([1, 2, 3, 5], REF.scores), REF, "term",
                            1e-4)


def test_duplicate_ids_fail():
    ref = _ref([4, 5], [5.0, 5.0], SCORES)
    assert R.ids_differ(_got([4, 4], [5.0, 5.0]), ref, "term", 1e-4)


def test_exact_key_families_need_the_same_order():
    ref = _ref([1, 2], [1.0, 1.0])
    assert R.ids_differ(_got([2, 1], [1.0, 1.0]), ref, "range", 1e-4)
    assert not R.ids_differ(_got([1, 2], [1.0, 1.0]), ref, "range", 1e-4)


def test_vector_reference_holds_the_last_id():
    rng = np.random.default_rng(3)
    v = rng.standard_normal((300, 16)).astype(np.float32)
    q = rng.standard_normal((2, 16)).astype(np.float32)
    ref = R.VectorReference(v).answers(q, 300, 10)
    tally = R.Tally()
    for a in ref:
        tally.add(_got(a.doc_ids, a.scores), a, "vector", 1e-4, "same")
    assert tally.ids_wrong == 0
    ids = ref[0].doc_ids.copy()
    ids[-1] = next(d for d in range(300) if d not in ids)
    tally.add(_got(ids, ref[0].scores), ref[0], "vector", 1e-4, "last")
    assert tally.ids_wrong == 1 and tally.score_gap == 0.0
