"""``chip_smoke.py`` at a tiny size on the CPU.

The phases run exactly as on the chip (2-shard byte-pmem + WAL engine on
the processes backend, ``SearchFrontend``, every family, live tail,
vectors + hybrid) with the fused kernels forced into interpret mode, and
every answer is checked against its reference.  ``main()`` itself must
refuse the CPU and a forced kernel path.
"""

import importlib.util
from pathlib import Path

import numpy as np
import pytest

from repro.core.query.types import TopDocs

_PATH = Path(__file__).resolve().parents[1] / "chip_smoke.py"


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", _PATH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_phases_tiny_with_interpret_kernels(chip_smoke, tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_FUSED_KERNEL", "1")
    a = chip_smoke.phase_a(
        str(tmp_path), n_docs=3000, seed=0, live_batches=2, live_batch_docs=100
    )
    families = {"TermQuery", "BooleanQuery", "PhraseQuery", "RangeQuery",
                "SortQuery", "FacetQuery"}
    assert set(a["committed"]) == families
    assert set(a["live"]) == families
    assert a["worker_jax_platforms"] == ["cpu"]
    for fam in ("term", "bool", "range", "sort", "facet"):
        assert a["dispatches"].get(f"fused.{fam}", 0) >= 1, a["dispatches"]
    b = chip_smoke.phase_b(str(tmp_path), n_passages=2000, dim=8, seed=0)
    assert b["counts"] == {"VectorQuery": 10, "HybridQuery": 4}
    assert b["dispatches"].get("fused.vector", 0) >= 1
    assert b["dispatches"].get("fused.hybrid", 0) >= 1


def test_check_allows_swaps_only_within_ties(chip_smoke):
    ref = TopDocs(5, np.array([1, 2, 3]), np.array([3.0, 2.0, 2.0]))
    swapped = TopDocs(5, np.array([1, 3, 2]), np.array([3.0, 2.0, 2.0]))
    chip_smoke.check(swapped, ref, "tie swap")
    # the run reaching the cut may continue past it: another tied id is fine
    chip_smoke.check(
        TopDocs(5, np.array([1, 2, 9]), np.array([3.0, 2.0, 2.0])), ref, "cut"
    )
    wrong = TopDocs(5, np.array([2, 1, 3]), np.array([3.0, 2.0, 2.0]))
    with pytest.raises(chip_smoke.ParityError):
        chip_smoke.check(wrong, ref, "untied swap")
    with pytest.raises(chip_smoke.ParityError):
        chip_smoke.check(TopDocs(4, ref.doc_ids, ref.scores), ref, "hits")
    with pytest.raises(chip_smoke.ParityError):
        chip_smoke.check(
            TopDocs(5, ref.doc_ids, ref.scores * (1 + 1e-3)), ref, "scores"
        )


@pytest.mark.parametrize("forced", [False, True])
def test_main_refuses_cpu_and_forced_kernels(chip_smoke, forced, monkeypatch,
                                             capsys):
    if forced:
        monkeypatch.setenv("REPRO_FUSED_KERNEL", "1")
    else:
        monkeypatch.delenv("REPRO_FUSED_KERNEL", raising=False)
    assert chip_smoke.main([]) != 0
    assert '"ok"' not in capsys.readouterr().out
