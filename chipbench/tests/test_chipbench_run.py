"""Whole runs of both cells at tiny sizes on the CPU (the chip check
skipped, everything else as on the chip): the result line's schema, the
references agreeing with a sound system, the control failing, the
refusal to measure off a TPU, and metrics found by file name."""

import json
import shutil

import pytest

from chipbench import manifest as M
from chipbench import run as RUN
from chipbench.tests.tiny import DENSE, NRT, run_tiny

SCHEMA = ("correct", "attempted", "failed", "metrics", "device")


@pytest.fixture(scope="module", params=[NRT, DENSE])
def sound(request):
    return request.param, run_tiny(request.param, control=True)


def test_sound_run_is_correct_with_schema(sound):
    name, out = sound
    assert out["correct"] is True, out["checks"]
    assert list(out)[-1] == "checks"  # the compared numbers come last
    for key in SCHEMA:
        assert key in out
    assert out["attempted"] > 0 and out["failed"] == 0
    man = M.load_manifest()
    want = {m["name"] for m in M.metrics_for(man, name, "end_to_end")}
    assert set(out["metrics"]) == want
    for v in out["metrics"].values():
        assert set(v) == {"value", "unit"} and v["value"] > 0
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(
        out["device"])
    for value, limit in out["checks"].values():
        assert value <= limit
    json.loads(json.dumps(out))  # one JSON line


def test_control_in_lower_precision_fails(sound):
    """The reference in bfloat16, put in the system's place, must fail the
    comparison the run applies."""
    name, out = sound
    ctl = out["control"]
    assert ctl["checked"] > 0
    assert ctl["correct"] is False, ctl
    gap, limit = ctl["checks"]["score_gap"]
    assert limit == out["checks"]["score_gap"][1]
    assert gap > 3 * limit, ctl
    assert gap > 10 * out["checks"]["score_gap"][0]


def test_every_end_to_end_metric_is_one_the_run_fills():
    """Every end-to-end name in BENCHMARK.json is a number the untraced
    run computes: an open loop with ingest, asked for all of them, fills
    each, so no cell can list one that reads nothing."""
    man = M.load_manifest()
    for m in man["end_to_end"]:
        m.pop("workloads", None)
    out = run_tiny(NRT, seed=2**31 + 41, manifest=man)
    assert out["correct"] is True, out["checks"]
    assert set(out["metrics"]) == {m["name"] for m in man["end_to_end"]}
    assert all(v["value"] > 0 for v in out["metrics"].values())


def test_traced_run_reads_per_layer_metrics():
    out = run_tiny(DENSE, seed=5, trace=True)
    assert out["correct"] is True
    man = M.load_manifest()
    names = {m["name"] for m in M.metrics_for(man, DENSE, "per_layer")}
    # the CPU trace has no device plane: only the counter readers report
    assert set(out["metrics"]) == {"mean_wave.closed",
                                   "dispatches_per_query.closed"}
    assert set(out["metrics"]) <= names
    assert {"busy_s", "window_s"} <= set(out["device"])


def test_refuses_to_measure_off_a_tpu(capsys):
    rc = RUN.main(["--workload", DENSE, "--seed", "1", "--seconds", "1"])
    assert rc != 0
    assert capsys.readouterr().out == ""
    with pytest.raises(RUN.DeviceError):
        RUN.check_device(1, require_tpu=True)


def test_unknown_workload_refused(capsys):
    assert RUN.main(["--workload", "nope", "--seed", "1", "--seconds",
                     "1"]) != 0
    assert capsys.readouterr().out == ""


def test_a_new_metric_file_is_found_by_name(tmp_path):
    """A per-layer metric is a file plus a manifest entry: a throwaway
    reader in a copy of the metrics directory is read with no other file
    edited."""
    metrics = tmp_path / "metrics"
    shutil.copytree(M.HERE / "metrics", metrics)
    (metrics / "waves_seen.py").write_text(
        "def read(r):\n    return r.frontend['waves']\n")
    man = M.load_manifest()
    man["per_layer"].append({
        "name": "waves_seen", "unit": "waves", "better": "higher",
        "source": "program_counter", "layer": "front end", "moves": "qps",
        "workloads": [DENSE]})
    out = run_tiny(DENSE, seed=6, trace=True, metrics_dir=metrics,
                   manifest=man)
    assert out["metrics"]["waves_seen"]["value"] > 0
    assert out["metrics"]["waves_seen"]["unit"] == "waves"
