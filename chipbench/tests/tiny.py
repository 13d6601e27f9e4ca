"""Tiny copies of the benchmark's cells for tests on the CPU: the real
configuration and traffic files with their scale cut down, the serial
ingest backend, and a short grace period."""

from __future__ import annotations

import copy
import json

from chipbench import manifest as M

NRT = "wiki500k-nrt-open"
DENSE = "msmarco500k-dense-closed"
#: each cell's configuration and traffic files, read without the manifest
#: so the tests hold whichever cells ``BENCHMARK.json`` lists
FILES = {NRT: ("wikimedium500k", "nrt-open"),
         DENSE: ("msmarco-passage-500k", "dense-closed")}


def cell_files(name: str) -> dict:
    config, traffic = FILES[name]
    return {
        "workload": {"name": name, "config": config, "traffic": traffic,
                     "chips": 1},
        "config": json.loads(
            (M.HERE / "configs" / f"{config}.json").read_text()),
        "traffic": json.loads(
            (M.HERE / "traffic" / f"{traffic}.json").read_text()),
    }


def tiny_cell(name: str, backend: str = "serial") -> dict:
    cell = cell_files(name)
    cfg = copy.deepcopy(cell["config"])
    mix = copy.deepcopy(cell["traffic"])
    cfg["n_docs"] = 1500
    cfg["deployment"].update(backend=backend, ingest_batch_docs=500)
    if cfg.get("vectors"):
        cfg["vectors"]["dim"] = 16
    mix["frontend"]["max_wave"] = 4
    mix["check_sample"] = 12
    if mix["loop"] == "open":
        mix["rate_qps"] = 12
        mix["queries"]["tasks_per_category"] = 1
        # the tail stays inside one power-of-two bucket per shard
        mix["ingest"].update(docs_per_s=40, batch_docs=10,
                             tail_docs_at_start=560)
        mix["commit_at_s"] = [0.5]
        mix["warmup"].update(batch_sizes=[1, 2], rehearsal_s=0.5)
    else:
        mix["clients"] = 4
        mix["queries"]["pool"] = 32
    return dict(cell, config=cfg, traffic=mix)


def limits(name: str) -> dict:
    return json.loads((M.HERE / "limits" / f"{name}.json").read_text())[
        "limits"]


def run_tiny(name: str, seed: int = 2**31 + 17, seconds: float = 1.5,
             trace: bool = False, **kw) -> dict:
    from chipbench import run as RUN

    return RUN.run_cell(
        kw.pop("manifest", None) or M.load_manifest(), name, seed, seconds,
        trace, require_tpu=False,
        compile_cache=False, cell=kw.pop("cell", None) or tiny_cell(name),
        limits=limits(name), grace_s=kw.pop("grace_s", 5.0), **kw)
