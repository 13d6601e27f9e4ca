"""Find a cell's files by the names ``BENCHMARK.json`` gives them.

A cell names a configuration and a traffic mix; a metric is named in
``end_to_end`` or ``per_layer``.  Each lives in a file of its own:

* ``chipbench/configs/<config>.json``
* ``chipbench/traffic/<traffic>.json``
* ``chipbench/metrics/<per-layer metric>.py`` (a ``read(r)`` function);
  a quantity split by the end-to-end metric it moves (``mean_wave.closed``,
  ``mean_wave.nrt``) may share one reader, ``metrics/<quantity>.py``

so a later change adds a cell, a configuration or a metric by adding a
file and a manifest entry, and edits nothing that is here.
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from typing import Callable, Dict, List

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


class ManifestError(RuntimeError):
    """The manifest or a file it names is missing or malformed."""


def _json(path: Path) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise ManifestError(f"missing {path.relative_to(ROOT)}") from None


def load_manifest(root: Path = ROOT) -> dict:
    return _json(root / "BENCHMARK.json")


def cell(manifest: dict, name: str) -> dict:
    """The cell's entry plus its configuration and traffic files."""
    for w in manifest["workloads"]:
        if w["name"] == name:
            break
    else:
        raise ManifestError(f"no workload named {name!r} in BENCHMARK.json")
    cfg = next((c for c in manifest["configs"] if c["name"] == w["config"]),
               None)
    if cfg is None:
        raise ManifestError(f"workload {name!r} names unknown config "
                            f"{w['config']!r}")
    return {
        "workload": w,
        "config": _json(ROOT / cfg["file"]),
        "traffic": _json(HERE / "traffic" / f"{w['traffic']}.json"),
    }


def metrics_for(manifest: dict, name: str, section: str) -> List[dict]:
    """Entries of ``section`` that cell ``name`` reports: those with no
    ``workloads`` key, and those that list it."""
    return [m for m in manifest[section]
            if "workloads" not in m or name in m["workloads"]]


def reader(metric: str, metrics_dir: Path = HERE / "metrics") -> Callable:
    """``read`` of ``metrics/<metric>.py``, loaded by file name, else of
    the shared ``metrics/<quantity>.py`` for ``<quantity>.<split>``."""
    path = metrics_dir / f"{metric}.py"
    if not path.exists() and "." in metric:
        path = metrics_dir / f"{metric.split('.')[0]}.py"
    if not path.exists():
        raise ManifestError(f"no reader {metric}.py for metric {metric!r}")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{metric.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def read_per_layer(entries: List[dict], r, metrics_dir: Path = HERE / "metrics"
                   ) -> Dict[str, dict]:
    """Every per-layer metric whose reader finds something to read."""
    out: Dict[str, dict] = {}
    for m in entries:
        v = reader(m["name"], metrics_dir)(r)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out
