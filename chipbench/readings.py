"""What the per-layer readers in ``chipbench/metrics/`` read: counters the
system keeps, differenced over the measured window, and the reduced device
trace of the traced run.  Each reader is a small function of a
``Readings``; one that finds nothing to read returns None and its metric
is left out of the result line."""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional


@dataclasses.dataclass
class Readings:
    #: ``SearchFrontend.stats()`` counters, end minus start of the window
    frontend: Dict[str, float]
    #: executor dispatches (``repro.core.query.profile``), same window
    dispatches: Dict[str, int]
    #: client-timed ``commit()`` calls that started inside the window (ms)
    commit_ms: List[float]
    #: ``chipbench.trace.reduce`` of the traced window (``--trace 1``)
    trace: Optional[dict] = None
    #: this device's row of ``peaks.json``
    peaks: Optional[dict] = None
    #: distinct waves that answered dense-vector queries in the window
    vector_waves: int = 0
    #: rows and width of the vector column those waves scanned
    vector_rows: int = 0
    vector_dim: int = 0


def mean_wave(r: Readings) -> Optional[float]:
    """Queries per coalesced wave (``wave_queries / waves``)."""
    waves = r.frontend.get("waves", 0)
    return r.frontend["wave_queries"] / waves if waves else None


def dispatches_per_query(r: Readings) -> Optional[float]:
    """Executor device dispatches per query the front end served
    (probes included)."""
    served = r.frontend.get("wave_queries", 0)
    return sum(r.dispatches.values()) / served if served else None


def device_idle_pct(r: Readings) -> Optional[float]:
    """Share of the traced window with no operation on the device."""
    if r.trace is None or r.trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - r.trace["busy_s"] / r.trace["window_s"])


def program_seconds(r: Readings, program: str) -> Optional[float]:
    """Device seconds of one jitted program in the traced window."""
    if r.trace is None:
        return None
    return r.trace["programs"].get(program) or None
