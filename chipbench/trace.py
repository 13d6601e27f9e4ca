"""Profiler trace of the measured window, and its reduction to numbers.

``Capture`` starts JAX's profiler at the window's start with the host and
Python tracers at their lowest levels, and brackets it with two
``chipbench_sync`` annotations whose host-clock times it records: the
trace's timestamps are relative, and the pair maps them onto
``time.perf_counter``, the clock of the benchmark's own host spans.

``load`` reads the ``.xplane.pb`` the profiler wrote into plain events of
the device planes (``/device:TPU:<n>``): the ``XLA Ops`` line (every
operation the device ran) and the ``XLA Modules`` line (one event per
program execution, named after its jitted function).

``reduce`` turns those into what the metrics read:

* ``busy_s``: length of the union of the operation intervals inside the
  traced window, averaged over the chips;
* ``window_s``: the traced window's length;
* ``programs``: device seconds per program (jit name, ``jit_`` prefix and
  the ``(id)`` suffix dropped), averaged over the chips;
* ``gaps``: the longest stretches with no operation running, each
  labelled by the host span kind that covers most of it (``commit``,
  ``ingest op``, ``probe``, ``wave``; ``no request outstanding`` where no
  client was waiting).
"""

from __future__ import annotations

import glob
import os
import re
import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

SYNC = "chipbench_sync"
_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
_PROGRAM = re.compile(r"^(?:jit_)?(.*?)(?:\(\d+\))?$")

#: host span kinds, strongest first, as the trace labels them
SPAN_LABELS = (
    ("commit", "commit"),
    ("ingest", "ingest op"),
    ("probe", "probe"),
    ("query", "wave"),
)


class Capture:
    """Profile ``[start(), stop()]`` into ``log_dir``."""

    def __init__(self, log_dir: str) -> None:
        self.log_dir = log_dir
        self.sync_ns: List[int] = []

    def _sync(self) -> None:
        import jax

        with jax.profiler.TraceAnnotation(SYNC):
            self.sync_ns.append(time.perf_counter_ns())

    def start(self) -> None:
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.log_dir, profiler_options=opts)
        self._sync()

    def stop(self) -> None:
        import jax

        self._sync()
        jax.profiler.stop_trace()


def load(log_dir: str, sync_ns: Sequence[int]) -> Optional[dict]:
    """Device events of the newest trace under ``log_dir`` on the host
    clock (see ``events_from_records``), or None without a trace."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        return None
    pd = ProfileData.from_file(files[-1])
    records = [
        (plane.name, line.name, ev.name, ev.start_ns, ev.duration_ns)
        for plane in pd.planes
        for line in plane.lines
        for ev in line.events
        if _DEVICE_PLANE.match(plane.name) or ev.name == SYNC
    ]
    return events_from_records(records, sync_ns)


def events_from_records(records: Sequence[tuple], sync_ns: Sequence[int]
                        ) -> dict:
    """``(plane, line, name, start_ns, duration_ns)`` records of a trace
    (its device planes and the host's sync marks) as ``{"chips": n,
    "ops": [(start, end, name, chip)], "modules": [(start, end, program,
    chip)]}`` in seconds of ``time.perf_counter``; ``n`` is 0 where no
    device plane exists.  The trace's relative clock is tied to the host
    clock by the first sync mark."""
    marks = [r[3] for r in records if r[2] == SYNC]
    planes: Dict[str, int] = {}
    ops: List[tuple] = []
    modules: List[tuple] = []
    for plane, line, name, start, dur in records:
        if not _DEVICE_PLANE.match(plane):
            continue
        chip = planes.setdefault(plane, len(planes))
        iv = (start, start + dur, name, chip)
        if line == "XLA Ops":
            ops.append(iv)
        elif line == "XLA Modules":
            modules.append(iv)
    if not marks or not sync_ns:
        return {"chips": len(planes), "ops": [], "modules": []}
    off = sync_ns[0] - min(marks)  # relative trace ns -> perf_counter ns

    def conv(evs):
        return [((s + off) * 1e-9, (e + off) * 1e-9, n, c)
                for s, e, n, c in evs]

    return {"chips": len(planes), "ops": conv(ops), "modules": conv(modules)}


def program_name(event_name: str) -> str:
    return _PROGRAM.match(event_name).group(1)


def _union(ivs: np.ndarray) -> np.ndarray:
    """Sorted, disjoint union of ``(start, end)`` rows."""
    if not len(ivs):
        return ivs.reshape(0, 2)
    ivs = ivs[np.argsort(ivs[:, 0])]
    ends = np.maximum.accumulate(ivs[:, 1])
    new = np.ones(len(ivs), dtype=bool)
    new[1:] = ivs[1:, 0] > ends[:-1]
    starts = ivs[new, 0]
    idx = np.flatnonzero(new)
    last = np.append(idx[1:] - 1, len(ivs) - 1)
    return np.stack([starts, ends[last]], axis=1)


def _label(lo: float, hi: float, spans: Dict[str, np.ndarray]) -> str:
    best, cover = "no request outstanding", 0.0
    for kind, label in SPAN_LABELS:
        s = spans.get(kind)
        if s is None or not len(s):
            continue
        ov = np.clip(np.minimum(s[:, 1], hi) - np.maximum(s[:, 0], lo), 0,
                     None)
        # covered length of [lo, hi] by the union of this kind's spans
        u = _union(np.stack([np.maximum(s[ov > 0, 0], lo),
                             np.minimum(s[ov > 0, 1], hi)], axis=1))
        c = float((u[:, 1] - u[:, 0]).sum()) if len(u) else 0.0
        if c > cover + 1e-12:
            best, cover = label, c
    return best


def reduce(events: dict, t0: float, t1: float,
           spans: Sequence[Tuple[str, float, float]] = (),
           top: int = 10) -> Optional[dict]:
    """Numbers of the traced window ``[t0, t1]`` (see module docstring);
    None where the trace holds no device operation."""
    chips = max(events.get("chips", 0), 1)
    if not events.get("ops"):
        return None
    per_chip: Dict[int, list] = {}
    for s, e, _, c in events["ops"]:
        if e > t0 and s < t1:
            per_chip.setdefault(c, []).append((max(s, t0), min(e, t1)))
    if not per_chip:
        return None
    unions = {c: _union(np.asarray(v, dtype=np.float64))
              for c, v in per_chip.items()}
    busy = sum(float((u[:, 1] - u[:, 0]).sum())
               for u in unions.values()) / chips
    programs: Dict[str, float] = {}
    for s, e, n, _ in events.get("modules", ()):
        lo, hi = max(s, t0), min(e, t1)
        if hi > lo:
            p = program_name(n)
            programs[p] = programs.get(p, 0.0) + (hi - lo) / chips
    # idle gaps: stretches where no chip ran anything
    busy_iv = _union(np.concatenate(list(unions.values())))
    edges = np.concatenate([[t0], busy_iv.ravel(), [t1]]).reshape(-1, 2)
    gaps = edges[edges[:, 1] > edges[:, 0]]
    longest = gaps[np.argsort(gaps[:, 0] - gaps[:, 1])[:top]]
    by_kind: Dict[str, list] = {}
    for kind, a, b in spans:
        by_kind.setdefault(kind, []).append((a, b))
    span_arr = {k: np.asarray(v, dtype=np.float64) for k, v in by_kind.items()}
    return {
        "busy_s": busy,
        "window_s": t1 - t0,
        "programs": programs,
        "gaps": [(_label(a, b, span_arr), float(b - a)) for a, b in longest],
    }


def breakdown(red: dict, top: int = 10) -> dict:
    """The result line's ``breakdown``: programs by device time and the
    longest idle gaps by what the host was doing."""
    ops = sorted(red["programs"].items(), key=lambda kv: -kv[1])[:top]
    return {
        "device_ops": [[n, s] for n, s in ops],
        "idle_gaps": [[label, s] for label, s in red["gaps"][:top]],
    }
