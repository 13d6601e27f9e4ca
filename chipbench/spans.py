"""The program's own spans (``repro.core.query.profile``) in a traced run.

The program records spans while a profiler session traces the host, so a
``--trace 1`` run holds the spans of its window beside the device trace,
both on ``time.perf_counter``.  This module turns them into:

* per-wave host times, for the per-layer readers in ``metrics/``.  A
  span's *self time* is its duration minus the part of it that its child
  spans cover (their union, clipped to it);
* the attribution of the device's idle time to host work
  (``attribute_gaps``): every instant of a thread's time inside a span
  belongs to its innermost open span, and an idle gap is labelled with the
  span name that covers most of it.  A ``host.gc`` span is innermost on
  every thread: a collection holds the interpreter lock, so while it runs
  in one thread no other thread runs Python.

``frontend.queue`` spans bound a query's wait, not host work, so they are
left out of the attribution.  A program without spans (no ``spans()`` in
its ``profile``) gives nothing to read.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from chipbench import trace as T

#: the span that bounds one dispatcher wave; idle time it alone covers is
#: not attributed to any layer
WAVE = "frontend.wave"
QUEUE = "frontend.queue"
GC = "host.gc"


def program_spans() -> Optional[list]:
    """The spans the program holds, or None where it keeps none."""
    from repro.core.query import profile

    read = getattr(profile, "spans", None)
    if read is None:
        return None
    return read() or None


def window_spans(r) -> Optional[list]:
    """The program's spans of a run with a device trace: spans are put on
    the device trace's clock, so a run without one reads nothing."""
    if r.trace is None:
        return None
    return program_spans()


def self_ns(spans: Sequence) -> Dict[int, int]:
    """Self time of each span (by span id): its duration minus the union
    of its children's intervals, clipped to it."""
    kids: Dict[int, list] = {}
    for s in spans:
        if s.parent_id is not None:
            kids.setdefault(s.parent_id, []).append((s.start_ns, s.end_ns))
    out = {}
    for s in spans:
        dur = s.end_ns - s.start_ns
        ch = kids.get(s.span_id)
        if ch:
            iv = np.clip(np.asarray(ch, dtype=np.float64), s.start_ns,
                         s.end_ns)
            u = T._union(iv)
            dur -= int((u[:, 1] - u[:, 0]).sum())
        out[s.span_id] = max(dur, 0)
    return out


def _waves(spans: Sequence) -> list:
    return [s for s in spans if s.name == WAVE]


def queue_wait_ms(spans: Sequence) -> Optional[float]:
    """Mean wait of the queries picked up (submit to their wave's pop)."""
    q = [s.end_ns - s.start_ns for s in spans if s.name == QUEUE]
    return 1e-6 * float(np.mean(q)) if q else None


def self_ms_per_wave(spans: Sequence, names: Tuple[str, ...]
                     ) -> Optional[float]:
    """Summed self time of the spans named ``names``, per wave (ms)."""
    waves = _waves(spans)
    if not waves:
        return None
    own = self_ns(spans)
    total = sum(own[s.span_id] for s in spans if s.name in names)
    return 1e-6 * total / len(waves)


def host_ms_per_wave(spans: Sequence) -> Optional[float]:
    """Mean ``frontend.wave`` duration less its time under ``exec.fetch``
    (the host waiting on the device), in ms: the host time of a wave."""
    waves = _waves(spans)
    if not waves:
        return None
    fetch: Dict[int, list] = {}
    for s in spans:
        if s.name == "exec.fetch":
            fetch.setdefault(s.thread, []).append((s.start_ns, s.end_ns))
    fetch_u = {t: T._union(np.asarray(v, dtype=np.float64))
               for t, v in fetch.items()}
    total = 0.0
    for w in waves:
        u = fetch_u.get(w.thread)
        waited = 0.0
        if u is not None:
            waited = float(_covered(u, np.array([w.start_ns], np.float64),
                                    np.array([w.end_ns], np.float64))[0])
        total += (w.end_ns - w.start_ns) - waited
    return 1e-6 * total / len(waves)


# ---------------------------------------------------------------------------
# idle time against host work
# ---------------------------------------------------------------------------


def _covered(u: np.ndarray, lo: np.ndarray, hi: np.ndarray) -> np.ndarray:
    """Length of each ``[lo[i], hi[i]]`` that the sorted, disjoint
    intervals ``u`` cover."""
    out = np.zeros(len(lo), dtype=np.float64)
    if not len(u) or not len(lo):
        return out
    cum = np.concatenate([[0.0], np.cumsum(u[:, 1] - u[:, 0])])
    i = np.searchsorted(u[:, 1], lo, side="right")  # first ending after lo
    j = np.searchsorted(u[:, 0], hi, side="left")  # [i, j) start before hi
    some = i < j
    ii, jj = i[some], j[some]
    tot = cum[jj] - cum[ii]
    tot -= np.clip(lo[some] - u[ii, 0], 0, None)
    tot -= np.clip(u[jj - 1, 1] - hi[some], 0, None)
    out[some] = np.clip(tot, 0, None)
    return out


def innermost(spans: Sequence) -> Dict[str, np.ndarray]:
    """Per span name, the sorted disjoint intervals (seconds of
    ``time.perf_counter``) in which a span of that name was the innermost
    one open on its thread, ``host.gc`` on every thread;
    ``frontend.queue`` left out."""
    host = [s for s in spans if s.name != QUEUE]
    gcs = [(s.start_ns, s.end_ns) for s in host if s.name == GC]
    kids: Dict[int, list] = {}
    for s in host:
        if s.parent_id is not None and s.name != GC:
            kids.setdefault(s.parent_id, []).append((s.start_ns, s.end_ns))
    by_name: Dict[str, list] = {}
    for s in host:
        iv = np.array([[s.start_ns, s.end_ns]], dtype=np.float64)
        cut = kids.get(s.span_id, []) + ([] if s.name == GC else gcs)
        if cut:
            u = T._union(np.clip(np.asarray(cut, dtype=np.float64),
                                 s.start_ns, s.end_ns))
            edges = np.concatenate([[s.start_ns], u.ravel(), [s.end_ns]])
            iv = edges.reshape(-1, 2)
            iv = iv[iv[:, 1] > iv[:, 0]]
        by_name.setdefault(s.name, []).append(iv)
    return {n: T._union(np.concatenate(v) * 1e-9) for n, v in by_name.items()}


def idle_gaps(events: dict, t0: float, t1: float) -> np.ndarray:
    """``(start, end)`` rows of the traced window ``[t0, t1]`` in which no
    chip ran an operation."""
    ops = [(max(s, t0), min(e, t1)) for s, e, _, _ in events.get("ops", ())
           if e > t0 and s < t1]
    busy = T._union(np.asarray(ops, dtype=np.float64).reshape(-1, 2))
    edges = np.concatenate([[t0], busy.ravel(), [t1]]).reshape(-1, 2)
    return edges[edges[:, 1] > edges[:, 0]]


def attribute_gaps(events: dict, t0: float, t1: float,
                   client: Sequence[Tuple[str, float, float]],
                   spans: Sequence, top: int = 10) -> dict:
    """The idle gaps of ``[t0, t1]`` against the program's spans.

    ``gaps``: the ``top`` longest, each ``(label, seconds)``; the label is
    the client label ``trace.reduce`` gives, then ``:`` and the span name
    that was innermost for most of the gap (``wave:sharded.merge``), or
    the client label alone where no span covers it.  ``idle_s`` is the
    window's idle time, ``attributed_s`` the part of it in which some span
    other than ``frontend.wave`` was innermost, and ``by_span`` the idle
    seconds each span name was innermost for."""
    gaps = idle_gaps(events, t0, t1)
    lo, hi = gaps[:, 0], gaps[:, 1]
    inner = innermost(spans)
    cover = {n: _covered(u, lo, hi) for n, u in inner.items()}
    leaf = [u for n, u in inner.items() if n != WAVE]
    leaf_u = (T._union(np.concatenate(leaf)) if leaf
              else np.zeros((0, 2)))
    idle_s = float((hi - lo).sum())
    attributed_s = float(_covered(leaf_u, lo, hi).sum())
    by_kind: Dict[str, list] = {}
    for kind, a, b in client:
        by_kind.setdefault(kind, []).append((a, b))
    client_arr = {k: np.asarray(v, dtype=np.float64)
                  for k, v in by_kind.items()}
    names = list(cover)
    mat = (np.stack([cover[n] for n in names]) if names
           else np.zeros((0, len(gaps))))
    labels: List[Tuple[str, float]] = []
    for g in np.argsort(lo - hi)[:top]:
        label = T._label(lo[g], hi[g], client_arr)
        if len(names) and mat[:, g].max() > 0:
            label = f"{label}:{names[int(np.argmax(mat[:, g]))]}"
        labels.append((label, float(hi[g] - lo[g])))
    return {
        "gaps": labels,
        "idle_s": idle_s,
        "attributed_s": attributed_s,
        "attributed_pct": 100.0 * attributed_s / idle_s if idle_s else None,
        "by_span": {n: float(c.sum()) for n, c in cover.items()},
    }
