"""The program's instrumentation: a dispatch ledger and host spans.

**Dispatch ledger.**  ``search_bench`` needs to show that fusion removes
dispatches (host→device round-trips between plan stages), not just that
throughput moved.  JAX's profiler hooks are version-fragile, so the
executors self-report instead: every per-(group, segment) device dispatch
calls ``record(tag)``, and a bench run wraps its timed region in
``capture()`` to read the delta.

Tags are ``<path>.<family>`` — e.g. ``vmap.term`` (PR 1 unfused batched
executor, one staged upload + dispatch per segment) vs ``fused.term``
(single fused dispatch per segment, no host staging).  The ledger counts
executor-issued dispatches, which is the quantity fusion changes; XLA may
still split a program internally, but it never adds host round-trips.

**Spans.**  ``with span(name, **attrs):`` times a region of host work at a
layer boundary.  A record (``Span``) holds the name, its own id, the id of
the span open around it on the same thread, a trace id (given, else the
enclosing span's: the wave number for a dispatcher wave, the request's
seqno for per-request spans), ``start_ns``/``end_ns`` on
``time.perf_counter_ns`` — the clock a device trace is mapped onto — the
recording thread, and the attrs.  ``add`` records a span whose two ends
were stamped elsewhere (a query's wait from ``submit`` to its wave).

Records are kept in memory only, in a buffer bounded at ``MAX_SPANS``
records: the oldest are evicted first and counted by ``dropped()``.
``spans()`` returns what the buffer holds.

Spans are recorded while ``enable(True)`` is in force, or while a JAX
profiler session traces the host (``jax.profiler.start_trace``): the
spans then lie beside the device trace they explain, with no call from
whoever started it.  Otherwise ``span()`` returns one shared no-op context
manager and keeps nothing.  While spans are recorded a ``gc.callbacks``
hook records each Python collection as a ``host.gc`` span (attr
``generation``); ``enable(False)`` removes it.
"""

from __future__ import annotations

import collections
import contextlib
import gc
import itertools
import threading
import time
from typing import Any, Callable, Dict, Iterator, List, NamedTuple, Optional

# ---------------------------------------------------------------------------
# dispatch ledger
# ---------------------------------------------------------------------------

_counts: "collections.Counter[str]" = collections.Counter()


def record(tag: str) -> None:
    """Count one executor-issued device dispatch."""
    _counts[tag] += 1


def snapshot() -> Dict[str, int]:
    return dict(_counts)


@contextlib.contextmanager
def capture() -> Iterator[Dict[str, int]]:
    """Yield a dict that is filled with the dispatch-count delta of the
    wrapped region (previous counts are restored on exit)."""
    before = dict(_counts)
    delta: Dict[str, int] = {}
    try:
        yield delta
    finally:
        for tag, n in _counts.items():
            d = n - before.get(tag, 0)
            if d:
                delta[tag] = d


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------

#: records the buffer holds before it evicts the oldest
MAX_SPANS = 1 << 18


class Span(NamedTuple):
    name: str
    span_id: int
    parent_id: Optional[int]
    trace_id: Optional[int]
    start_ns: int
    end_ns: int
    thread: int
    attrs: Dict[str, Any]


_on = False
_buf: "collections.deque[Span]" = collections.deque(maxlen=MAX_SPANS)
_buf_lock = threading.RLock()  # a collection may record inside _keep
_dropped = 0  # records evicted since the buffer was last emptied
_ids = itertools.count(1)
_local = threading.local()
_gc_hooked = False
_gc_open: Dict[int, tuple] = {}  # thread -> (start_ns, parent, trace)


def _host_traced_first() -> bool:
    # bound on first use, so importing this module imports no JAX
    global _host_traced
    from jax.profiler import TraceAnnotation

    _host_traced = TraceAnnotation.is_enabled
    return _host_traced()


#: True while a JAX profiler session traces the host
_host_traced: Callable[[], bool] = _host_traced_first


def recording() -> bool:
    """Are spans being recorded now?"""
    return _on or _host_traced()


def enable(on: bool) -> None:
    """Record spans from now (``True``: the buffer and ``dropped()`` start
    empty) or stop recording them and remove the ``host.gc`` hook."""
    global _on, _dropped
    _on = bool(on)
    if _on:
        with _buf_lock:
            _buf.clear()
            _dropped = 0
        _hook_gc()
    else:
        _unhook_gc()


def spans() -> List[Span]:
    """The records the buffer holds, oldest first."""
    return list(_buf)


def dropped() -> int:
    """Records evicted from the full buffer since it was last emptied."""
    return _dropped


def _stack() -> list:
    try:
        return _local.stack
    except AttributeError:
        _local.stack = []
        return _local.stack


def _keep(rec: Span) -> None:
    global _dropped
    with _buf_lock:
        if len(_buf) == MAX_SPANS:
            _dropped += 1
        _buf.append(rec)


def add(name: str, start_ns: int, end_ns: int, trace: Optional[int] = None,
        **attrs: Any) -> None:
    """Record a span stamped by the caller (its two ends may lie on
    different threads); its parent is the span open on this thread."""
    if not recording():
        return
    stack = _stack()
    parent = stack[-1] if stack else None
    _keep(Span(name, next(_ids), parent.span_id if parent else None,
               trace if trace is not None else
               (parent.trace_id if parent else None),
               start_ns, end_ns, threading.get_ident(), attrs))


class _Open:
    """One span being timed (``span()`` while recording)."""

    __slots__ = ("name", "attrs", "trace_id", "span_id", "parent_id",
                 "start_ns")

    def __init__(self, name: str, trace: Optional[int], attrs: dict) -> None:
        self.name = name
        self.attrs = attrs
        self.trace_id = trace

    def __enter__(self) -> "_Open":
        stack = _stack()
        if stack:
            parent = stack[-1]
            self.parent_id = parent.span_id
            if self.trace_id is None:
                self.trace_id = parent.trace_id
        else:
            self.parent_id = None
        self.span_id = next(_ids)
        stack.append(self)
        self.start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        end = time.perf_counter_ns()
        stack = _stack()
        if stack and stack[-1] is self:
            stack.pop()
        _keep(Span(self.name, self.span_id, self.parent_id, self.trace_id,
                   self.start_ns, end, threading.get_ident(), self.attrs))


class _NoSpan:
    """The shared span of a process that records none."""

    __slots__ = ()

    def __enter__(self) -> "_NoSpan":
        return self

    def __exit__(self, *exc) -> None:
        return None


_NOOP = _NoSpan()


def span(name: str, trace: Optional[int] = None, **attrs: Any):
    """Context manager timing the enclosed host work as span ``name``;
    ``trace`` sets the trace id (default: the enclosing span's)."""
    if not (_on or _host_traced()):
        return _NOOP
    if not _gc_hooked:
        _hook_gc()
    return _Open(name, trace, attrs)


def _on_gc(phase: str, info: dict) -> None:
    tid = threading.get_ident()
    if phase == "start":
        if recording():
            stack = _stack()
            parent = stack[-1] if stack else None
            _gc_open[tid] = (time.perf_counter_ns(),
                             parent.span_id if parent else None,
                             parent.trace_id if parent else None)
        return
    opened = _gc_open.pop(tid, None)
    if opened is not None:
        start, parent_id, trace = opened
        _keep(Span("host.gc", next(_ids), parent_id, trace, start,
                   time.perf_counter_ns(), tid,
                   {"generation": info.get("generation")}))


def _hook_gc() -> None:
    global _gc_hooked
    with _buf_lock:
        if not _gc_hooked:
            gc.callbacks.append(_on_gc)
            _gc_hooked = True


def _unhook_gc() -> None:
    global _gc_hooked
    with _buf_lock:
        if not _gc_hooked:
            return
        gc.callbacks.remove(_on_gc)
        _gc_hooked = False
        _gc_open.clear()
