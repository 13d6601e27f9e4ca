"""Drive one measured window through the system's serving entry points
(``SearchFrontend.submit``, ``submit_ingest`` and ``commit``) and record,
on the host clock, what every client saw.

Open loop: a sender thread submits each query at its scheduled time and
never waits; a query's latency runs from its scheduled send to its
completion (coordinated omission counted, as ``serve_bench.py`` does).
Ingest, when the mix has it, submits one acked batch at each scheduled
time; at its ack a probe ``TermQuery(marker)`` goes through the front end
and is re-issued as each answer returns until the answer holds the whole
batch.  Commits run at fixed offsets.

Closed loop: each client thread sends, waits for the answer, and sends
again until the window closes.

One collector thread waits on every ticket in submission order (the
dispatcher serves its queue in order, so this observes completions as
they happen) and stamps its completion time.

Every interval a client had outstanding is also kept as a host span
(query, probe, ingest op, commit) for labelling the device trace's idle
gaps.
"""

from __future__ import annotations

import collections
import dataclasses
import threading
import time
from typing import Callable, Dict, List, Optional

import numpy as np

#: a client waits this long past the window's close for late answers
#: (the run's default; a planted-fault test shortens it)
GRACE_S = 60.0


@dataclasses.dataclass
class Rec:
    kind: str  # "q" query | "p" probe
    idx: int  # arrival index (q) or batch number (p)
    sched: float  # due time (perf_counter s)
    sent: float = 0.0
    done: float = 0.0
    ticket: object = None
    error: Optional[str] = None
    n_vis: int = 0  # docs visible to the answer's bound snapshot


@dataclasses.dataclass
class Batch:
    idx: int
    sched: float
    sent: float = 0.0
    acked: float = 0.0
    seen: float = 0.0
    probes: int = 0
    error: Optional[str] = None


@dataclasses.dataclass
class Recording:
    start: float = 0.0
    end: float = 0.0
    queries: List[Rec] = dataclasses.field(default_factory=list)
    probes: List[Rec] = dataclasses.field(default_factory=list)
    batches: List[Batch] = dataclasses.field(default_factory=list)
    commits: List[tuple] = dataclasses.field(default_factory=list)
    sender_late_s: List[float] = dataclasses.field(default_factory=list)
    spans: List[tuple] = dataclasses.field(default_factory=list)


class Window:
    """One window over an open ``SearchFrontend``.

    ``make_query(i)`` returns arrival ``i``'s query object (open loop) or
    client traffic's next query (closed loop, ``i`` counts sends);
    ``make_probe(b)`` batch ``b``'s probe; ``batch_docs(b)`` its docs.
    """

    def __init__(self, fe, k: int, make_query: Callable[[int], object],
                 arrivals: Optional[np.ndarray] = None,
                 clients: int = 0,
                 ingest_at: Optional[np.ndarray] = None,
                 batch_docs: Optional[Callable[[int], list]] = None,
                 make_probe: Optional[Callable[[int], object]] = None,
                 probe_hits: int = 0,
                 commit_at: tuple = (),
                 n_vis: Optional[Callable[[object], int]] = None,
                 grace_s: float = GRACE_S) -> None:
        self.fe = fe
        self.grace_s = grace_s
        self.k = k
        self.make_query = make_query
        self.arrivals = arrivals
        self.clients = clients
        self.ingest_at = ingest_at
        self.batch_docs = batch_docs
        self.make_probe = make_probe
        self.probe_hits = probe_hits
        self.commit_at = tuple(commit_at)
        self.n_vis = n_vis or (lambda searcher: 0)
        self.rec = Recording()
        self._lock = threading.Lock()  # submission order == collector order
        self._pending: "collections.deque[Rec]" = collections.deque()
        self._cv = threading.Condition()
        self._open = 0  # batches whose probes are not finished
        self._senders_done = threading.Event()

    # -- submission ------------------------------------------------------------
    def _submit(self, r: Rec, query) -> None:
        from repro.serve.search_frontend import OverloadError

        with self._lock:
            r.sent = time.perf_counter()
            try:
                r.ticket = self.fe.submit(query, self.k)
            except OverloadError as exc:
                r.error = f"shed: {exc}"
                r.done = r.sent
                return
            with self._cv:
                self._pending.append(r)
                self._cv.notify()

    def _open_sender(self) -> None:
        t0 = self.rec.start
        for i, off in enumerate(self.arrivals):
            due = t0 + float(off)
            wait = due - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            r = Rec("q", i, due)
            self.rec.queries.append(r)
            self._submit(r, self.make_query(i))
            self.rec.sender_late_s.append(r.sent - due)

    def _closed_client(self, cid: int, counter: List[int]) -> None:
        end = self.rec.end
        while True:
            with self._lock:
                i = counter[0]
                counter[0] += 1
            now = time.perf_counter()
            if now >= end:
                return
            r = Rec("q", i, now)
            self.rec.queries.append(r)
            self._submit(r, self.make_query(i))
            if r.ticket is None:
                continue
            r.ticket._done.wait(self.grace_s + end - now)

    def _ingester(self) -> None:
        t0 = self.rec.start
        for b, off in enumerate(self.ingest_at):
            due = t0 + float(off)
            wait = due - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            bt = Batch(b, due)
            self.rec.batches.append(bt)
            with self._cv:
                self._open += 1
            bt.sent = time.perf_counter()
            try:
                ticket = self.fe.submit_ingest(self.batch_docs(b),
                                               timeout=self.grace_s)
            except Exception as exc:  # noqa: BLE001 — a refused batch fails
                bt.error = f"submit_ingest: {type(exc).__name__}: {exc}"
                self._close_batch()
                continue
            threading.Thread(
                target=self._await_ack, args=(bt, ticket), daemon=True,
                name="chipbench-ack",
            ).start()

    def _await_ack(self, bt: Batch, ticket) -> None:
        try:
            ticket.result(timeout=self.grace_s + 30.0)
        except Exception as exc:  # noqa: BLE001 — an unacked batch fails
            bt.error = f"ack: {type(exc).__name__}: {exc}"
            self._close_batch()
            return
        bt.acked = time.perf_counter()
        self.rec.spans.append(("ingest", bt.sent, bt.acked))
        self._probe(bt)

    def _probe(self, bt: Batch) -> None:
        bt.probes += 1
        r = Rec("p", bt.idx, time.perf_counter())
        self.rec.probes.append(r)
        self._submit(r, self.make_probe(bt.idx))
        if r.ticket is None:  # shed: try again after a beat
            threading.Timer(0.01, self._probe, args=(bt,)).start()

    def _close_batch(self) -> None:
        with self._cv:
            self._open -= 1
            self._cv.notify_all()

    def _committer(self) -> None:
        t0 = self.rec.start
        for off in self.commit_at:
            due = t0 + off
            wait = due - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            c0 = time.perf_counter()
            try:
                self.fe.commit(timeout=self.grace_s)
                err = None
            except Exception as exc:  # noqa: BLE001 — recorded, run fails
                err = f"{type(exc).__name__}: {exc}"
            c1 = time.perf_counter()
            self.rec.commits.append((c0, c1, err))
            self.rec.spans.append(("commit", c0, c1))

    # -- completion ------------------------------------------------------------
    def _collector(self, deadline: float) -> None:
        batches = {}
        while True:
            with self._cv:
                while not self._pending:
                    if self._senders_done.is_set() and self._open <= 0:
                        return
                    if time.perf_counter() > deadline:
                        return
                    self._cv.wait(0.05)
                r = self._pending.popleft()
            left = deadline - time.perf_counter()
            if not r.ticket._done.wait(max(left, 0.0)):
                r.error = "no answer within the grace period"
                continue
            r.done = time.perf_counter()
            if r.ticket.error is not None:
                r.error = f"{type(r.ticket.error).__name__}: {r.ticket.error}"
            else:
                r.n_vis = self.n_vis(r.ticket.searcher)
            self.rec.spans.append(("probe" if r.kind == "p" else "query",
                                   r.sent, r.done))
            if r.kind != "p":
                continue
            if not batches:
                batches = {b.idx: b for b in self.rec.batches}
            bt = batches.get(r.idx) or next(
                b for b in self.rec.batches if b.idx == r.idx)
            batches[r.idx] = bt
            hits = -1 if r.error else r.ticket.result_td.total_hits
            if hits == self.probe_hits:
                bt.seen = r.done
                self._close_batch()
            elif hits > self.probe_hits:
                r.error = f"probe over-count: {hits} > {self.probe_hits}"
                bt.error = r.error
                self._close_batch()
            elif time.perf_counter() < deadline:
                self._probe(bt)
            else:
                bt.error = "acked batch never seen by a probe"
                self._close_batch()

    # -- run -------------------------------------------------------------------
    def run(self, seconds: float, on_start: Callable[[], None] = lambda: None,
            on_end: Callable[[], None] = lambda: None) -> Recording:
        on_start()
        self.rec.start = time.perf_counter()
        self.rec.end = self.rec.start + seconds
        deadline = self.rec.end + self.grace_s
        threads = []
        if self.arrivals is not None:
            threads.append(threading.Thread(target=self._open_sender))
        counter = [0]
        for c in range(self.clients):
            threads.append(threading.Thread(
                target=self._closed_client, args=(c, counter)))
        if self.ingest_at is not None:
            threads.append(threading.Thread(target=self._ingester))
        if self.commit_at:
            threads.append(threading.Thread(target=self._committer))
        collector = threading.Thread(target=self._collector, args=(deadline,))
        collector.start()
        for t in threads:
            t.start()
        left = self.rec.end - time.perf_counter()
        if left > 0:
            time.sleep(left)
        on_end()
        for t in threads:
            t.join(self.grace_s)
        self._senders_done.set()
        with self._cv:
            self._cv.notify_all()
        collector.join(self.grace_s + 30.0)
        for bt in self.rec.batches:
            if bt.error is None and not bt.seen:
                bt.error = "acked batch never seen by a probe"
        return self.rec


# ---------------------------------------------------------------------------
# window statistics (host clock)
# ---------------------------------------------------------------------------


def p95_ms(values_s: List[float]) -> Optional[float]:
    """95th percentile (numpy's linear interpolation) in ms, or None."""
    if not values_s:
        return None
    return float(np.percentile(np.asarray(values_s) * 1e3, 95))


def summarize(rec: Recording) -> Dict[str, object]:
    """End-to-end numbers of one window plus the failure count."""
    served = [r for r in rec.queries if r.error is None and r.done]
    in_window = [r for r in served if r.done <= rec.end]
    out: Dict[str, object] = {
        "queries": len(rec.queries),
        "served": len(served),
        "qps": len(in_window) / (rec.end - rec.start),
        "query_p95_ms": p95_ms([r.done - r.sched for r in served]),
        "ack_p95_ms": p95_ms(
            [b.acked - b.sched for b in rec.batches if b.acked]),
        "visible_p95_ms": p95_ms(
            [b.seen - b.acked for b in rec.batches if b.seen]),
        "commit_ms": [1e3 * (c1 - c0) for c0, c1, _ in rec.commits],
        "failed_queries": len(rec.queries) - len(served),
        "failed_batches": sum(1 for b in rec.batches if b.error),
        "commit_errors": [e for _, _, e in rec.commits if e],
        "sender_late_p95_ms": p95_ms(rec.sender_late_s),
        "sender_late_max_ms": (
            1e3 * max(rec.sender_late_s) if rec.sender_late_s else None),
        "probes": len(rec.probes),
    }
    return out
