"""Find the knee of an open-loop cell: the highest offered query rate the
system sustains, with the cell's ingest stream and commit cadence on,
without a growing backlog.

    python3 chipbench/sweep.py --workload <cell> --seed <n> --rates 20,30,40 --seconds 14 --commit-at 1

One deployment is built and warmed as a run builds it; then one window
per rate, in the order given, each with the cell's ingest and probes and
one ``commit()`` near its start, so that a window as long as the cell's
commit interval shows whether the queue a commit leaves behind clears
before the next one.  A rate is sustained when

* no query was shed or failed, and every acked batch was read back;
* the last answer due in the window arrived within 1 s of its close;
* the last ack of a batch due in the window arrived within 1 s of its
  close: the writer ops share the one dispatcher with the waves, so a
  query rate can leave every answer on time while the ack queue grows
  all through the window.

Each window also reports the queries that arrived during its longest
commit and the most queries outstanding at once (an unanswered one counts
until the grace period ends): the readings the cell's ``shed_watermark``
is set from.

Each window prints one JSON line; the last line names the knee, the
highest sustained rate below the first that was not.  Runs only on a TPU,
like ``run.py``; the rate and watermark found go into the cell's traffic
file by hand, and off its ``unmeasured`` list.
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import List, Optional

import numpy as np

from run import DeviceError, GRACE_S, M, Served, log, summarize  # noqa: E402


def max_outstanding(queries, until: float) -> int:
    """Most queries sent and not yet answered at any one time; a query
    never answered is outstanding until ``until``, and a shed one (its
    ``done`` stamped at its send) never is."""
    sent = [r for r in queries if r.sent and r.done != r.sent]
    ev = sorted([(r.sent, 1) for r in sent]
                + [(r.done or until, -1) for r in sent])
    return int(max(np.cumsum([d for _, d in ev]), default=0))


def commit_arrivals(rec) -> int:
    """Queries due while the longest of the window's commits ran."""
    if not rec.commits:
        return 0
    c0, c1, _ = max(rec.commits, key=lambda c: c[1] - c[0])
    return sum(1 for r in rec.queries if c0 <= r.sched <= c1)


def sustained(rec, stats) -> dict:
    ok = [r for r in rec.queries if r.error is None and r.done]
    last = sorted(ok, key=lambda r: r.sched)[-max(1, len(ok) // 4):]
    drain = max((r.done for r in ok), default=rec.end) - rec.end
    ack_drain = max((b.acked for b in rec.batches if b.acked),
                    default=rec.end) - rec.end
    verdict = (stats["failed_queries"] == 0 and stats["failed_batches"] == 0
               and drain <= 1.0 and ack_drain <= 1.0)
    return {"p50_last_quarter_ms": 1e3 * float(np.median(
                [r.done - r.sched for r in last])) if last else None,
            "drain_after_close_s": drain, "ack_drain_after_close_s": ack_drain,
            "max_outstanding": max_outstanding(rec.queries + rec.probes,
                                               rec.end + GRACE_S),
            "commit_arrivals": commit_arrivals(rec),
            "sustained": bool(verdict)}


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True,
                    help="comma-separated offered rates (queries/s)")
    ap.add_argument("--seconds", type=float, default=14.0)
    ap.add_argument("--commit-at", type=float, default=1.0,
                    help="offset of the window's commit (s)")
    args = ap.parse_args(argv)
    rates = [float(x) for x in args.rates.split(",")]
    try:
        cell = M.cell(M.load_manifest(), args.workload)
    except M.ManifestError as exc:
        log(f"chipbench: refused: {exc}")
        return 2
    mix = cell["traffic"]
    if mix["loop"] != "open":
        log("chipbench: a knee is swept for open-loop cells only")
        return 2
    plan_per = (int(args.seconds * mix["ingest"]["docs_per_s"]
                    / mix["ingest"]["batch_docs"]) if mix.get("ingest") else 0)
    try:
        S = Served(args.workload, cell, args.seed, plan_per * len(rates),
                   require_tpu=True, compile_cache=True, grace_s=GRACE_S,
                   log_run=f"sweep {rates} x {args.seconds}s")
    except DeviceError as exc:
        log(f"chipbench: refused: {exc}")
        return 2
    knee = None
    try:
        for rate in rates:
            win, _ = S.window(args.seconds, rate=rate,
                              commit_at=(args.commit_at,))
            before = S.counter.compiles["window"]
            S.counter.in_window = True
            rec = win.run(args.seconds)
            S.counter.in_window = False
            S.fe.drain(timeout=GRACE_S)
            stats = summarize(rec)
            verdict = sustained(rec, stats)
            line = dict(
                rate_qps=rate,
                served_qps=stats["served"] / args.seconds,
                **{k: stats[k] for k in ("query_p95_ms", "ack_p95_ms",
                                         "visible_p95_ms", "failed_queries",
                                         "failed_batches",
                                         "sender_late_p95_ms")},
                commit_ms=stats["commit_ms"],
                **verdict,
                window_compiles=S.counter.compiles["window"] - before,
            )
            print(json.dumps(line), flush=True)
            if not verdict["sustained"]:
                break
            knee = rate
    finally:
        S.close()
    print(json.dumps({"knee_qps": knee}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
