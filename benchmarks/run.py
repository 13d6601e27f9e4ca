"""Benchmark orchestrator — one module per paper figure.

  commit_bench  — Fig 3: commit time vs commit frequency (SSD/PMEM/byte)
  search_bench  — Fig 5: per-family search QPS, hot vs cold page cache
  nrt_bench     — Fig 4: NRT QPS + reopen time vs commit frequency
  ingest_bench  — sustained ingest: lifecycle metrics + pipeline docs/sec
  serve_bench   — closed-loop serving: coalesced waves vs sequential
                  dispatch, offered vs achieved QPS, overload shedding
  kernel_bench  — Pallas kernel microbench + analytic TPU roofline
  embedbag_bench— EmbeddingBag substrate op scaling

Prints ``name,param,us_per_call,derived`` CSV lines.
Run: PYTHONPATH=src python -m benchmarks.run [--only commit|search|nrt|ingest|kernel|embed]

``--smoke`` is the CI perf-trajectory entry point: it runs the small
ingest configuration (with its loud lifecycle/throughput regression
gates) and writes ``BENCH_ingest.json`` — docs/sec, flush/commit latency,
and durability-barrier counts per directory kind — then the search smoke
(``search_bench.run_smoke``) which writes ``BENCH_search.json`` — batched
vs fused QPS, per-query latency percentiles, dispatch counts and the
fused-path roofline — both uploaded by CI as artifacts so every PR
appends a point to the perf record.
"""

import argparse
import json
import sys
import time

BENCH_INGEST_JSON = "BENCH_ingest.json"
BENCH_SEARCH_JSON = "BENCH_search.json"


def run_smoke(out_path: str = BENCH_INGEST_JSON) -> dict:
    """Small ingest benchmark -> BENCH_ingest.json (raises on regression)."""
    from benchmarks import commit_bench, ingest_bench

    lifecycle = ingest_bench.run(smoke=True)
    pipeline = ingest_bench.run_pipeline(smoke=True)
    wal = commit_bench.run_wal(docs_per_commit=500, n_docs=1500)
    payload = {
        "bench": "ingest",
        "mode": "smoke",
        "kinds": {
            r["dir"]: {
                "docs_per_sec": round(r["docs_per_sec"], 1),
                "flush_mean_ms": round(r["flush_mean_ms"], 3),
                "merge_total_ms": round(r["merge_total_ms"], 3),
                "commit_mean_ms": round(r["commit_mean_ms"], 3),
                "commits": r["commits"],
                **(
                    {
                        "barriers": r["barriers"],
                        "barriers_per_commit": round(r["barriers_per_commit"], 3),
                    }
                    if "barriers" in r
                    else {}
                ),
            }
            for r in pipeline
            if r["path"] == "columnar"
        },
        "speedup_vs_reference_ram": round(
            ingest_bench.pipeline_speedup(pipeline), 2
        ),
        "lifecycle": {
            r["dir"]: {
                "segments": r["segments"],
                "merges": r["merges"],
                "storage_ratio": round(r["storage_ratio"], 3),
                "reopen_mean_ms": round(r["reopen_mean_ms"], 3),
            }
            for r in lifecycle
        },
        # the durable ingest buffer (ack = durable, commit = publish):
        # ack latency per batch + the WAL-vs-non-WAL byte-path commit gap
        "wal": {
            "wal_ack_us": round(wal["wal"]["wal_ack_us"], 1),
            "commit_us": round(wal["wal"]["commit_us"], 1),
            "commit_us_nonwal": round(wal["base"]["commit_us"], 1),
            "commit_speedup": round(wal["commit_speedup"], 2),
            "barriers_per_batch": round(wal["barriers_per_batch"], 3),
        },
        # the DWPT writer-parallelism rows land in the same file via the
        # CI job's `ingest_bench --shards 2 --smoke` step (one measurement,
        # one writer: ingest_bench.append_sharded_json)
    }
    with open(out_path, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
        f.write("\n")
    # the printable gates (raises SystemExit on regression); reuses the
    # rows measured above rather than re-running the benchmark
    for line in ingest_bench.main(smoke=True, rows=lifecycle, pipe=pipeline):
        print(line, flush=True)
    w = payload["wal"]
    print(
        f"commit_wal_smoke,byte-pmem,{w['commit_us']:.0f},us_per_commit"
        f";nonwal={w['commit_us_nonwal']:.0f}"
        f",speedup={w['commit_speedup']:.2f}"
        f",wal_ack_us={w['wal_ack_us']:.0f}"
        f",barriers_per_batch={w['barriers_per_batch']:.2f}",
        flush=True,
    )
    # WAL gates: commit = publish must beat the non-WAL byte path >=1.5x,
    # and an ack must cost exactly one durability barrier
    if w["commit_speedup"] < 1.5:
        raise SystemExit(
            f"commit_bench regression: WAL commit only "
            f"{w['commit_speedup']:.2f}x the non-WAL byte path (need >=1.5)"
        )
    if not 0.99 <= w["barriers_per_batch"] <= 1.01:
        raise SystemExit(
            f"commit_bench regression: {w['barriers_per_batch']:.2f} "
            f"barriers per acked batch (need exactly 1)"
        )
    print(f"# wrote {out_path}", file=sys.stderr)
    return payload


def run_smoke_search(out_path: str = BENCH_SEARCH_JSON) -> dict:
    """Search smoke -> BENCH_search.json (raises when the fused path loses
    its >=2x batched-term margin over the unfused executors, when the
    search-at-ack live path loses its >=10x ack-to-visible margin over
    flush-reopen, when live==flush parity breaks, or when the serving
    front end's coalesced waves lose to sequential dispatch at the tail /
    overload fails to shed-and-bound)."""
    from benchmarks import nrt_bench, search_bench, serve_bench

    search_bench.run_smoke(out_path)
    # merges the nrt_ack_to_visible_us / live_search_parity rows into the
    # same file (and enforces its own loud gates)
    nrt_bench.run_smoke(out_path)
    # merges the closed-loop serving rows (coalescing + overload gates)
    payload = serve_bench.run_smoke(out_path)
    print(f"# wrote {out_path}", file=sys.stderr)
    return payload


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument(
        "--smoke",
        action="store_true",
        help="CI mode: small ingest config, writes BENCH_ingest.json",
    )
    args = ap.parse_args()

    from repro.kernels.runtime import use_compile_cache

    use_compile_cache()
    if args.smoke:
        run_smoke()
        run_smoke_search()
        return

    from benchmarks import commit_bench, ingest_bench, kernel_bench
    from benchmarks import embedbag_bench, nrt_bench, search_bench

    suites = {
        "commit": commit_bench.main,
        "search": search_bench.main,
        "nrt": nrt_bench.main,
        "ingest": ingest_bench.main,
        "kernel": kernel_bench.main,
        "embed": embedbag_bench.main,
    }
    if args.only:
        suites = {args.only: suites[args.only]}

    print("name,param,us_per_call,derived")
    for name, fn in suites.items():
        t0 = time.time()
        try:
            for line in fn():
                print(line, flush=True)
        except Exception as e:  # a failing suite must not hide the others
            print(f"{name},ERROR,0,{type(e).__name__}:{e}", flush=True)
            raise
        print(f"# {name} done in {time.time()-t0:.1f}s", file=sys.stderr)


if __name__ == "__main__":
    main()
