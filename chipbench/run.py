"""One run of one benchmark cell on the chip.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``) names a configuration and a traffic mix; the
run

1. builds the configuration's deployment from ``--seed``
   (``chipbench.deploy``): generate, acked ingest, flush, commit, reopen;
2. warms the cell's own shapes through the searcher and, for mixes with
   ingest, a rehearsal of the traffic itself;
3. drives a window of ``--seconds`` through ``SearchFrontend.submit``,
   ``submit_ingest`` and ``commit`` (``chipbench.drive``), with the
   profiler on under ``--trace 1``;
4. frees the system, then compares a seeded sample of the window's
   answers with the plain references (``chipbench.reference``) and reads
   every acked batch back through its probe;
5. prints, last on standard error, each compared number beside its limit,
   and last on standard output one JSON line: ``correct``, ``attempted``,
   ``failed``, ``metrics`` (end-to-end under ``--trace 0``, per-layer
   under ``--trace 1``), ``device``, ``breakdown`` (traced runs) and the
   ``checks`` it compared.

It measures only on a TPU: without one, with fewer chips than the cell
asks for, or with the kernel path forced, it exits non-zero and prints no
result.  JAX's persistent compilation cache lives in ``<checkout>/.jax_cache``
whatever the environment says, so only a checkout's first run of a cell
compiles.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()  # set-up is timed from here

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Callable, Dict, List, Optional  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import numpy as np  # noqa: E402

from chipbench import manifest as M  # noqa: E402
from chipbench import reference as R  # noqa: E402
from chipbench import trace as T  # noqa: E402
from chipbench import traffic as TR  # noqa: E402
from chipbench.corpus import marker, rng_for, token_text, word  # noqa: E402
from chipbench.drive import GRACE_S, Window, summarize  # noqa: E402
from chipbench.readings import Readings  # noqa: E402


class DeviceError(RuntimeError):
    """No accelerator to measure on (or not the one the cell asks for)."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# device, compile cache, compile counting
# ---------------------------------------------------------------------------


def check_device(chips: int, require_tpu: bool) -> dict:
    if require_tpu and os.environ.get("REPRO_FUSED_KERNEL"):
        raise DeviceError("REPRO_FUSED_KERNEL is set: the kernel path must "
                          "not be forced where it is measured")
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if require_tpu:
        if info["platform"] != "tpu":
            raise DeviceError(f"JAX found no TPU (platform "
                              f"{info['platform']!r}); refusing to measure")
        if info["count"] < chips:
            raise DeviceError(f"cell asks for {chips} chips, JAX found "
                              f"{info['count']}")
        from repro.core.query.fused import kernel_enabled
        from repro.kernels.runtime import resolve_interpret

        if not kernel_enabled(10) or resolve_interpret(None):
            raise DeviceError("the fused Pallas kernels are not compiled on "
                              "this backend")
    return info


def peaks_for(kind: str) -> dict:
    table = json.loads((M.HERE / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise DeviceError(f"device kind {kind!r} is not in chipbench/"
                          f"peaks.json")
    return table[kind]


def use_compile_cache() -> str:
    import jax

    path = str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Backend compiles and persistent-cache loads, split into set-up and
    window (JAX's monitoring events; one listener per process)."""

    _active: Optional["CompileCounter"] = None
    _registered = False

    def __init__(self) -> None:
        self.in_window = False
        self.compiles = {"setup": 0, "window": 0}
        self.compile_s = {"setup": 0.0, "window": 0.0}
        self.cache_hits = {"setup": 0, "window": 0}
        self.window_programs: Dict[str, int] = {}
        CompileCounter._active = self
        if not CompileCounter._registered:
            import jax

            jax.monitoring.register_event_duration_secs_listener(
                CompileCounter._on_duration)
            jax.monitoring.register_event_listener(CompileCounter._on_event)
            CompileCounter._registered = True

    @staticmethod
    def _on_duration(event, secs, fun_name="?", **_):
        self = CompileCounter._active
        if self is None or event != "/jax/core/compile/backend_compile_duration":
            return
        ph = "window" if self.in_window else "setup"
        self.compiles[ph] += 1
        self.compile_s[ph] += secs
        if self.in_window:
            self.window_programs[fun_name] = (
                self.window_programs.get(fun_name, 0) + 1)

    @staticmethod
    def _on_event(event, **_):
        self = CompileCounter._active
        if self is not None and event == "/jax/compilation_cache/cache_hits":
            self.cache_hits["window" if self.in_window else "setup"] += 1


# ---------------------------------------------------------------------------
# the cell's queries
# ---------------------------------------------------------------------------


def to_query(task: R.Task, field: str = "body"):
    from repro.core.query.types import (
        BooleanQuery, FacetQuery, PhraseQuery, RangeQuery, SortQuery,
        TermQuery,
    )

    def term(t):
        return TermQuery(field, word(t))

    f = task.family
    if f == "term":
        return term(task.terms[0])
    if f in ("and", "or"):
        return BooleanQuery(tuple(term(t) for t in task.terms), f)
    if f == "phrase":
        return PhraseQuery(field, tuple(word(t) for t in task.terms))
    if f == "range":
        return RangeQuery(task.field, task.lo, task.hi)
    if f == "sort":
        return SortQuery(term(task.terms[0]), task.field)
    if f == "facet":
        return FacetQuery(term(task.terms[0]) if task.terms else None,
                          task.field, task.n_bins)
    raise ValueError(f"unknown family {f}")


def ladder_queries(ladder: dict, tasks: dict) -> list:
    """One query per ladder token of each family whose live-tail program
    is shaped by its postings bucket (term, boolean AND/OR, sort), built
    like the family's tasks."""
    from repro.core.query.types import BooleanQuery, SortQuery, TermQuery

    like = {t.family: t for ts in tasks.values() for t in ts}
    out = []
    for fam, tokens in ladder.items():
        for tok in tokens:
            term = TermQuery("body", token_text(tok))
            if fam == "term":
                out.append(term)
            elif fam in ("and", "or"):
                out.append(BooleanQuery((term,) * len(like[fam].terms), fam))
            elif fam == "sort":
                out.append(SortQuery(term, like[fam].field))
    return out


def warm_shapes(searcher, queries: list, is_tasks: bool, sizes: List[int],
                max_wave: int, k: int, ladder: list) -> None:
    """Run every program shape the window can meet, through the searcher
    the front end's waves call (the dispatcher is idle meanwhile).

    Tasks: each task alone in every batch-size bucket (a family group's
    batch pads to a power of two and its postings to the bucket of its
    busiest term), each ladder query the same way for the live tail's
    buckets, and every group size for the cross-shard merge.  Vectors:
    every wave size."""
    if is_tasks:
        for q in list(queries) + list(ladder):
            for b in sizes:
                searcher.search_batch([q] * b, k)
        for n in range(1, max_wave + 1):
            if n not in sizes:
                searcher.search_batch([queries[0]] * n, k)
    else:
        for n in range(1, max_wave + 1):
            searcher.search_batch(
                [queries[(n * 7 + j) % len(queries)] for j in range(n)], k)


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


def _du(path: str) -> int:
    total = 0
    for d, _, files in os.walk(path):
        for f in files:
            try:
                total += os.path.getsize(os.path.join(d, f))
            except OSError:
                pass
    return total


def _limits(name: str) -> dict:
    path = M.HERE / "limits" / f"{name}.json"
    if not path.exists():
        raise M.ManifestError(f"missing chipbench/limits/{name}.json")
    return json.loads(path.read_text())["limits"]


class Served:
    """One cell's system, built and warmed, with the helpers a window
    needs.  ``close()`` stops the front end and the shard workers."""

    def __init__(self, name: str, cell: dict, seed: int, batches: int,
                 *, require_tpu: bool, compile_cache: bool, grace_s: float,
                 log_run: str) -> None:
        w, self.cfg, self.mix = (cell["workload"], cell["config"],
                                 cell["traffic"])
        self.seed, self.grace_s = seed, grace_s
        self.dev = check_device(w["chips"], require_tpu)
        self.peaks = peaks_for(self.dev["kind"]) if require_tpu else None
        if compile_cache:
            log(f"chipbench: compile cache {use_compile_cache()}")
        self.counter = CompileCounter()
        log(f"chipbench: {name} seed={seed} {log_run} on "
            f"{self.dev['platform']} {self.dev['kind']} x{self.dev['count']}")

        from chipbench import deploy
        from repro.core.query.types import VectorQuery
        from repro.serve.search_frontend import SearchFrontend

        mix, cfg = self.mix, self.cfg
        self.k = mix["k"]
        fe_cfg = mix["frontend"]
        warm = mix.get("warmup", {})
        self.ing = mix.get("ingest")
        self.is_tasks = mix["queries"]["kind"] == "tasks"
        rehearse_s = float(warm.get("rehearsal_s", 0)) if self.ing else 0.0
        self.next_batch = (len(TR.ingest_plan(mix, rehearse_s)["at"])
                           if self.ing else 0)
        self.tasks = (TR.build_tasks(mix, cfg["corpus"]) if self.is_tasks
                      else {})
        tail_queries: dict = {}
        if self.ing:
            for ts in self.tasks.values():
                for t in ts:
                    if t.family in ("term", "and", "or", "sort"):
                        tail_queries.setdefault(t.family, []).append(t.terms)
        self.workdir = tempfile.mkdtemp(prefix="chipbench-")
        self.dep = self.fe = None
        try:
            self.dep = deploy.build(
                cfg, mix, seed, self.next_batch + batches,
                os.path.join(self.workdir, "index"), log, tail_queries,
                self.next_batch)
            self.phases = dict(self.dep.phases)
            self.fe = SearchFrontend(
                self.dep.engine, max_wave=fe_cfg["max_wave"],
                shed_watermark=fe_cfg["shed_watermark"],
                reopen_lag_docs=fe_cfg["reopen_lag_docs"],
                reopen_lag_s=fe_cfg["reopen_lag_s"],
            )
            t0 = time.perf_counter()
            if self.is_tasks:
                warm_qs = [to_query(t) for c in sorted(self.tasks)
                           for t in self.tasks[c]]
            else:
                self.pool = TR.query_vectors(mix, self.dep.docs.vectors, seed)
                metric = cfg["vectors"]["metric"]
                self.vq = warm_qs = [VectorQuery(tuple(map(float, v)), metric)
                                     for v in self.pool]
            self.phases["queries_s"] = time.perf_counter() - t0
            t0 = time.perf_counter()
            warm_shapes(self.dep.engine.manager.searcher, warm_qs,
                        self.is_tasks, warm.get("batch_sizes", [1]),
                        fe_cfg["max_wave"], self.k,
                        ladder_queries(self.dep.ladder, self.tasks))
            self.phases["warm_sweep_s"] = time.perf_counter() - t0
            if rehearse_s > 0:
                # a short run of the mix itself: probes, acks, reopens
                t0 = time.perf_counter()
                self.window(rehearse_s, TR.S_WARM, batch0=0)[0].run(rehearse_s)
                self.fe.drain(timeout=grace_s)
                self.phases["rehearsal_s"] = time.perf_counter() - t0
        except BaseException:
            self.close()
            raise

    # -- windows ---------------------------------------------------------------
    def window(self, seconds: float, stream: int = TR.S_ARRIVALS,
               batch0: Optional[int] = None, rate: Optional[float] = None,
               commit_at: tuple = ()) -> tuple:
        """``(Window, tasks)``: a window of the cell's traffic and the task
        of each arrival (None for dense clients); ingest takes the next
        unused batches (``batch0`` overrides), ``rate`` overrides the
        mix's."""
        from repro.core.query.types import TermQuery

        dep, mix = self.dep, self.mix
        if not self.is_tasks:
            return Window(self.fe, self.k, lambda i: self.vq[i % len(self.vq)],
                          clients=mix["clients"], n_vis=_n_vis,
                          grace_s=self.grace_s), None
        if rate is not None:
            mix = dict(mix, rate_qps=rate)
        arr = TR.open_arrivals(mix, self.tasks, seconds, self.seed, stream)
        queries = [to_query(t) for t in arr.tasks]
        first = self.next_batch if batch0 is None else batch0
        plan = TR.ingest_plan(mix, seconds) if self.ing else None
        if plan is not None and batch0 is None:
            self.next_batch += len(plan["at"])

        def batch_docs(b: int) -> list:
            lo = dep.first_batch + (first + b) * dep.batch_docs
            return dep.docs.docs(lo, lo + dep.batch_docs)

        win = Window(
            self.fe, self.k, queries.__getitem__, arrivals=arr.at,
            ingest_at=None if plan is None else plan["at"],
            batch_docs=batch_docs,
            make_probe=lambda b: TermQuery("body", marker(first + b)),
            probe_hits=dep.batch_docs, commit_at=commit_at, n_vis=_n_vis,
            grace_s=self.grace_s,
        )
        return win, arr.tasks

    def close(self) -> None:
        if self.fe is not None:
            self.fe.close()
        if self.dep is not None:
            self.dep.engine.close()


def _n_vis(searcher) -> int:
    """Docs visible to an answer's bound snapshot (committed + tail)."""
    return int(searcher.total_docs)


def run_cell(manifest: dict, name: str, seed: int, seconds: float,
             trace: bool, *, require_tpu: bool = True,
             compile_cache: bool = True, cell: Optional[dict] = None,
             limits: Optional[dict] = None, metrics_dir: Path = M.HERE /
             "metrics", t_process: Optional[float] = None,
             control: bool = False,
             fault: Optional[Callable] = None,
             grace_s: float = GRACE_S) -> dict:
    """Run cell ``name`` once and return the result line's object (plus,
    under ``control``, the control's readings of the same answers).

    ``cell``/``limits`` override the manifest's files (tests at tiny
    sizes); ``fault(engine, frontend)`` breaks the system before the
    window (the tests' planted faults)."""
    from repro.core.query import profile

    t_proc = T_PROCESS if t_process is None else t_process
    cell = cell or M.cell(manifest, name)
    cfg, mix = cell["config"], cell["traffic"]
    limits = limits if limits is not None else _limits(name)
    if require_tpu and mix.get("unmeasured"):
        raise M.ManifestError(
            f"traffic {cell['workload']['traffic']!r} still holds "
            f"placeholders {mix['unmeasured']}: set them on the chip first")
    ing = mix.get("ingest")
    n_batches = len(TR.ingest_plan(mix, seconds)["at"]) if ing else 0
    S = Served(name, cell, seed, n_batches, require_tpu=require_tpu,
               compile_cache=compile_cache, grace_s=grace_s,
               log_run=f"seconds={seconds} trace={int(trace)}")
    dev, peaks, counter, phases = S.dev, S.peaks, S.counter, S.phases
    dep, is_tasks, k = S.dep, S.is_tasks, S.k
    fe = S.fe
    cap = T.Capture(os.path.join(S.workdir, "trace")) if trace else None
    snap: dict = {}
    try:
        if fault is not None:
            fault(dep.engine, fe)
        win, tasks = S.window(seconds, commit_at=tuple(
            c for c in mix.get("commit_at_s", ()) if c < seconds))

        def on_start():
            snap["fe"] = fe.stats()
            snap["disp"] = profile.snapshot()
            counter.in_window = True
            if cap:
                cap.start()
            snap["t0"] = time.perf_counter()

        def on_end():
            snap["t1"] = time.perf_counter()
            if cap:
                cap.stop()
            counter.in_window = False
            snap["fe1"] = fe.stats()
            snap["disp1"] = profile.snapshot()

        setup_s = time.perf_counter() - t_proc
        rec = win.run(seconds, on_start, on_end)
        if counter.in_window:  # the window raised before on_end
            on_end()
        fe.drain(timeout=grace_s)
        stats = summarize(rec)
        import jax

        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in jax.devices())

        # what the check needs, taken before the system is freed
        rng = rng_for(seed, TR.S_CHECK)
        served = [r for r in rec.queries if r.error is None and r.done]
        pick = rng.choice(len(served), min(mix["check_sample"], len(served)),
                          replace=False) if served else []
        sample = []
        for i in sorted(pick):
            r = served[i]
            task = (tasks[r.idx] if is_tasks
                    else R.Task("dense", "vector", vector=r.idx % len(S.vq)))
            sample.append((task, r.ticket.result_td, r.n_vis))
        wave_of_vector = {r.ticket.wave for r in served
                          if not is_tasks and r.done <= rec.end}
        probe_wrong = sum(1 for p in rec.probes
                          if p.error and "over-count" in p.error)
        for r in rec.queries + rec.probes:
            r.ticket = None
        n_shards = dep.engine.n_shards
    finally:
        S.close()
    fe = win = None
    gc.collect()

    # -- trace ------------------------------------------------------------
    red = None
    if cap:
        ev = T.load(cap.log_dir, cap.sync_ns)
        red = T.reduce(ev, snap["t0"], snap["t1"], rec.spans) if ev else None
    log(f"chipbench: disk {_du(S.workdir) / 2**30:.2f} GiB in the run's "
        f"index and trace directory")
    shutil.rmtree(S.workdir, ignore_errors=True)

    # -- correctness -------------------------------------------------------
    t0 = time.perf_counter()
    tol = limits["score_gap"]
    tally, ctally = R.Tally(), R.Tally()
    torn = 0
    base = dep.n_base + dep.n_tail
    if is_tasks:
        ref = R.TextReference(dep.docs, cfg["similarity"]["k1"],
                              cfg["similarity"]["b"])
        for task, td, nv in sample:
            if (nv - base) % dep.batch_docs or nv < base:
                torn += 1
                continue
            a = ref.answer(task, nv, k)
            tally.add(td, a, task.family, tol, f"{task.category} {task}")
            if control:
                ctally.add(ref.control(task, nv, k), a, task.family, tol,
                           f"control {task.category}")
    else:
        ref = R.VectorReference(dep.docs.vectors)
        qs = np.stack([S.pool[t.vector] for t, _, _ in sample]) if sample \
            else np.zeros((0, 1), np.float32)
        nv = sample[0][2] if sample else 0
        if any(s[2] != nv for s in sample):
            torn += 1
        answers = ref.answers(qs, nv, k)
        for (task, td, _), a in zip(sample, answers):
            tally.add(td, a, "vector", tol, f"vector row {task.vector}")
        if control:
            for a_c, a in zip(ref.answers(qs, nv, k, control=True), answers):
                ctally.add(a_c, a, "vector", tol, "control vector")
    check_s = time.perf_counter() - t0

    unseen = sum(1 for b in rec.batches
                 if b.acked and not b.seen)
    checks = {
        "score_gap": [tally.score_gap, limits["score_gap"]],
        "hits_wrong": [tally.hits_wrong, limits["hits_wrong"]],
        "ids_wrong": [tally.ids_wrong, limits["ids_wrong"]],
        "facets_wrong": [tally.facets_wrong, limits["facets_wrong"]],
        "torn_snapshots": [torn, limits["torn_snapshots"]],
    }
    if ing:
        checks["acked_unseen"] = [unseen, limits["acked_unseen"]]
        checks["probe_overcount"] = [probe_wrong, limits["probe_overcount"]]
        checks["commit_errors"] = [len(stats["commit_errors"]),
                                   limits["commit_errors"]]
    correct = bool(tally.checked) and all(v <= lim
                                          for v, lim in checks.values())
    log(f"chipbench: {tally.checked} of {mix['check_sample']} sampled "
        f"answers checked")
    for ex in tally.examples:
        log(f"chipbench: mismatch: {ex}")

    # -- metrics -----------------------------------------------------------
    attempted = stats["queries"] + len(rec.batches)
    failed = stats["failed_queries"] + stats["failed_batches"]
    if trace:
        fe0, fe1 = snap["fe"], snap["fe1"]
        r = Readings(
            frontend={kk: fe1[kk] - fe0[kk]
                      for kk in ("waves", "wave_queries", "queries")},
            dispatches={kk: v - snap["disp"].get(kk, 0)
                        for kk, v in snap["disp1"].items()},
            commit_ms=stats["commit_ms"],
            trace=red, peaks=peaks,
            vector_waves=len(wave_of_vector),
            vector_rows=(dep.n_base if not is_tasks else 0),
            vector_dim=(cfg["vectors"]["dim"] if cfg.get("vectors") else 0),
        )
        metrics = M.read_per_layer(
            M.metrics_for(manifest, name, "per_layer"), r, metrics_dir)
    else:
        values = {
            "qps": stats["qps"],
            "query_p95_ms": stats["query_p95_ms"],
            "ack_p95_ms": stats["ack_p95_ms"],
            "visible_p95_ms": stats["visible_p95_ms"],
            "setup_s": setup_s,
        }
        metrics = {}
        for m in M.metrics_for(manifest, name, "end_to_end"):
            v = values.get(m["name"])
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    device = dict(dev, memory_peak_bytes=int(peak))
    if trace:
        device["busy_s"] = red["busy_s"] if red else 0.0
        device["window_s"] = red["window_s"] if red else seconds

    # -- earlier lines: set-up phases, compiles, host-side notes ----------
    log("chipbench: setup_s " + json.dumps(
        dict({kk: round(v, 3) for kk, v in phases.items()},
             total=round(setup_s, 3))))
    log(f"chipbench: compiles setup={counter.compiles['setup']} "
        f"({counter.compile_s['setup']:.1f}s, "
        f"{counter.cache_hits['setup']} cache loads) window="
        f"{counter.compiles['window']} ({counter.compile_s['window']:.1f}s, "
        f"{counter.cache_hits['window']} cache loads) "
        f"window programs {counter.window_programs}")
    log("chipbench: window " + json.dumps({
        kk: stats[kk] for kk in ("queries", "served", "probes",
                                 "failed_queries", "failed_batches",
                                 "sender_late_p95_ms", "sender_late_max_ms",
                                 "commit_ms")}))
    log(f"chipbench: shards {n_shards}, reference check {check_s:.1f}s over "
        f"{tally.checked} answers")
    if red:
        log("chipbench: trace " + json.dumps(T.breakdown(red)))
    out = {
        "correct": correct,
        "attempted": int(attempted),
        "failed": int(failed),
        "metrics": metrics,
        "device": device,
    }
    if trace and red:
        out["breakdown"] = T.breakdown(red)
    if control:
        # the control's answers held to the same limits: it must come out
        # not correct where the system's come out correct
        cchecks = {kk: [getattr(ctally, kk), limits[kk]] for kk in
                   ("score_gap", "hits_wrong", "ids_wrong", "facets_wrong")}
        out["control"] = {
            "correct": bool(ctally.checked) and all(
                v <= lim for v, lim in cchecks.values()),
            "checked": ctally.checked, "checks": cchecks,
        }
    out["checks"] = checks  # last: each compared number beside its limit
    # limits last on standard error
    for key, (v, lim) in checks.items():
        log(f"check {key} {v} limit {lim}")
    return out


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        manifest = M.load_manifest()
        out = run_cell(manifest, args.workload, args.seed, args.seconds,
                       bool(args.trace))
    except (DeviceError, M.ManifestError) as exc:
        log(f"chipbench: refused: {exc}")
        return 2
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
