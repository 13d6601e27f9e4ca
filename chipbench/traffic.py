"""The one traffic generator: every mix is a JSON file of parameters under
``chipbench/traffic/`` and this module turns it, with ``--seed``, into the
work of one run.

A mix names:

* ``loop``: ``"open"`` (``rate_qps``, arrivals as a Poisson process
  conditioned on its count, so every seed sends the same number of
  queries in another order) or ``"closed"`` (``clients`` that each wait
  for their answer before sending again);
* ``queries``: ``{"kind": "tasks", ...}`` (luceneutil task categories over
  term-frequency bands, equal weights) or ``{"kind": "vectors", ...}``
  (dense query vectors: near-duplicates of stored passages plus random
  directions);
* optionally ``ingest`` (acked batches at a fixed rate, each batch's docs
  carrying a marker token that read-back probes search for) and
  ``commit_at_s`` (``commit()`` calls at fixed offsets in the window).

Task terms are fixed by the mix (``task_seed``), not by the run seed, as
luceneutil fixes its task file: the run seed draws the corpus, the order
and timing of arrivals and which of a category's tasks each arrival is.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

import numpy as np

from chipbench.corpus import rng_for, zipf_folded_cdf
from chipbench.reference import Task

#: rng streams of one run (``corpus.rng_for(seed, stream)``); stream 3 is
#: unused and kept so the other streams draw as in the measured runs
S_BASE, S_TAIL, S_ARRIVALS, _, S_QUERIES, S_CHECK, S_WARM = range(7)


def band_terms(corpus_cfg: dict, bands: Dict[str, list], task_seed: int,
               ) -> Dict[str, np.ndarray]:
    """Token ids whose expected document frequency (share of docs holding
    the token in the body) lies in each band ``[lo, hi)``, from the
    generator's own law: ``1 - E[(1 - p)^L]`` over the length law."""
    p = np.diff(np.concatenate(
        [[0.0], zipf_folded_cdf(corpus_cfg["zipf_a"], corpus_cfg["vocab"])]))
    lens = np.maximum(4, rng_for(task_seed, 0).lognormal(
        np.log(corpus_cfg["mean_len"]), 0.5, size=1024).astype(np.int64))
    log1m = np.log1p(-p)
    share = 1.0 - np.exp(np.outer(log1m, lens)).mean(axis=1)
    return {
        name: np.flatnonzero((share >= lo) & (share < hi))
        for name, (lo, hi) in bands.items()
    }


def build_tasks(mix: dict, corpus_cfg: dict) -> Dict[str, List[Task]]:
    """``tasks_per_category`` fixed tasks for every category of a task mix
    (drawn from ``task_seed``, the same in every run)."""
    q = mix["queries"]
    terms = band_terms(corpus_cfg, q["bands"], q["task_seed"])
    rng = rng_for(q["task_seed"], 1)
    out: Dict[str, List[Task]] = {}
    for cat, spec in q["categories"].items():
        fam = spec["family"]
        tasks = []
        for _ in range(q["tasks_per_category"]):
            toks = []
            for band in spec.get("bands", ()):
                pool = [t for t in terms[band] if t not in toks]
                toks.append(int(rng.choice(pool)))
            lo = hi = 0
            if fam == "range":
                dom = spec["domain"]
                width = int(rng.uniform(*spec["width"]) * dom)
                lo = int(rng.integers(0, dom - width))
                hi = lo + width
            tasks.append(Task(cat, fam, tuple(toks), spec.get("field", ""),
                              lo, hi, spec.get("n_bins", 0)))
        out[cat] = tasks
    return out


@dataclasses.dataclass
class Arrivals:
    """Open-loop schedule: offsets (s) from the window start and tasks."""

    at: np.ndarray
    tasks: List[Task]


def open_arrivals(mix: dict, tasks: Dict[str, List[Task]], seconds: float,
                  seed: int, stream: int = S_ARRIVALS) -> Arrivals:
    """``round(rate * seconds)`` arrivals, uniform order statistics over the
    window (a Poisson process given its count); every category the same
    share, in a seeded order."""
    rng = rng_for(seed, stream)
    n = int(round(mix["rate_qps"] * seconds))
    at = np.sort(rng.uniform(0.0, seconds, size=n))
    cats = sorted(tasks)
    per = np.full(len(cats), n // len(cats))
    per[rng.choice(len(cats), n - per.sum(), replace=False)] += 1
    labels = np.repeat(np.arange(len(cats)), per)
    rng.shuffle(labels)
    picks = rng.integers(0, 1 << 30, size=n)
    chosen = [
        tasks[cats[c]][int(p) % len(tasks[cats[c]])]
        for c, p in zip(labels, picks)
    ]
    return Arrivals(at, chosen)


def query_vectors(mix: dict, vectors: np.ndarray, seed: int) -> np.ndarray:
    """Pool of dense query vectors: ``near_dup_share`` of them a stored
    passage's vector plus ``noise`` Gaussian noise, the rest random
    directions, shuffled."""
    q = mix["queries"]
    rng = rng_for(seed, S_QUERIES)
    n = q["pool"]
    n_dup = int(round(q["near_dup_share"] * n))
    rows = rng.choice(len(vectors), n_dup, replace=False)
    dim = vectors.shape[1]
    pool = np.concatenate([
        vectors[rows] + q["noise"] * rng.standard_normal((n_dup, dim)),
        rng.standard_normal((n - n_dup, dim)),
    ]).astype(np.float32)
    return pool[rng.permutation(n)]


def ingest_plan(mix: dict, seconds: float) -> Optional[dict]:
    """Batch offsets (s) from the window start, ``None`` without ingest."""
    ing = mix.get("ingest")
    if not ing:
        return None
    every = ing["batch_docs"] / ing["docs_per_s"]
    n = int(np.floor(seconds / every + 1e-9))
    return {"at": np.arange(n) * every, "batch_docs": ing["batch_docs"]}
