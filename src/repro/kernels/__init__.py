"""Pallas TPU kernels for the perf-critical hot spots.

  fused_exec  — fused per-family query execution (search hot loop)
  vector_topk — dense-vector / hybrid similarity top-k
  bitset      — packed-bitmap boolean combine + popcount (filter hot loop)
  decode_attn — grouped-query flash-decode (KV-segment serving hot loop)

``bitset`` and ``decode_attn`` have pure-jnp oracles in ``ref.py`` and jit'd
wrappers in ``ops.py``; the fused kernels are driven by
``repro.core.query.fused`` and checked against ``core/query/exec.py`` and
``search_single``.  Kernels are interpreted only where the backend has no
Pallas compiler (``runtime.resolve_interpret``).
"""
