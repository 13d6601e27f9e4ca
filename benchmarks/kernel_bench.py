"""Kernel microbenchmarks: Pallas (interpret) vs jnp oracle wall-clock on
CPU, plus the analytic TPU roofline for each kernel's shapes.

Wall-clock on CPU is NOT the score (the target is TPU); the derived column
reports bytes-touched and the v5e roofline time =
max(flops/197T, bytes/819G) for the kernel's tile schedule.
"""

from __future__ import annotations

import time
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import fused_exec as fk
from repro.kernels import ops
from repro.kernels.runtime import has_compiled_backend

PEAK = 197e12
BW = 819e9


def _time(fn, *args, reps=5):
    fn(*args)  # warm/compile
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / reps


def main(smoke: bool = False) -> List[str]:
    """``smoke`` drops to the small shape per kernel and 2 reps — the CI
    row exists to prove the kernels still run end-to-end and keep their
    roofline columns populated, not to produce stable CPU timings."""
    reps = 2 if smoke else 5
    rng = np.random.default_rng(0)
    out = []

    # fused term executor kernel: packed dl|live words of the gathered
    # postings + freqs tiles -> BM25 + live mask + per-block top-k in one
    # pallas_call (the doc-side gather runs in the caller's XLA prologue)
    for bsz, p in ((8, 4096),) if smoke else ((8, 4096), (32, 8192)):
        f_g = jnp.asarray(
            (rng.integers(10, 500, (bsz, p)) << 1 | 1).astype(np.int32)
        )
        f_freqs = jnp.asarray(rng.integers(1, 30, (bsz, p)).astype(np.int32))
        idfs = jnp.asarray(rng.uniform(0.5, 4.0, bsz).astype(np.float32))
        interp = not has_compiled_backend()
        t = _time(
            lambda: fk.term_topk_tiles(
                f_g, f_freqs, idfs, 120.0, 0.9, 0.4, 10, interp
            ),
            reps=reps,
        )
        # packed dl|live + freqs tile reads, per lane
        bytes_touched = bsz * p * (4 + 4)
        roof = max(bsz * p * 8 / PEAK, bytes_touched / BW)
        mode = "us_cpu_interp" if interp else "us_compiled"
        out.append(
            f"fused_term,B={bsz}xP={p},{t*1e6:.0f},{mode}"
            f";tpu_roofline_us={roof*1e6:.2f},bytes={bytes_touched}"
        )

    # bitset combine
    for w in (1 << 15,) if smoke else (1 << 15, 1 << 18):
        bm = jnp.asarray(rng.integers(0, 2**32, (4, w), dtype=np.uint32))
        t = _time(lambda: ops.bitset_combine(bm, "and"), reps=reps)
        bytes_touched = 4 * w * 4 + w * 4
        roof = bytes_touched / BW
        out.append(
            f"bitset_and,T=4xW={w},{t*1e6:.0f},us_cpu_interp"
            f";tpu_roofline_us={roof*1e6:.2f},docs={w*32}"
        )

    # decode attention: the long_500k-cell shape (scaled)
    for s in (4096,) if smoke else (4096, 16384):
        b, hkv, g, d = 1, 2, 6, 128
        q = jnp.asarray(rng.standard_normal((b, hkv, g, d)), jnp.bfloat16)
        k = jnp.asarray(rng.standard_normal((b, hkv, s, d)), jnp.bfloat16)
        v = jnp.asarray(rng.standard_normal((b, hkv, s, d)), jnp.bfloat16)
        t = _time(lambda: ops.decode_attention(q, k, v), reps=reps)
        flops = 4 * b * hkv * g * s * d
        bytes_touched = 2 * b * hkv * s * d * 2
        roof = max(flops / PEAK, bytes_touched / BW)
        out.append(
            f"decode_attn,S={s},{t*1e6:.0f},us_cpu_interp"
            f";tpu_roofline_us={roof*1e6:.2f},kv_bytes={bytes_touched}"
        )
    return out


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument(
        "--smoke", action="store_true", help="small shapes, 2 reps (CI row)"
    )
    args = ap.parse_args()
    for line in main(smoke=args.smoke):
        print(line)
