"""Every fused Pallas kernel compiles for a TPU v5e chip.

Interpret mode on the CPU checks what the kernels compute, not whether
Mosaic accepts them: block shapes must obey the (8, 128) tiling rule and a
kernel may not gather.  These tests compile each kernel for a described
(not attached) v5e chip at the widths ``chip_smoke.py`` serves and assert
the compiled program carries the kernel as a ``tpu_custom_call`` under
its stable ``name``, and that the jitted programs the benchmark's trace
reduction reads keep their names.

The topology is described inside a module fixture only: one process at a
time may load the TPU compiler library, so nothing here may touch it while
modules are imported.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.query import exec as qexec
from repro.core.query import fused
from repro.kernels import fused_exec as fk
from repro.kernels import vector_topk as vk

# the widths chip_smoke.py serves: 500k docs and 100k 768-d passages over
# 2 shards (one segment per shard), k=10, month facets, family groups of
# up to 32 queries per wave
BATCH = 32
K = 10
SEG_DOCS = -(-500_000 // 2 // fk.BLOCK) * fk.BLOCK  # doc space = longest row
VEC_DOCS = -(-100_000 // 2 // fk.BLOCK) * fk.BLOCK
DIM = 768
MONTH_BINS = 12
# the benchmark's dense cell: one shard's share of 500k passages, full
# 64-query waves
CELL_WAVE = 64
CELL_VEC_DOCS = -(-500_000 // 2 // fk.BLOCK) * fk.BLOCK

I32, F32 = jnp.int32, jnp.float32


@pytest.fixture(scope="module")
def one_chip():
    """One v5e chip of a described 2x2 topology, persistent cache off (a
    compile for a described chip cannot be read back without one)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            topo = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:  # noqa: BLE001 — no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        prev = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", prev)
            compilation_cache.reset_cache()


#: family -> the ``pallas_call`` name its kernel carries into the trace
KERNEL_NAMES = {
    "term": "fused_term", "bool": "fused_bool", "sort": "fused_sort",
    "range": "fused_range", "facet": "fused_facet",
    "vector": "vector_topk", "vector_cell": "vector_topk",
    "hybrid": "hybrid_topk",
}


def _cases():
    """name -> (kernel call, argument (shape, dtype) list)."""
    b, nd, nv = BATCH, SEG_DOCS, VEC_DOCS
    return {
        "term": (
            lambda g, f, idf, a, k1, bb: fk.term_topk_tiles(
                g, f, idf, a, k1, bb, K, False
            ),
            [((b, nd), I32), ((b, nd), I32), ((b,), F32), ((), F32),
             ((), F32), ((), F32)],
        ),
        "bool": (
            lambda d, c, live: fk.bool_topk_tiles(d, c, live, K, 2, True, False),
            [((b, nd), F32), ((b, nd), I32), ((nd,), I32)],
        ),
        "sort": (
            lambda m, dv: fk.sort_topk_tiles(m, dv, K, False),
            [((b, nd), I32), ((nd,), F32)],
        ),
        "range": (
            lambda dv, live, lo, hi: fk.range_topk_tiles(
                dv, live, lo, hi, K, False
            ),
            [((nd,), I32), ((nd,), I32), ((b,), I32), ((b,), I32)],
        ),
        "facet": (
            lambda m, bins: fk.facet_hist_tiles(m, bins, MONTH_BINS, False),
            [((b, nd), I32), ((nd,), I32)],
        ),
        "vector": (
            lambda v, live, q: vk.vector_topk_tiles(
                v, live, q, K, False, DIM, False
            ),
            [((nv, DIM), F32), ((nv,), I32), ((b, DIM), F32)],
        ),
        "vector_cell": (
            lambda v, live, q: vk.vector_topk_tiles(
                v, live, q, K, False, DIM, False
            ),
            [((CELL_VEC_DOCS, DIM), F32), ((CELL_VEC_DOCS,), I32),
             ((CELL_WAVE, DIM), F32)],
        ),
        "hybrid": (
            lambda d, v, live, q, a: vk.hybrid_topk_tiles(
                d, v, live, q, a, K, False, DIM, False
            ),
            [((b, nv), F32), ((nv, DIM), F32), ((nv,), I32), ((b, DIM), F32),
             ((b,), F32)],
        ),
    }


@pytest.mark.parametrize(
    "family",
    ["term", "bool", "sort", "range", "facet", "vector", "vector_cell",
     "hybrid"],
)
def test_kernel_compiles_for_v5e(one_chip, family):
    fn, shapes = _cases()[family]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    name = KERNEL_NAMES[family]
    assert re.search(
        rf"%{name}(\.\d+)? = .*custom_call_target=\"tpu_custom_call\"", text
    ), f"kernel name {name!r} lost in lowering"


def _program(name, sharding):
    """(jitted function, abstract arguments, static keywords) of the
    program the benchmark reads under jit name ``name``, at served
    widths: one shard's vector column, a 2-segment merge, a 2-shard
    merge."""
    b, nv = BATCH, VEC_DOCS

    def a(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    if name == "_fused_vector":
        return fused._fused_vector, (
            a((nv, DIM), F32), a((nv,), I32), a((b, DIM), F32), a((), I32),
        ), dict(k=K, cosine=False, dim=DIM, use_kernel=True, interpret=False)
    if name == "_concat_merge":
        return qexec._concat_merge, (
            (a((b, K), F32),) * 2, (a((b, K), I32),) * 2, (a((b,), I32),) * 2,
        ), dict(k=K)
    return qexec.merge_topk, (a((b, 2 * K), F32), a((b, 2 * K), I32)), dict(
        k=K)


@pytest.mark.parametrize("program", ["_fused_vector", "_concat_merge",
                                     "merge_topk"])
def test_program_names_survive_for_v5e(one_chip, program):
    """The trace reduction and the result line's breakdown find these
    programs by their jit names (``jit_<name>``)."""
    fn, args, static = _program(program, one_chip)
    text = fn.lower(*args, **static).compile().as_text()
    assert text.startswith(f"HloModule jit_{program},"), text[:120]
