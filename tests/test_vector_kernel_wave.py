"""The dense-vector kernel scores a whole wave per column block.

``vector_topk_tiles`` keeps each (8, 128, D_pad) block of the vector column
in VMEM while every query of the wave is scored against it, so one wave
reads the column once.  These tests pin both halves of that contract on
the CPU (the kernel interpreted):

  * parity: a wave of B queries returns, bit for bit, what B separate
    one-query calls return (per-block winners and counts), and what the
    XLA oracle (``exec._vector_core``, vmapped and jitted as
    ``_vector_topk_batch``) returns after the production epilogue merges
    the blocks — over dead rows, zero rows and exact score ties that
    straddle block boundaries;
  * structure: walking the kernel's grid in order, the column operand's
    block index changes once per block and never with the query, so the
    pipeline fetches each block exactly once per wave.
"""

import functools
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.query import exec as qexec
from repro.core.query import fused
from repro.kernels import vector_topk as vk
from repro.kernels.fused_exec import BLOCK

pytestmark = pytest.mark.vector

N_ROWS = 2500          # three doc blocks once padded to 3072
N_ANCHORS = 8          # planted directions whose copies tie exactly
ANCHOR_ROW = 10        # in-block offset of each anchor's copies


def column(dim, seed=5):
    """(ND_pad, D_pad) column and (ND_pad,) live flags: Gaussian rows, every
    7th row zero (a vectorless doc), every 11th row dead, padding rows dead
    and zero, and each anchor copied into the same slot of all three blocks
    (one copy dead) so that equal scores straddle block boundaries."""
    rng = np.random.default_rng(seed)
    dp = vk.pad_dim(dim)
    nd = -(-N_ROWS // BLOCK) * BLOCK
    v = np.zeros((nd, dp), np.float32)
    v[:N_ROWS, :dim] = rng.standard_normal((N_ROWS, dim))
    v[3:N_ROWS:7] = 0.0
    live = np.zeros(nd, np.int32)
    live[:N_ROWS] = 1
    live[::11] = 0
    anchors = 5.0 * rng.standard_normal((N_ANCHORS, dim)).astype(np.float32)
    for t, a in enumerate(anchors):
        for blk in range(nd // BLOCK):
            r = blk * BLOCK + ANCHOR_ROW + t
            v[r] = 0.0
            v[r, :dim] = a
            live[r] = 1
    live[2 * BLOCK + ANCHOR_ROW] = 0
    return v, live, anchors


def wave(bsz, dim, anchors, seed=9):
    """(B, D_pad) queries, each near one anchor (so the anchors' tied
    copies rank first); query 0 is the anchor itself."""
    rng = np.random.default_rng(seed)
    q = np.zeros((bsz, vk.pad_dim(dim)), np.float32)
    a = anchors[np.arange(bsz) % N_ANCHORS] / 5.0
    q[:, :dim] = a + 0.1 * rng.standard_normal((bsz, dim))
    q[0, :dim] = anchors[0]
    return q


@functools.partial(jax.jit, static_argnames=("k", "cosine", "dim"))
def kernel(v, live, q, k, cosine, dim):
    return vk.vector_topk_tiles(v, live, q, k, cosine, dim, interpret=True)


@functools.partial(jax.jit, static_argnames=("k", "cosine", "dim"))
def one_query_calls(v, live, q, k, cosine, dim):
    """The kernel called once per query (B = 1), stacked along the wave."""
    return jax.lax.map(
        lambda qi: jax.tree.map(
            lambda x: x[0],
            vk.vector_topk_tiles(v, live, qi[None], k, cosine, dim,
                                 interpret=True),
        ),
        q,
    )


@pytest.mark.parametrize("k", [1, 10, 128])
@pytest.mark.parametrize("dim", [24, 768])
@pytest.mark.parametrize("metric", ["dot", "cosine"])
@pytest.mark.parametrize("bsz", [1, 2, 8, 64])
def test_wave_matches_single_queries_and_oracle(bsz, metric, dim, k):
    cosine = metric == "cosine"
    v, live, anchors = column(dim)
    q = wave(bsz, dim, anchors)
    blk_v, blk_i, blk_c = kernel(v, live, q, k, cosine, dim)
    nb = v.shape[0] // BLOCK
    assert blk_v.shape == blk_i.shape == (bsz, nb, vk.DIM_TILE)
    assert blk_c.shape == (bsz, nb)

    # the wave == B one-query calls, per-block winners and counts included
    for got, one in zip((blk_v, blk_i, blk_c),
                        one_query_calls(v, live, q, k, cosine, dim)):
        np.testing.assert_array_equal(got, one)

    # the merged wave == the XLA oracle over the unpadded column
    vals, ids = fused._hier_topk(blk_v, blk_i, k)
    o_vals, o_ids, o_hits = qexec._vector_topk_batch(
        jnp.asarray(v[:N_ROWS, :dim]), jnp.asarray(live[:N_ROWS] > 0),
        jnp.asarray(q[:, :dim]), k, cosine,
    )
    np.testing.assert_array_equal(vals, o_vals)
    np.testing.assert_array_equal(ids, o_ids)
    np.testing.assert_array_equal(blk_c.sum(-1), o_hits)

    # the planted ties reached the answer: query 0's best score is held
    # by its anchor's live copies, lowest doc first across blocks
    if k >= 2:
        assert vals[0, 0] == vals[0, 1]
        assert list(ids[0, :2]) == [ANCHOR_ROW, BLOCK + ANCHOR_ROW]


def _vector_call(bsz, nb, dp):
    """The ``vector_topk`` pallas_call equation of a traced wave."""
    jaxpr = jax.make_jaxpr(
        lambda v, l, q: vk.vector_topk_tiles(v, l, q, 10, False, dp, True)
    )(
        jax.ShapeDtypeStruct((nb * BLOCK, dp), jnp.float32),
        jax.ShapeDtypeStruct((nb * BLOCK,), jnp.int32),
        jax.ShapeDtypeStruct((bsz, dp), jnp.float32),
    )
    calls = [e for e in jaxpr.eqns if e.primitive.name == "pallas_call"]
    assert [e.params["name"] for e in calls] == ["vector_topk"]
    return calls[0]


@pytest.mark.parametrize("bsz", [1, 8, 64])
def test_wave_reads_each_column_block_once(bsz):
    """Walk the grid in the pipeline's order (last axis fastest): a block
    is fetched whenever the column operand's block index differs from the
    step before, so fetches == NB means one read of the column per wave,
    whatever the wave's size."""
    nb, dp = 5, 256
    call = _vector_call(bsz, nb, dp)
    gm = call.params["grid_mapping"]
    col = [
        bm for bm in gm.block_mappings[: gm.num_inputs]
        if bm.array_aval.shape == (nb * 8, 128, dp)
    ]
    assert len(col) == 1, [bm.array_aval.shape for bm in gm.block_mappings]
    imap = col[0].index_map_jaxpr
    fetches, seen, prev = 0, set(), None
    for step in itertools.product(*(range(n) for n in gm.grid)):
        idx = tuple(
            int(x) for x in jax.core.eval_jaxpr(imap.jaxpr, imap.consts, *step)
        )
        fetches += idx != prev
        seen.add(idx)
        prev = idx
    assert len(seen) == nb
    assert fetches == nb


def test_batch_past_max_wave_runs_in_passes(monkeypatch):
    """A batch larger than ``MAX_WAVE`` makes one pass over the column per
    ``MAX_WAVE`` queries and answers exactly as one wave does."""
    v, live, anchors = column(24)
    q = wave(8, 24, anchors)
    whole = kernel(v, live, q, 10, True, 24)
    monkeypatch.setattr(vk, "MAX_WAVE", 2)

    def passes(v, live, q):
        return vk.vector_topk_tiles(v, live, q, 10, True, 24, interpret=True)

    jaxpr = jax.make_jaxpr(passes)(v, live, q)
    assert [e.params["name"] for e in jaxpr.eqns
            if e.primitive.name == "pallas_call"] == ["vector_topk"] * 4
    for got, want in zip(jax.jit(passes)(v, live, q), whole):
        np.testing.assert_array_equal(got, want)
