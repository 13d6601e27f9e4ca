"""Pallas kernel sweeps: shapes x dtypes vs the pure-jnp oracles (ref.py).

Kernels run with interpret=True on CPU (the Bash-level target is TPU; the
interpreter executes the same kernel body).
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


@pytest.mark.parametrize("t", [1, 2, 4, 7])
@pytest.mark.parametrize("w", [1024, 5000])
@pytest.mark.parametrize("mode", ["and", "or"])
def test_bitset_sweep(rng, t, w, mode):
    bm = jnp.asarray(rng.integers(0, 2**32, (t, w), dtype=np.uint32))
    comb, cnt = ops.bitset_combine(bm, mode)
    rcomb, rcnt = ref.bitset_combine_ref(bm, mode)
    np.testing.assert_array_equal(np.asarray(comb), np.asarray(rcomb))
    assert int(cnt) == int(rcnt)


@pytest.mark.parametrize(
    "b,hkv,g,d,s,dv",
    [
        (1, 1, 1, 64, 256, 64),     # MHA single
        (2, 2, 5, 96, 700, 80),     # GQA ragged dims
        (1, 1, 16, 320, 1024, 128), # MLA-like absorbed
        (4, 8, 4, 128, 512, 128),   # aligned
    ],
)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attn_sweep(rng, b, hkv, g, d, s, dv, dtype):
    q = jnp.asarray(rng.standard_normal((b, hkv, g, d)), dtype)
    k = jnp.asarray(rng.standard_normal((b, hkv, s, d)), dtype)
    v = jnp.asarray(rng.standard_normal((b, hkv, s, dv)), dtype)
    kvl = jnp.asarray(rng.integers(1, s + 1, b).astype(np.int32))
    out = ops.decode_attention(q, k, v, kv_len=kvl)
    rout = ref.decode_attn_ref(q, k, v, kv_len=kvl)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(rout), rtol=tol, atol=tol
    )


def test_decode_attn_matches_model_path(rng):
    """Kernel == the jnp decode attention used by serve_step."""
    from repro.models.transformer import _decode_attn_jnp

    b, hkv, g, d, s = 2, 2, 3, 64, 512
    q = jnp.asarray(rng.standard_normal((b, hkv, g, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, s, hkv, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, s, hkv, d)), jnp.float32)
    kvl = jnp.asarray([512, 300], np.int32)
    model_out = _decode_attn_jnp(q, k, v, kvl)  # (B,Hkv,G,D)
    kern_out = ops.decode_attention(
        q, k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3), kv_len=kvl
    )
    np.testing.assert_allclose(
        np.asarray(model_out), np.asarray(kern_out), rtol=2e-5, atol=2e-5
    )


def _run_py(code, env):
    import os
    import subprocess
    import sys

    full = {k: v for k, v in os.environ.items()
            if k != "JAX_COMPILATION_CACHE_DIR"}
    full.update(env)
    out = subprocess.run(
        [sys.executable, "-c", code], env=full, capture_output=True,
        text=True, timeout=120,
    )
    assert out.returncode == 0, out.stderr
    return out.stdout.strip().splitlines()[-1]


def test_compile_cache_env_dir_is_left_to_jax(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, the helper sets nothing and the
    compiled programs land in that directory."""
    code = (
        "import jax, jax.numpy as jnp\n"
        "from repro.kernels.runtime import use_compile_cache\n"
        "d = use_compile_cache()\n"
        "jax.jit(lambda x: x * 2 + 1)(jnp.ones(3)).block_until_ready()\n"
        "print(d, jax.config.jax_compilation_cache_dir)\n"
    )
    got = _run_py(code, {
        "JAX_COMPILATION_CACHE_DIR": str(tmp_path),
        "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
    })
    assert got == f"{tmp_path} {tmp_path}"
    assert any(tmp_path.iterdir())


def test_compile_cache_defaults_to_fixed_checkout_dir():
    """Unset, the cache is the checkout's own fixed .jax_cache directory."""
    from pathlib import Path

    code = (
        "import jax\n"
        "from repro.kernels.runtime import use_compile_cache\n"
        "d = use_compile_cache()\n"
        "print(d, jax.config.jax_compilation_cache_dir)\n"
    )
    want = str(Path(__file__).resolve().parents[1] / ".jax_cache")
    assert _run_py(code, {}) == f"{want} {want}"
