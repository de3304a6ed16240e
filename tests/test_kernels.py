"""Per-kernel validation: shape sweeps vs ref.py oracles.

GF(2^8) coding is bit-exact — assertions are exact equality, not allclose.
Kernels run in interpret mode (CPU container); the kernel bodies are the
TPU artifacts. Hypothesis-based kernel properties live in
tests/test_kernels_property.py so this module runs on minimal
environments without hypothesis.
"""
import jax
import numpy as np
import pytest

from repro.core import make_unilrc, paper_schemes
from repro.core.codec import decode_plan, single_recovery_plan
from repro.core.gf import expand_coding_matrix_to_bits, gf_matmul
from repro.kernels import apply_decode, encode, ops, recover_single
from repro.kernels.gf_bitmatmul import gf_bitmatmul
from repro.kernels.ref import gf_bitmatmul_ref, gf_matmul_ref
from repro.kernels.xor_reduce import xor_reduce


# ---------------------------------------------------------------------------
# gf_bitmatmul — shape sweep
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m,k", [(1, 1), (2, 5), (12, 30), (24, 112), (30, 180)])
@pytest.mark.parametrize("B", [512, 1024, 2048])
def test_gf_bitmatmul_sweep(m, k, B):
    rng = np.random.default_rng(m * 1000 + k + B)
    A = rng.integers(0, 256, (m, k), dtype=np.uint8)
    data = rng.integers(0, 256, (k, B), dtype=np.uint8)
    a_bits = expand_coding_matrix_to_bits(A)
    got = np.asarray(                  # repro-lint: allow=RA001,RA008
        gf_bitmatmul(a_bits, data, block_b=512))
    want = gf_matmul(A, data)
    assert np.array_equal(got, want)
    # and the numpy bit-plane oracle agrees too
    assert np.array_equal(gf_bitmatmul_ref(a_bits, data), want)


def test_gf_bitmatmul_edge_values():
    """All-zeros, all-0xFF, identity coefficients."""
    k, B = 7, 512
    eye = np.eye(k, dtype=np.uint8)
    data = np.full((k, B), 0xFF, dtype=np.uint8)
    got = np.asarray(                  # repro-lint: allow=RA001
        gf_bitmatmul(expand_coding_matrix_to_bits(eye), data))
    assert np.array_equal(got, data)
    zeros = np.zeros((3, k), dtype=np.uint8)
    got = np.asarray(                  # repro-lint: allow=RA001
        gf_bitmatmul(expand_coding_matrix_to_bits(zeros), data))
    assert not got.any()


# ---------------------------------------------------------------------------
# xor_reduce — sweep
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("s", [2, 3, 7, 17, 21])
@pytest.mark.parametrize("lanes", [2048, 4096])
@pytest.mark.parametrize("dtype", [np.int32, np.uint32])
def test_xor_reduce_sweep(s, lanes, dtype):
    rng = np.random.default_rng(s * lanes)
    blocks = rng.integers(0, 2**31 - 1, (s, lanes)).astype(dtype)
    got = np.asarray(xor_reduce(blocks))   # repro-lint: allow=RA001
    want = blocks[0].copy()
    for j in range(1, s):
        want ^= blocks[j]
    assert np.array_equal(got, want)


# ---------------------------------------------------------------------------
# ops-level: encode / recover / decode on real codes
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("scheme", ["30-of-42"])
@pytest.mark.parametrize("name", ["ALRC", "OLRC", "ULRC", "UniLRC"])
def test_encode_matches_host(scheme, name, B=3000):
    code = paper_schemes(scheme)[name]
    rng = np.random.default_rng(5)
    data = rng.integers(0, 256, (code.k, B), dtype=np.uint8)
    got = np.asarray(encode(code, data))
    assert np.array_equal(got, code.encode(data))


def test_encode_wide_210():
    """The widest paper code (210,180) through the MXU kernel."""
    code = paper_schemes("180-of-210")["UniLRC"]
    rng = np.random.default_rng(6)
    data = rng.integers(0, 256, (code.k, 1024), dtype=np.uint8)
    got = np.asarray(encode(code, data))
    assert np.array_equal(got, code.encode(data))


def test_encode_traced_first_then_eager():
    """The coding-matrix cache filled by a call traced under jit must
    serve a later eager call (no leaked tracer)."""
    code = make_unilrc(1, 3)
    rng = np.random.default_rng(10)
    data = rng.integers(0, 256, (2, code.k, 256), dtype=np.uint8)
    want = np.stack([code.encode(d) for d in data])
    ops._a_bits_for.cache_clear()
    traced = jax.jit(lambda x: ops.encode_many(code, x))(data)
    assert np.array_equal(np.asarray(traced), want)
    assert np.array_equal(np.asarray(ops.encode_many(code, data)), want)


def test_recover_single_xor_path():
    code = make_unilrc(1, 6)
    rng = np.random.default_rng(7)
    data = rng.integers(0, 256, (code.k, 2222), dtype=np.uint8)
    cw = code.encode(data)
    blocks = {i: cw[i] for i in range(code.n)}
    for t in [0, 17, 30, 36, 41]:
        plan = single_recovery_plan(code, t)
        assert plan.xor_only
        got = np.asarray(recover_single(plan, blocks))
        assert np.array_equal(got, cw[t])


def test_apply_decode_multi_erasure():
    code = make_unilrc(2, 4)   # (36, 24, 8)
    rng = np.random.default_rng(8)
    data = rng.integers(0, 256, (code.k, 1536), dtype=np.uint8)
    cw = code.encode(data)
    erased = (0, 5, 11, 25, 31, 35)
    plan = decode_plan(code, erased)
    blocks = {i: cw[i] for i in range(code.n) if i not in erased}
    rec = apply_decode(plan, blocks)
    for e in erased:
        assert np.array_equal(np.asarray(rec[e]), cw[e])


def test_ref_table_path_matches_host():
    rng = np.random.default_rng(9)
    M = rng.integers(0, 256, (6, 13), dtype=np.uint8)
    x = rng.integers(0, 256, (13, 640), dtype=np.uint8)
    assert np.array_equal(np.asarray(gf_matmul_ref(M, x)), gf_matmul(M, x))


def test_default_interpret_only_on_cpu(monkeypatch):
    """Interpret mode is for the CPU; a backend that is neither the CPU
    nor a TPU is refused instead of silently interpreting the kernels."""
    assert ops.default_interpret() is True          # the CPU test backend
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    ops.default_interpret.cache_clear()
    try:
        with pytest.raises(RuntimeError, match="'gpu'"):
            ops.default_interpret()
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        ops.default_interpret.cache_clear()
        assert ops.default_interpret() is False
    finally:
        ops.default_interpret.cache_clear()


# ---------------------------------------------------------------------------
# Pallas flash attention forward vs naive oracle (interpret mode)
# ---------------------------------------------------------------------------

import jax.numpy as jnp

from repro.kernels.flash_attention import flash_attention_fwd
from repro.kernels.ref import flash_attention_ref

FLASH_CASES = [
    # causal, window, B, Hq, Hkv, Sq, Skv, dk, dv, bq, bk, dtype
    (True, 0, 1, 2, 1, 256, 256, 128, 128, 128, 128, jnp.float32),
    (True, 0, 2, 4, 2, 256, 256, 128, 128, 64, 128, jnp.bfloat16),
    (False, 0, 1, 2, 2, 128, 256, 128, 128, 128, 64, jnp.float32),
    (True, 128, 1, 2, 1, 512, 512, 128, 128, 128, 128, jnp.float32),
]


@pytest.mark.parametrize(
    "causal,window,B,Hq,Hkv,Sq,Skv,dk,dv,bq,bk,dtype", FLASH_CASES)
def test_pallas_flash_matches_ref(causal, window, B, Hq, Hkv, Sq, Skv,
                                  dk, dv, bq, bk, dtype):
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(B, Hq, Sq, dk)), dtype)
    k = jnp.asarray(rng.normal(size=(B, Hkv, Skv, dk)), dtype)
    v = jnp.asarray(rng.normal(size=(B, Hkv, Skv, dv)), dtype)
    out, lse = flash_attention_fwd(q, k, v, causal=causal, window=window,
                                   block_q=bq, block_k=bk, interpret=True)
    ref = flash_attention_ref(q, k, v, causal=causal, window=window)
    assert lse.shape == (B, Hq, Sq)
    assert bool(jnp.isfinite(lse).all() if causal else True)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        rtol=tol, atol=tol)
