"""Pallas TPU kernel: GF(2^8) coding matmul as a bit-plane binary matmul.

Hardware adaptation (DESIGN.md §3): ISA-L's PSHUFB nibble-table lookups have
no TPU analogue — VMEM has no fast arbitrary gather. Instead we exploit that
multiplication by a constant in GF(2^8) is GF(2)-linear: expanding the
(m, k) coefficient matrix into an (8m, 8k) binary matrix A_bits and the data
bytes into 8 bit-planes turns the whole encode into

    parity_bits = (A_bits @ data_bits) mod 2        -- one MXU matmul

with exact fp32 accumulation (8k <= 2^24 summands). The kernel:

  1. reads a (k, Bt) uint8 data tile from HBM into VMEM,
  2. unpacks it in-register to (8k, Bt) bit-planes (so HBM traffic stays at
     byte granularity — the 8x expansion lives only in VMEM),
  3. one fp32 MXU matmul against the resident (8m, 8k) A_bits tile,
  4. mod-2 via integer AND, repacks 8 bit rows per output byte row,
  5. writes the (m, Bt) uint8 parity tile.

Grid: (B // Bt,) — parity rows are small (m <= 30 for the paper's widest
code => 8m <= 240 MXU rows), so m is not tiled; the byte stream is.

Tile maths for VMEM (the planner's 8 MiB budget, kernels/autotune.py):
  A_bits fp32: 8m*8k*4  = 240*1440*4   = 1.4 MiB  (n=210 code)
  x_bits fp32: 8k*Bt*4  = 1440*512*4   = 2.8 MiB
  out fp32:    8m*Bt*4  = 240*512*4    = 0.5 MiB
The v5e compiler accepts both planned shapes at 1 MiB blocks: k=180/m=30
at Bt=512 and k=30/m=12 at Bt=4096 (tests/test_tpu_compile.py). Bt is a
multiple of 128, the TPU lane count.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BLOCK_B = 512


def _code_tile(a_bits, data, *, m: int, k: int):
    """(8m, 8k) bit matrix x (k, Bt) byte tile -> (m, Bt) byte tile."""
    bt = data.shape[-1]
    # Unpack to bit-planes: row j*8 + b holds bit b of data row j (LSB-first,
    # matching gf.expand_coding_matrix_to_bits column order).
    d32 = data.astype(jnp.int32)                           # (k, Bt)
    shifts = jnp.arange(8, dtype=jnp.int32).reshape(1, 8, 1)
    bits = jnp.bitwise_and(
        jax.lax.shift_right_logical(d32[:, None, :], shifts), 1)
    x_bits = bits.reshape(8 * k, bt).astype(jnp.float32)   # (8k, Bt)

    # Mosaic has no uint8 -> f32 cast; widen to int32 first.
    a_f32 = a_bits.astype(jnp.int32).astype(jnp.float32)
    acc = jax.lax.dot_general(
        a_f32, x_bits,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)                # (8m, Bt)
    acc_i = acc.astype(jnp.int32) & 1                      # mod 2

    # Repack: out byte row i = sum_b acc[8i+b] << b.
    acc3 = acc_i.reshape(m, 8, bt)
    weights = (jnp.int32(1) << jnp.arange(8, dtype=jnp.int32)).reshape(1, 8, 1)
    packed = jnp.sum(acc3 * weights, axis=1)               # (m, Bt) int32
    return packed.astype(jnp.uint8)


def _kernel(a_bits_ref, data_ref, out_ref, *, m: int, k: int):
    """One (k, Bt) -> (m, Bt) coding tile."""
    out_ref[...] = _code_tile(a_bits_ref[...], data_ref[...], m=m, k=k)


def _kernel_batched(a_bits_ref, data_ref, out_ref, *, m: int, k: int):
    """One stripe's (1, k, Bt) -> (1, m, Bt) coding tile; A_bits resident
    across the whole stripe-batch grid."""
    out_ref[0] = _code_tile(a_bits_ref[...], data_ref[0], m=m, k=k)


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def gf_bitmatmul(a_bits: jax.Array, data: jax.Array,
                 block_b: int = DEFAULT_BLOCK_B,
                 interpret: bool = True) -> jax.Array:
    """parity = A @ data over GF(2^8), bit-plane MXU formulation.

    a_bits: (8m, 8k) uint8 in {0,1} — from gf.expand_coding_matrix_to_bits.
    data:   (k, B) uint8, B a multiple of `block_b` (ops.py pads).
    Returns (m, B) uint8.
    """
    m8, k8 = a_bits.shape
    assert m8 % 8 == 0 and k8 % 8 == 0
    m, k = m8 // 8, k8 // 8
    kk, B = data.shape
    assert kk == k, (kk, k)
    assert B % block_b == 0, (B, block_b)

    grid = (B // block_b,)
    return pl.pallas_call(
        functools.partial(_kernel, m=m, k=k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((m8, k8), lambda b: (0, 0)),        # resident
            pl.BlockSpec((k, block_b), lambda b: (0, b)),
        ],
        out_specs=pl.BlockSpec((m, block_b), lambda b: (0, b)),
        out_shape=jax.ShapeDtypeStruct((m, B), jnp.uint8),
        interpret=interpret,
    )(a_bits, data)


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def gf_bitmatmul_batched(a_bits: jax.Array, data: jax.Array,
                         block_b: int = DEFAULT_BLOCK_B,
                         interpret: bool = True) -> jax.Array:
    """Stripe-batched coding matmul: one launch for S stripes.

    a_bits: (8m, 8k) uint8 in {0,1} — shared across the batch.
    data:   (S, k, B) uint8, B a multiple of `block_b` (ops.py pads).
    Returns (S, m, B) uint8.

    Grid is (S, B // block_b); the A_bits operand's index map is constant,
    so the coefficient tile stays resident in VMEM for the whole batch —
    the per-launch overhead and the A_bits HBM traffic are paid once, not
    once per stripe.
    """
    m8, k8 = a_bits.shape
    assert m8 % 8 == 0 and k8 % 8 == 0
    m, k = m8 // 8, k8 // 8
    S, kk, B = data.shape
    assert kk == k, (kk, k)
    assert B % block_b == 0, (B, block_b)

    grid = (S, B // block_b)
    return pl.pallas_call(
        functools.partial(_kernel_batched, m=m, k=k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((m8, k8), lambda s, b: (0, 0)),     # resident
            pl.BlockSpec((1, k, block_b), lambda s, b: (s, 0, b)),
        ],
        out_specs=pl.BlockSpec((1, m, block_b), lambda s, b: (s, 0, b)),
        out_shape=jax.ShapeDtypeStruct((S, m, B), jnp.uint8),
        interpret=interpret,
    )(a_bits, data)
