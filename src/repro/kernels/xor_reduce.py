"""Pallas TPU kernel: XOR-fold of s blocks — UniLRC's entire single-failure
decode path (XOR locality, paper §2.3.3/§4.1 Property 2).

The TPU analogue of the paper's Fig 3 "XOR beats MUL+XOR" result: this
kernel is a pure VPU bitwise reduction — no MXU pass, no
table gathers, ~s*B byte reads and B writes. Compare kernels/gf_bitmatmul
(the MUL+XOR path) which needs an (8m x 8k x Bt) MXU contraction.

ops.py hands it the uint8 blocks as they are; the kernel itself is
dtype-agnostic over integer types.
"""
from __future__ import annotations

import functools

import jax
from jax.experimental import pallas as pl

DEFAULT_BLOCK_B = 2048  # elements per tile


def _kernel(blocks_ref, out_ref, *, s: int):
    acc = blocks_ref[0, :]
    for j in range(1, s):             # s is small (r+1 <= 29); unrolled XOR tree
        acc = acc ^ blocks_ref[j, :]
    out_ref[...] = acc


def _kernel_batched(blocks_ref, out_ref, *, s: int):
    acc = blocks_ref[0, 0, :]
    for j in range(1, s):
        acc = acc ^ blocks_ref[0, j, :]
    out_ref[0, 0, :] = acc


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def xor_reduce(blocks: jax.Array, block_b: int = DEFAULT_BLOCK_B,
               interpret: bool = True) -> jax.Array:
    """(s, B) int array -> (B,) XOR-fold along axis 0."""
    s, B = blocks.shape
    assert B % block_b == 0, (B, block_b)
    grid = (B // block_b,)
    return pl.pallas_call(
        functools.partial(_kernel, s=s),
        grid=grid,
        in_specs=[pl.BlockSpec((s, block_b), lambda b: (0, b))],
        out_specs=pl.BlockSpec((block_b,), lambda b: (b,)),
        out_shape=jax.ShapeDtypeStruct((B,), blocks.dtype),
        interpret=interpret,
    )(blocks)


@functools.partial(jax.jit, static_argnames=("block_b", "interpret"))
def xor_reduce_batched(blocks: jax.Array, block_b: int = DEFAULT_BLOCK_B,
                       interpret: bool = True) -> jax.Array:
    """(S, s, B) int array -> (S, B) XOR-fold along axis 1, one launch.

    The stripe-batch analogue of `xor_reduce`: grid (S, B // block_b), so
    recovering the same failed block across S stripes is a single kernel
    launch instead of S.

    The output is laid out (S, 1, B): a TPU block's last two dims must be
    multiples of (8, 128) or equal the array's, and a one-stripe (1, Bt)
    block of an (S, B) array is neither."""
    S, s, B = blocks.shape
    assert B % block_b == 0, (B, block_b)
    grid = (S, B // block_b)
    out = pl.pallas_call(
        functools.partial(_kernel_batched, s=s),
        grid=grid,
        in_specs=[pl.BlockSpec((1, s, block_b), lambda si, b: (si, 0, b))],
        out_specs=pl.BlockSpec((1, 1, block_b), lambda si, b: (si, 0, b)),
        out_shape=jax.ShapeDtypeStruct((S, 1, B), blocks.dtype),
        interpret=interpret,
    )(blocks)
    return out.reshape(S, B)
