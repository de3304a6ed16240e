"""The plain reference of the store's checksums: CRC32C of fixed-length
chunks, written from its definition. Imports nothing of the program.

CRC32C (Castagnoli; RFC 3720, B.4): the reflected polynomial 0x82F63B78,
the register started at and finally XORed with 0xFFFFFFFF; its check
value, CRC-32C("123456789"), is 0xE3069283. `crc32c` runs it a bit at a
time on the host.

On the device: the CRC of an N-byte chunk is affine over GF(2), crc(x) =
L bits(x) XOR crc(N zero bytes), where column (j, b) of L is what bit b
of byte j adds to the register alone, found by running the register bit
by bit. `chunk_crcs` takes the chunks as rows and sums their bit planes
against L on the MXU, at matmul precision "highest": each sum counts at
most 8N bits, exact in float32. A block goes at a time, so the bit planes
stay small. `mantissa_bits` below 23 rounds the sums first, as a control
of a CRC computed at a lower precision does (7: bfloat16).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

POLY = 0x82F63B78


def _shift(reg: int) -> int:
    """The register after one bit."""
    return (reg >> 1) ^ (POLY if reg & 1 else 0)


def crc32c(data: bytes) -> int:
    reg = 0xFFFFFFFF
    for byte in data:
        reg ^= byte
        for _ in range(8):
            reg = _shift(reg)
    return reg ^ 0xFFFFFFFF


@functools.cache
def planes(nbytes: int) -> tuple[np.ndarray, int]:
    """(P, c): P (8, nbytes, 32) with P[b, j, q] bit q of what bit b of
    byte j adds to the register, and c = crc32c of `nbytes` zero bytes."""
    P = np.zeros((8, nbytes, 32), np.uint8)
    for b in range(8):
        reg = 1 << b
        for j in range(nbytes - 1, -1, -1):     # bytes after j shift it on
            for _ in range(8):
                reg = _shift(reg)
            P[b, j] = (reg >> np.arange(32)) & 1
    return P, crc32c(bytes(nbytes))


def chunk_crcs(blocks, nbytes: int, mantissa_bits: int = 23):
    """(N, B) uint8 on the device -> (N, B // nbytes) uint32: the CRC32C
    of every `nbytes`-byte chunk, a block at a time. Traceable."""
    P, c = planes(nbytes)
    P = jnp.asarray(P, jnp.bfloat16)
    n, B = blocks.shape
    weights = jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32)

    def step(x):                                    # (chunks, nbytes)
        acc = sum(jnp.dot(((x >> b) & 1).astype(jnp.bfloat16), P[b],
                          preferred_element_type=jnp.float32)
                  for b in range(8))
        acc = jax.lax.reduce_precision(acc, exponent_bits=8,
                                       mantissa_bits=mantissa_bits)
        bits = (acc.astype(jnp.int32) & 1).astype(jnp.uint32)
        return jnp.sum(bits * weights, axis=1, dtype=jnp.uint32) ^ \
            jnp.uint32(c)

    with jax.default_matmul_precision("highest"):
        return jax.lax.map(step, blocks.reshape(n, B // nbytes, nbytes))
