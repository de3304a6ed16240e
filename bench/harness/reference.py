"""The plain reference: GF(2^8) and the UniLRC code, written from the paper.

Imports nothing of the program. UniLRC(alpha, z) (arXiv:2505.06819, §3.2)
over GF(2^8) with the primitive polynomial x^8+x^4+x^3+x^2+1 (0x11D) and
generator 2:

  k = alpha*z*(z-1) data blocks, g = alpha*z global parities, z local
  parities; codeword order [data | globals | locals].
  Global row t (t = 1..g), data column j: e_j^t with e_j = 2^j.
  Local row i: the XOR of global rows i*alpha .. (i+1)*alpha - 1, plus 1 on
  the data columns of group i (k/z consecutive columns).
  Group i: its data, globals i*alpha .. (i+1)*alpha - 1 and local i; every
  group XORs to zero and sits in one cluster.

Products over GF(2^8) come by tables on the host (`encode_columns`), or on
the device by bit planes (`bitplane_product`): bit r of A[i, j] * x is the
parity of a sum of x's bits, and the sums, at most 8k, are exact in the
MXU's float32 accumulator.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

CHUNK = 1 << 16          # columns per bit-plane product on the device

POLY = 0x11D


@functools.cache
def _tables() -> tuple[np.ndarray, np.ndarray]:
    exp = np.zeros(510, dtype=np.int64)
    log = np.zeros(256, dtype=np.int64)
    x = 1
    for i in range(255):
        exp[i] = exp[i + 255] = x
        log[x] = i
        x <<= 1
        if x & 0x100:
            x ^= POLY
    return exp, log


@functools.cache
def mul_table() -> np.ndarray:
    """(256, 256) uint8: a * b in GF(2^8)."""
    exp, log = _tables()
    out = np.zeros((256, 256), dtype=np.uint8)
    nz = np.arange(1, 256)
    out[1:, 1:] = exp[log[nz][:, None] + log[nz][None, :]]
    return out


def gf_pow(a: int, e: int) -> int:
    exp, log = _tables()
    return 1 if e == 0 else int(exp[(log[a] * e) % 255])


@functools.cache
def unilrc(alpha: int, z: int) -> tuple[np.ndarray, tuple[tuple[int, ...], ...]]:
    """(parity matrix A of shape (n - k, k), local groups)."""
    k, g = alpha * z * (z - 1), alpha * z
    per = k // z
    glob = np.array([[gf_pow(gf_pow(2, j), t) for j in range(k)]
                     for t in range(1, g + 1)], dtype=np.uint8)
    local = np.zeros((z, k), dtype=np.uint8)
    for i in range(z):
        local[i] = np.bitwise_xor.reduce(glob[i * alpha:(i + 1) * alpha])
        local[i, i * per:(i + 1) * per] ^= 1
    groups = tuple(
        tuple(range(i * per, (i + 1) * per))
        + tuple(range(k + i * alpha, k + (i + 1) * alpha)) + (k + g + i,)
        for i in range(z))
    return np.concatenate([glob, local]), groups


def encode_columns(A: np.ndarray, data: np.ndarray) -> np.ndarray:
    """Parities of a (k, C) byte array: (n - k, C) = A @ data over GF(2^8)."""
    prod = mul_table()[A[:, :, None], data[None, :, :]]      # (m, k, C)
    return np.bitwise_xor.reduce(prod, axis=1)


def bit_matrix(A: np.ndarray) -> np.ndarray:
    """(m, k) GF(2^8) coefficients -> (8m, 8k) bits: output bit r of row i
    takes input bit c of column j where bit r of A[i, j] * 2^c is set."""
    m, k = A.shape
    prod = mul_table()[A[:, :, None], 1 << np.arange(8)]
    bits = (prod[..., None] >> np.arange(8)) & 1              # (m, k, c, r)
    return bits.transpose(0, 3, 1, 2).reshape(8 * m, 8 * k).astype(np.uint8)


def bitplane_product(a_bits, data, mantissa_bits: int = 23):
    """`bit_matrix(A)` (8m, 8k) times data (k, C) over GF(2^8), on the
    device: (m, C) uint8. The float32 sums are rounded to `mantissa_bits`
    first: 23 keeps them exact; fewer is the lower precision a control
    stands for."""
    k, cols = data.shape
    shifts = jnp.arange(8, dtype=jnp.uint8)[None, :, None]
    x = ((data[:, None, :] >> shifts) & 1).reshape(8 * k, cols)
    # bits are exact in bfloat16, and their sums (<= 8k) in float32
    y = jnp.dot(a_bits.astype(jnp.bfloat16), x.astype(jnp.bfloat16),
                preferred_element_type=jnp.float32)
    y = jax.lax.reduce_precision(y, exponent_bits=8,
                                 mantissa_bits=mantissa_bits)
    bits = y.astype(jnp.int32) & 1
    m = a_bits.shape[0] // 8
    weights = (1 << jnp.arange(8, dtype=jnp.int32))[None, :, None]
    return jnp.sum(bits.reshape(m, 8, cols) * weights, axis=1).astype(
        jnp.uint8)


def encode_on_device(a_bits, data):
    """Parities of a (k, B) byte array on the device, exactly, `CHUNK`
    columns at a time so the bit planes stay small. Traceable."""
    k, B = data.shape
    c = math.gcd(B, CHUNK)
    x = data.reshape(k, B // c, c).transpose(1, 0, 2)
    y = jax.lax.map(lambda d: bitplane_product(a_bits, d), x)
    return y.transpose(1, 0, 2).reshape(-1, B)


def placement_faults(nodes: list[int], groups, nodes_per_cluster: int) -> int:
    """How many of a stripe's guarantees its placement breaks: two blocks on
    one node, a local group spread over clusters, two groups in one
    cluster. `nodes[b]` is the node holding block b."""
    faults = len(nodes) - len(set(nodes))
    clusters = []
    for grp in groups:
        cs = {nodes[b] // nodes_per_cluster for b in grp}
        faults += len(cs) - 1
        clusters.append(min(cs))
    return faults + len(clusters) - len(set(clusters))
