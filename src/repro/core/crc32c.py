"""CRC32C (Castagnoli) of fixed-length chunks, as the store checksums blocks.

HDFS's default (`dfs.checksum.type` = CRC32C, `dfs.bytes-per-checksum` =
512): every block carries one CRC32C per 512-byte chunk, and a reader
checks each chunk before it uses the bytes. The CRC is the reflected
polynomial 0x82F63B78 with init and xorout 0xFFFFFFFF; its check value,
CRC-32C("123456789"), is 0xE3069283.

The CRC of a chunk of fixed length N is affine over GF(2):

    crc(x) = L @ bits(x)  XOR  c,    c = crc(N zero bytes)

with L a (32, 8N) binary matrix. Its row 8i + r is bit r of the CRC's
little-endian byte i, and its column 8j + b is bit b of byte j of the
chunk (LSB-first): the bit layout of `gf.expand_coding_matrix_to_bits`,
so the MXU coding kernel computes the CRCs of many chunks in one matmul,
one chunk per column (`kernels.ops.checksum_many`). `crc32c_chunks` is
the host oracle, byte by byte through a table.
"""
from __future__ import annotations

import functools

import numpy as np

POLY = 0x82F63B78        # CRC32C, reflected
MASK = 0xFFFFFFFF        # init and xorout
CHECK = 0xE3069283       # CRC-32C of b"123456789"
BYTES_PER_CHECKSUM = 512  # a CRC's chunk: HDFS's dfs.bytes-per-checksum


def _step(crc: int) -> int:
    """One bit of the reflected register."""
    return (crc >> 1) ^ (POLY if crc & 1 else 0)


def crc32c_bitwise(data: bytes) -> int:
    """CRC32C of `data`, one bit at a time: the definition."""
    crc = MASK
    for byte in data:
        crc ^= byte
        for _ in range(8):
            crc = _step(crc)
    return crc ^ MASK


@functools.cache
def _table() -> np.ndarray:
    """(256,) uint32: the register after eight steps from each byte."""
    out = np.zeros(256, np.uint32)
    for v in range(256):
        crc = v
        for _ in range(8):
            crc = _step(crc)
        out[v] = crc
    return out


def crc32c_chunks(data: np.ndarray, nbytes: int) -> np.ndarray:
    """(..., B) uint8 -> (..., B // nbytes) uint32: the CRC32C of every
    `nbytes`-byte chunk of the last axis, on the host."""
    data = np.asarray(data, np.uint8)
    *lead, B = data.shape
    if B % nbytes:
        raise ValueError(f"{B} bytes are not whole chunks of {nbytes}")
    chunks = data.reshape(-1, nbytes)
    table = _table()
    crc = np.full(chunks.shape[0], MASK, np.uint32)
    for j in range(nbytes):
        crc = table[(crc ^ chunks[:, j]) & 0xFF] ^ (crc >> 8)
    return (crc ^ np.uint32(MASK)).reshape(*lead, B // nbytes)


@functools.cache
def crc32c_matrix(nbytes: int) -> tuple[np.ndarray, int]:
    """(L, c) for chunks of `nbytes` bytes: L (32, 8 * nbytes) uint8 in
    {0, 1} and c = crc(zero bytes), so crc(x) = L @ bits(x) XOR c.

    Column 8j + b is what bit b of byte j alone adds to the register: the
    bit enters at the start of byte j and takes the eight steps of its
    byte and eight for each byte after it."""
    L = np.zeros((32, 8 * nbytes), np.uint8)
    rows = np.arange(32)
    for b in range(8):
        crc = 1 << b
        for j in reversed(range(nbytes)):
            for _ in range(8):
                crc = _step(crc)
            L[:, 8 * j + b] = (crc >> rows) & 1
    return L, crc32c_bitwise(bytes(nbytes))
