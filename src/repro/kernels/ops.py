"""Jit'd public wrappers around the coding kernels.

API (all uint8 byte streams):
  encode(code, data)            -> parity blocks         (gf_bitmatmul)
  apply_matrix(M, blocks)       -> GF matmul on blocks   (gf_bitmatmul)
  xor_fold(blocks)              -> XOR of blocks         (xor_reduce)
  recover_single(plan, blocks)  -> one block             (xor path if plan
                                                          is XOR-only)

Stripe-batched variants (leading S axis, ONE kernel launch per call):
  encode_many(code, data)       -> (S, k, B) -> (S, n, B)
  apply_matrix_many(M, blocks)  -> (S, k, B) -> (S, m, B)
  xor_fold_many(blocks)         -> (S, s, B) -> (S, B)
  recover_many(plan, blocks)    -> {src: (S, B)} -> (S, B)
  apply_decode_many(plan, blocks) -> {src: (S, B)} -> {erased: (S, B)}
  checksum_many(blocks, nbytes) -> (..., B) -> (..., B // nbytes) uint32
                                   CRC32C per chunk   (gf_bitmatmul)
  verify_many(blocks, expect, nbytes) -> (N,) chunks failing per block

`interpret` defaults to True when JAX's backend is the CPU and False on a
TPU (`default_interpret`); any other backend raises. The Pallas kernel
body is identical in both modes.

KERNEL_LAUNCHES counts pallas_call launches per kernel (host-side, outside
jit) so tests and benchmarks can assert batching actually batches. All
mutation goes through `_count_launch` under a lock, so the totals stay
exact when the sharded front-end flushes engines from a worker pool;
`launch_scope()` gives a caller a *thread-local* delta counter — the only
way to attribute launches to one shard's flush while other shards launch
concurrently (a global snapshot pair would fold their launches in). The
repo lint (rule RA007) flags any direct mutation of the counters outside
`repro/kernels/`.
"""
from __future__ import annotations

import collections
import contextlib
import functools
import threading
from collections.abc import Iterator

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.codec import DecodePlan, RecoveryPlan
from repro.core.codes import Code
from repro.core.crc32c import crc32c_matrix
from repro.core.gf import expand_coding_matrix_to_bits

from . import autotune
from .gf_bitmatmul import gf_bitmatmul, gf_bitmatmul_batched
from .xor_reduce import xor_reduce, xor_reduce_batched

KERNEL_LAUNCHES: collections.Counter = collections.Counter()
_LAUNCH_LOCK = threading.Lock()
_LAUNCH_SCOPES = threading.local()      # per-thread stack of LaunchScope


class LaunchScope:
    """Thread-local launch delta: counts launches issued by the current
    thread while the scope is active. Live-updating — `total` may be
    read mid-scope (the front-end's virtual-time service model samples
    it between execution and handle resolution)."""

    __slots__ = ("counts",)

    def __init__(self) -> None:
        self.counts: collections.Counter = collections.Counter()

    @property
    def total(self) -> int:
        return sum(self.counts.values())


def _scope_stack() -> list[LaunchScope]:
    stack = getattr(_LAUNCH_SCOPES, "stack", None)
    if stack is None:
        stack = _LAUNCH_SCOPES.stack = []
    return stack


@contextlib.contextmanager
def launch_scope() -> Iterator[LaunchScope]:
    """Context manager attributing kernel launches to the current thread:
    every launch issued by this thread inside the scope is counted on the
    yielded `LaunchScope` (and still on the global KERNEL_LAUNCHES).
    Scopes nest; launches from OTHER threads never leak in, which is what
    makes per-shard launch accounting exact under the worker pool."""
    scope = LaunchScope()
    stack = _scope_stack()
    stack.append(scope)
    try:
        yield scope
    finally:
        stack.remove(scope)


def _count_launch(name: str) -> None:
    """The one mutation point for launch accounting (lint rule RA007):
    global counter under the lock, plus every active scope of the
    calling thread."""
    with _LAUNCH_LOCK:
        KERNEL_LAUNCHES[name] += 1
    for scope in _scope_stack():
        scope.counts[name] += 1


def reset_kernel_launch_counts() -> None:
    with _LAUNCH_LOCK:
        KERNEL_LAUNCHES.clear()


def kernel_launch_snapshot() -> dict[str, int]:
    """Point-in-time copy of KERNEL_LAUNCHES. Callers that need a delta
    (the repair engine's launch accounting, the simulator's traffic
    oracle) take a snapshot before and after instead of mutating the
    live counter, so concurrent accounting consumers don't clobber each
    other. Single-threaded accounting only — under the shard worker
    pool a snapshot delta folds in every other thread's launches; use
    `launch_scope()` there."""
    with _LAUNCH_LOCK:
        return dict(KERNEL_LAUNCHES)


def launches_since(snapshot: dict[str, int]) -> int:
    """Total launches since `snapshot` (see kernel_launch_snapshot)."""
    with _LAUNCH_LOCK:
        total = sum(KERNEL_LAUNCHES.values())
    return total - sum(snapshot.values())


@functools.cache
def default_interpret() -> bool:
    """True (Pallas interpret mode) when JAX's backend is the CPU, False on
    a TPU. Any other backend raises: the kernels are written for the TPU,
    and interpret mode anywhere but the CPU would hide that the device is
    not running them. Decided once per process."""
    platform = jax.default_backend()
    if platform == "cpu":
        return True
    if platform == "tpu":
        return False
    raise RuntimeError(
        f"the coding kernels run on a TPU, or in interpret mode on the "
        f"CPU; JAX's backend is {platform!r}")


def _pad_to(x: np.ndarray | jax.Array, mult: int, axis: int):
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x, size
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), size


@functools.lru_cache(maxsize=64)
def _a_bits_for(code_key: str, A_bytes: bytes, shape: tuple) -> jax.Array:
    A = np.frombuffer(A_bytes, dtype=np.uint8).reshape(shape)
    # Concrete even when the first call is traced under jit: a cached
    # tracer would leak into every later call.
    with jax.ensure_compile_time_eval():
        return jnp.asarray(expand_coding_matrix_to_bits(A))


def _bits(A: np.ndarray, tag: str) -> jax.Array:
    A = np.ascontiguousarray(A, dtype=np.uint8)
    return _a_bits_for(tag, A.tobytes(), A.shape)


def apply_matrix(M: np.ndarray, blocks: jax.Array, *,
                 block_b: int | None = None, interpret: bool | None = None,
                 tag: str = "adhoc") -> jax.Array:
    """GF(2^8) matmul M (m,k) @ blocks (k,B) -> (m,B), via the MXU kernel.

    `block_b=None` (the default everywhere outside kernel oracles)
    resolves the lane tile through the autotune planner — padding and
    grid shape follow the VMEM budget model / measured timings instead
    of a hard-coded constant (lint rule RA008 enforces this)."""
    if interpret is None:
        interpret = default_interpret()
    a_bits = _bits(M, tag)
    blocks = jnp.asarray(blocks, dtype=jnp.uint8)
    if block_b is None:
        block_b = autotune.plan_matmul_tiles(
            M.shape[1], M.shape[0], blocks.shape[-1]).block_b
    padded, B = _pad_to(blocks, block_b, axis=1)
    _count_launch("gf_bitmatmul")
    out = gf_bitmatmul(a_bits, padded, block_b=block_b, interpret=interpret)
    return out[:, :B]


def apply_matrix_many(M: np.ndarray, blocks: jax.Array, *,
                      block_b: int | None = None,
                      interpret: bool | None = None,
                      tag: str = "adhoc") -> jax.Array:
    """Stripe-batched GF(2^8) matmul: M (m,k) @ blocks (S,k,B) -> (S,m,B).

    One `gf_bitmatmul_batched` launch for the whole batch; the expanded
    A_bits tile is resident in VMEM across all S stripes. Lane tiling
    is autotuned (see `apply_matrix`)."""
    return _bitmatmul_many(_bits(M, tag), blocks, block_b=block_b,
                           interpret=interpret)


def _bitmatmul_many(a_bits: jax.Array, blocks: jax.Array, *,
                    block_b: int | None, interpret: bool | None
                    ) -> jax.Array:
    """(8m, 8k) bit matrix @ (S, k, B) bytes -> (S, m, B): one
    `gf_bitmatmul_batched` launch, lanes padded to the planned tile."""
    if interpret is None:
        interpret = default_interpret()
    blocks = jnp.asarray(blocks, dtype=jnp.uint8)
    if block_b is None:
        block_b = autotune.plan_matmul_tiles(
            a_bits.shape[1] // 8, a_bits.shape[0] // 8,
            blocks.shape[-1]).block_b
    padded, B = _pad_to(blocks, block_b, axis=2)
    _count_launch("gf_bitmatmul")
    out = gf_bitmatmul_batched(a_bits, padded, block_b=block_b,
                               interpret=interpret)
    return out[:, :, :B]


def encode(code: Code, data: jax.Array, *, block_b: int | None = None,
           interpret: bool | None = None) -> jax.Array:
    """data (k, B) uint8 -> full codeword (n, B): [data | parities]."""
    parity = apply_matrix(code.A, data, block_b=block_b,
                          interpret=interpret, tag=code.name)
    return jnp.concatenate([jnp.asarray(data, jnp.uint8), parity], axis=0)


def encode_many(code: Code, data: jax.Array, *,
                block_b: int | None = None,
                interpret: bool | None = None) -> jax.Array:
    """data (S, k, B) uint8 -> (S, n, B) codewords, ONE kernel launch.

    The batched analogue of `encode`: S stripes ride a stripe-batch grid
    dimension instead of S separate launches."""
    parity = apply_matrix_many(code.A, data, block_b=block_b,
                               interpret=interpret, tag=code.name)
    return jnp.concatenate([jnp.asarray(data, jnp.uint8), parity], axis=1)


def xor_fold(blocks: jax.Array, *, interpret: bool | None = None) -> jax.Array:
    """(s, B) uint8 -> (B,) uint8 XOR-fold.

    The kernel folds the bytes as they are. The planner sizes the tile in
    4-byte words, so the byte tile is 4 * plan.block_b. (Viewing the bytes
    as int32 outside the kernel costs the TPU compiler ~80 s per shape to
    unpack the result, and a 32x lane-padded copy to pack the input.)"""
    if interpret is None:
        interpret = default_interpret()
    blocks = jnp.asarray(blocks, dtype=jnp.uint8)
    s, B = blocks.shape
    tile = 4 * autotune.plan_xor_tiles(s, B).block_b
    padded, _ = _pad_to(blocks, tile, axis=1)
    _count_launch("xor_reduce")
    return xor_reduce(padded, block_b=tile, interpret=interpret)[:B]


def xor_fold_many(blocks: jax.Array, *,
                  interpret: bool | None = None) -> jax.Array:
    """(S, s, B) uint8 -> (S, B) uint8 XOR-fold along axis 1, one launch."""
    if interpret is None:
        interpret = default_interpret()
    blocks = jnp.asarray(blocks, dtype=jnp.uint8)
    S, s, B = blocks.shape
    tile = 4 * autotune.plan_xor_tiles(s, B).block_b      # see xor_fold
    padded, _ = _pad_to(blocks, tile, axis=2)
    _count_launch("xor_reduce")
    return xor_reduce_batched(padded, block_b=tile,
                              interpret=interpret)[:, :B]


def recover_single(plan: RecoveryPlan, blocks: dict[int, jax.Array], *,
                   interpret: bool | None = None) -> jax.Array:
    """Execute a single-failure recovery plan on device.

    XOR-only plans (every UniLRC recovery — Property 2) take the pure-VPU
    xor_reduce path; mixed-coefficient plans fall back to the MXU kernel.
    """
    src = jnp.stack([jnp.asarray(blocks[s], jnp.uint8) for s in plan.sources])
    if plan.xor_only:
        return xor_fold(src, interpret=interpret)
    M = np.array([plan.coeffs], dtype=np.uint8)       # (1, s)
    return apply_matrix(M, src, interpret=interpret)[0]


def apply_decode(plan: DecodePlan, blocks: dict[int, jax.Array], *,
                 interpret: bool | None = None) -> dict[int, jax.Array]:
    """Execute a multi-erasure decode plan on device."""
    if not plan.erased:
        return {}
    src = jnp.stack([jnp.asarray(blocks[s], jnp.uint8) for s in plan.sources])
    if np.all((plan.M == 0) | (plan.M == 1)) and len(plan.erased) == 1:
        sel = src[np.flatnonzero(plan.M[0])]
        return {plan.erased[0]: xor_fold(sel, interpret=interpret)}
    rec = apply_matrix(plan.M, src, interpret=interpret)
    return {e: rec[i] for i, e in enumerate(plan.erased)}


def recover_many(plan: RecoveryPlan, blocks: dict[int, jax.Array], *,
                 interpret: bool | None = None) -> jax.Array:
    """Execute one single-failure plan across S stripes in ONE launch.

    blocks: {source block id -> (S, B) uint8} — the same source block read
    from S stripes, stacked. Returns the recovered target as (S, B).
    XOR-only plans take the batched VPU path; mixed-coefficient plans take
    the batched MXU path with a (1, s) coefficient matrix."""
    src = jnp.stack([jnp.asarray(blocks[s], jnp.uint8)
                     for s in plan.sources], axis=1)       # (S, s, B)
    if plan.xor_only:
        return xor_fold_many(src, interpret=interpret)
    M = np.array([plan.coeffs], dtype=np.uint8)            # (1, s)
    return apply_matrix_many(M, src, interpret=interpret)[:, 0]


def apply_decode_many(plan: DecodePlan, blocks: dict[int, jax.Array], *,
                      interpret: bool | None = None
                      ) -> dict[int, jax.Array]:
    """Execute one multi-erasure decode plan across S stripes in one launch.

    blocks: {source block id -> (S, B) uint8}. Returns {erased: (S, B)}."""
    if not plan.erased:
        return {}
    src = jnp.stack([jnp.asarray(blocks[s], jnp.uint8)
                     for s in plan.sources], axis=1)       # (S, s, B)
    if np.all((plan.M == 0) | (plan.M == 1)) and len(plan.erased) == 1:
        sel = src[:, np.flatnonzero(plan.M[0])]
        return {plan.erased[0]: xor_fold_many(sel, interpret=interpret)}
    rec = apply_matrix_many(plan.M, src, interpret=interpret)
    return {e: rec[:, i] for i, e in enumerate(plan.erased)}


@functools.lru_cache(maxsize=8)
def _crc_bits(nbytes: int) -> tuple[jax.Array, np.uint32]:
    L, c = crc32c_matrix(nbytes)
    with jax.ensure_compile_time_eval():      # see _a_bits_for
        return jnp.asarray(L), np.uint32(c)


@functools.partial(jax.jit, static_argnames="nbytes")
def _chunk_columns(blocks: jax.Array, *, nbytes: int) -> jax.Array:
    """(N, B) bytes -> (nbytes, N * B // nbytes): one column per chunk,
    block by block. Each block's chunks are transposed on their own and
    the blocks then laid side by side: one transpose of the (N * chunks,
    nbytes) whole took the TPU compiler 22 s at 180 1 MiB blocks, this
    under 1 s (compiled for a v5e)."""
    n, B = blocks.shape
    per_block = jnp.swapaxes(blocks.reshape(n, B // nbytes, nbytes), 1, 2)
    return per_block.transpose(1, 0, 2).reshape(nbytes, -1)


@functools.partial(jax.jit, static_argnames="shape")
def _crc_words(out: jax.Array, c: jax.Array, *, shape: tuple) -> jax.Array:
    """(1, 4, chunks) CRC bytes, little-endian, XOR c -> uint32 `shape`."""
    b = out[0].astype(jnp.uint32)
    return ((b[0] | b[1] << 8 | b[2] << 16 | b[3] << 24) ^ c).reshape(shape)


def checksum_many(blocks: jax.Array, nbytes: int, *,
                  interpret: bool | None = None) -> jax.Array:
    """(..., B) uint8 -> (..., B // nbytes) uint32: the CRC32C of every
    `nbytes`-byte chunk, in ONE `gf_bitmatmul_batched` launch.

    The CRC of a fixed-length chunk is GF(2)-affine (`core.crc32c`): the
    chunks become the lanes of one (1, nbytes, chunks) batch, the
    (32, 8 * nbytes) bit matrix L takes each to its four CRC bytes, and
    the constant is XORed in after."""
    blocks = jnp.asarray(blocks, dtype=jnp.uint8)
    *lead, B = blocks.shape
    if B % nbytes:
        raise ValueError(f"{B}-byte blocks are not whole {nbytes}-byte "
                         f"chunks")
    a_bits, c = _crc_bits(nbytes)
    # (S, n, B) codewords go in one stripe at a time: merging S and n in
    # one program took the TPU compiler over 700 s at (3, 210, 1 MiB).
    rows = ([blocks.reshape(-1, B)] if blocks.ndim < 3 else
            list(blocks.reshape(-1, *blocks.shape[-2:])))
    cols = [_chunk_columns(x, nbytes=nbytes) for x in rows]
    cols = cols[0] if len(cols) == 1 else jnp.concatenate(cols, axis=1)
    out = _bitmatmul_many(a_bits, cols[None], block_b=None,
                          interpret=interpret)
    return _crc_words(out, c, shape=(*lead, B // nbytes))


@jax.jit
def _chunks_failing(crcs: jax.Array, expect: jax.Array) -> jax.Array:
    return jnp.sum(crcs != expect, axis=-1, dtype=jnp.int32)


def verify_many(blocks: jax.Array, expect: jax.Array, nbytes: int, *,
                interpret: bool | None = None) -> jax.Array:
    """(N, B) blocks and their stored (N, B // nbytes) CRCs -> (N,) int32:
    how many chunks of each block fail their CRC. One launch, as
    `checksum_many`; only the counts need leave the device."""
    return _chunks_failing(checksum_many(blocks, nbytes, interpret=interpret),
                           jnp.asarray(expect, dtype=jnp.uint32))
