"""Pytree <-> flat bytes serialization for checkpoint striping.

The train state (params + optimizer + step) is flattened to one contiguous
byte buffer plus a JSON-able manifest (paths, shapes, dtypes, offsets).
The buffer is what the erasure-coding layer stripes; the manifest is tiny
and stored replicated (the paper's coordinator holds stripe metadata the
same way).

Two ways to flatten, one result: `serialize_tree` copies every leaf to
host `bytes`; `DeviceStripes.of`, for a state that already lives on one
device, lays the same bytes out as stripes there, one encode window at a
time, so the encoder reads them where they are and only codewords cross
the host link.
"""
from __future__ import annotations

import dataclasses
import functools
import json
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs

# The dtypes `leaf_bytes` flattens on the device: 1, 2 or 4 bytes each.
DEVICE_DTYPES = frozenset(np.dtype(d) for d in (
    jnp.bool_, jnp.int8, jnp.uint8, jnp.int16, jnp.uint16, jnp.float16,
    jnp.bfloat16, jnp.int32, jnp.uint32, jnp.float32))
# Platforms whose device memory is host memory: with no link to cross,
# the host path's copies cost less there than the device flatten's
# byte-spreading matmuls (0.083 s against 0.198 s for a 69 MB state on 8
# Xeon cores).
HOST_PLATFORMS = frozenset({"cpu"})
ROW = 512            # bytes `leaf_bytes` spreads per matmul row
CHUNK = 64 << 20     # bytes of a leaf flattened at once: bounds temporaries
FLAT_LIMIT = 2**31   # bytes a flat buffer may hold: XLA indexes it in int32
_UINT = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32}


@dataclasses.dataclass(frozen=True)
class Manifest:
    entries: tuple  # ((path, shape, dtype, offset, nbytes), ...)
    treedef_repr: str
    total_bytes: int

    def to_json(self) -> str:
        return json.dumps({
            "entries": [[p, list(s), d, o, n] for p, s, d, o, n in self.entries],
            "treedef": self.treedef_repr,
            "total_bytes": self.total_bytes,
        })

    @classmethod
    def from_json(cls, s: str) -> "Manifest":
        obj = json.loads(s)
        return cls(tuple((p, tuple(sh), d, o, n)
                         for p, sh, d, o, n in obj["entries"]),
                   obj["treedef"], obj["total_bytes"])


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


def _nbytes(leaf) -> int:
    return leaf.nbytes if hasattr(leaf, "nbytes") else np.asarray(leaf).nbytes


def _manifest(described, treedef) -> Manifest:
    """The manifest of leaves described as (path, shape, dtype), their
    bytes packed back to back in that order."""
    entries = []
    offset = 0
    for path, shape, dtype in described:
        nbytes = math.prod(shape) * np.dtype(dtype).itemsize
        entries.append((_path_str(path), tuple(shape), str(dtype), offset,
                        nbytes))
        offset += nbytes
    return Manifest(tuple(entries), str(treedef), offset)


def serialize_tree(tree: Any) -> tuple[bytes, Manifest, Any]:
    """-> (buffer, manifest, treedef). Leaves in tree-flatten order; a
    device leaf's copy to the host is counted in `obs.TRANSFERS`."""
    leaves_with_path, treedef = jax.tree_util.tree_flatten_with_path(tree)
    described = []
    chunks = []
    with obs.span("ckpt.serialize", bytes=sum(
            _nbytes(leaf) for _path, leaf in leaves_with_path), path="host"):
        for path, leaf in leaves_with_path:
            arr = obs.to_host(leaf)
            described.append((path, arr.shape, arr.dtype))
            if arr.dtype == jnp.bfloat16:
                arr = arr.view(np.uint16)
            chunks.append(arr.tobytes())
        buf = b"".join(chunks)
    return buf, _manifest(described, treedef), treedef


def _rows(leaf) -> tuple[int, int]:
    """(rows along the leading axis, bytes per row); a 0-d leaf is one
    row."""
    if leaf.ndim == 0:
        return 1, leaf.dtype.itemsize
    return leaf.shape[0], math.prod(leaf.shape[1:]) * leaf.dtype.itemsize


def stripes_on_device(tree: Any, stripe_bytes: int, *,
                      host_platforms: frozenset = HOST_PLATFORMS) -> bool:
    """True when `DeviceStripes.of` takes the tree: every leaf a
    `jax.Array` of a `DEVICE_DTYPES` dtype, all on one and the same
    device of a platform not in `host_platforms`, each with fewer than
    2^31 rows of at most FLAT_LIMIT / 4 bytes, and a stripe of
    `stripe_bytes` under FLAT_LIMIT. Numpy or Python leaves, sharded
    leaves and an empty tree are False."""
    devices = set()
    for leaf in jax.tree_util.tree_leaves(tree):
        if not isinstance(leaf, jax.Array) or leaf.dtype not in DEVICE_DTYPES:
            return False
        rows, row_bytes = _rows(leaf)
        if rows >= 2**31 or row_bytes > FLAT_LIMIT // 4:
            return False
        devices |= leaf.devices()
    return (len(devices) == 1 and stripe_bytes < FLAT_LIMIT
            and next(iter(devices)).platform not in host_platforms)


@functools.lru_cache(maxsize=None)
def _spread(width: int) -> np.ndarray:
    """(ROW, ROW) 0/1: row j * (ROW // width) + c, byte j of element c,
    to column width * c + j."""
    cols = ROW // width
    e = np.zeros((ROW, ROW), np.float32)
    for j in range(width):
        e[j * cols + np.arange(cols), width * np.arange(cols) + j] = 1
    return e


def leaf_bytes(leaf: jax.Array) -> jax.Array:
    """A leaf's bytes, row-major and little-endian, as 1-D uint8: what
    `np.asarray(leaf).tobytes()` gives, without leaving the device. A
    bool is one byte, 0 or 1.

    `lax.bitcast_convert_type(leaf, uint8)` has shape `shape + (itemsize,)`
    and the TPU lays that minor axis of 2 or 4 across 128 lanes: its
    temporaries are 32x a float32 leaf and 64x a bfloat16 one (8.1 GB for
    a 2.42 GB state, compiled for a v5e). Here each row of ROW / itemsize
    elements is spread to its ROW bytes by one matmul: the byte planes
    `(x >> 8j) & 0xff` side by side, exact in bfloat16, times a 0/1
    matrix. Each output sums one non-zero product, so every byte is
    exact. Only a tail shorter than one row takes the bitcast."""
    if leaf.dtype == jnp.bool_:
        leaf = leaf.astype(jnp.uint8)
    width = leaf.dtype.itemsize
    words = jax.lax.bitcast_convert_type(leaf, _UINT[width]).reshape(-1)
    cols = ROW // width
    body = (words.size // cols) * cols
    if width == 1 or body == 0:
        return jax.lax.bitcast_convert_type(words, jnp.uint8).reshape(-1)
    rows = words[:body].reshape(-1, cols)
    planes = jnp.concatenate(
        [(rows >> (8 * j)) & 0xff for j in range(width)], axis=1)
    spread = jnp.dot(planes.astype(jnp.bfloat16),
                     jnp.asarray(_spread(width), jnp.bfloat16),
                     preferred_element_type=jnp.float32)
    out = spread.astype(jnp.uint8).reshape(-1)
    if body == words.size:
        return out
    tail = jax.lax.bitcast_convert_type(words[body:], jnp.uint8)
    return jnp.concatenate([out, tail.reshape(-1)])


def _align(leaf) -> int:
    """Rows of the leaf's (8 x packing, 128) TPU tile, or of a 1-D array's
    1024-element tile: a slice whose rows start and end on these compiles
    as a plain copy (a slice off them took the TPU compiler 16-35 s for
    one 50 MB leaf, compiled for a v5e)."""
    return 32 if leaf.ndim >= 2 else 1024


def _row_blocks(leaf, a: int, b: int) -> tuple:
    """Rows [r0, r1) of a leaf that hold its bytes [a, b), in blocks of
    CHUNK bytes or one row, whichever is more, each block starting and
    ending on `_align` rows unless a row is more than CHUNK / `_align`
    bytes: the blocks may hold a few rows more than [a, b) at either
    end."""
    rows, row_bytes = _rows(leaf)
    step = max(1, CHUNK // row_bytes)
    align = _align(leaf) if step >= _align(leaf) else 1
    step -= step % align
    lo = (a // row_bytes) // align * align
    hi = min(rows, -(-(-(-b // row_bytes)) // align) * align)
    cuts = [lo, *range((lo // step + 1) * step, hi, step), hi]
    return tuple(zip(cuts[:-1], cuts[1:]))


@functools.partial(jax.jit, static_argnames=("plan", "stripes", "k",
                                             "block_size"))
def flatten_window(leaves: list, *, plan: tuple, stripes: int, k: int,
                   block_size: int) -> jax.Array:
    """`stripes` stripes as (stripes, k, block_size) uint8, zero-padded,
    from blocks of the leaves' rows. `plan` holds, per leaf, the offset
    of its first byte from the window's first byte (negative when the
    leaf began in an earlier window) and its row blocks [r0, r1)
    (`_row_blocks`), blocks in increasing offset.

    Each block's bytes are written whole at their own offset: bytes a
    block holds beyond the window fall in a margin of each stripe's flat
    buffer and are dropped, so no block is sliced. Each stripe is filled
    as one flat buffer and then placed: XLA indexes a 1-D update with
    int32, so one flat buffer of more than 2^31 bytes would put later
    bytes at wrapped offsets."""
    size = k * block_size
    blocks = []
    for leaf, (pos, rows) in zip(leaves, plan):
        row_bytes = _rows(leaf)[1]
        blocks += [(leaf, r0, r1, pos + r0 * row_bytes,
                    pos + r1 * row_bytes) for r0, r1 in rows]
    margin = max((p1 - p0 for *_rest, p0, p1 in blocks), default=0)
    margin = -(-margin // 65536) * 65536
    out = jnp.zeros((stripes, k, block_size), jnp.uint8)
    s = 0
    flat = jnp.zeros(margin + size + margin, jnp.uint8)

    def place(s, flat, out):
        return out.at[s].set(flat[margin:margin + size].reshape(
            k, block_size))

    with jax.named_scope("ckpt_flatten"):
        for leaf, r0, r1, p0, p1 in blocks:
            # One block at a time: without the barrier XLA spreads every
            # block before it lays out any, and holds the window's bytes
            # several times over.
            leaf, flat, out = jax.lax.optimization_barrier((leaf, flat, out))
            while p0 >= (s + 1) * size:
                out, flat = place(s, flat, out), jnp.zeros_like(flat)
                s += 1
            part = leaf_bytes(leaf.reshape(1) if leaf.ndim == 0
                              else leaf[r0:r1])
            flat = jax.lax.dynamic_update_slice(
                flat, part, (margin + p0 - s * size,))
            while p1 > (s + 1) * size and s + 1 < stripes:
                out, flat = place(s, flat, out), jnp.zeros_like(flat)
                s += 1
                flat = jax.lax.dynamic_update_slice(
                    flat, part, (margin + p0 - s * size,))
        out = place(s, flat, out)
    return out


@dataclasses.dataclass(frozen=True)
class DeviceStripes:
    """A device state's bytes as stripes, zero-padded to whole stripes:
    what `serialize_tree`'s buffer holds, laid out on the leaves' device
    one window at a time (`window`), so no host copy is made and the
    state is never held twice. `StripeCodec.write_stream` takes it in
    place of host bytes."""
    leaves: tuple
    offsets: tuple       # each leaf's first byte in the buffer
    nbytes: int
    k: int
    block_size: int

    @classmethod
    def of(cls, tree: Any, k: int, block_size: int
           ) -> tuple["DeviceStripes", Manifest, Any]:
        """-> (stripes, manifest, treedef): the device twin of
        `serialize_tree` for a tree that `stripes_on_device`, with the
        same manifest and treedef. Nothing is copied or computed yet."""
        leaves_with_path, treedef = jax.tree_util.tree_flatten_with_path(
            tree)
        leaves = tuple(leaf for _path, leaf in leaves_with_path)
        nbytes = sum(math.prod(x.shape) * x.dtype.itemsize for x in leaves)
        with obs.span("ckpt.serialize", bytes=nbytes, path="device"):
            manifest = _manifest([(path, leaf.shape, leaf.dtype)
                                  for path, leaf in leaves_with_path],
                                 treedef)
        offsets = tuple(e[3] for e in manifest.entries)
        return (cls(leaves, offsets, manifest.total_bytes, k, block_size),
                manifest, treedef)

    @property
    def stripes(self) -> int:
        return max(1, -(-self.nbytes // (self.k * self.block_size)))

    def window(self, w0: int, wn: int) -> jax.Array:
        """Stripes [w0, w0 + wn) as a (wn, k, block_size) uint8 array on
        the leaves' device, built from the leaves whose bytes fall in it."""
        leaves, plan = self.plan(w0, wn)
        return flatten_window(leaves, plan=plan, stripes=wn, k=self.k,
                              block_size=self.block_size)

    def plan(self, w0: int, wn: int) -> tuple[list, tuple]:
        """-> (leaves, plan): what `flatten_window` takes for stripes
        [w0, w0 + wn)."""
        size = self.k * self.block_size
        lo, hi = w0 * size, (w0 + wn) * size
        ends = (*self.offsets[1:], self.nbytes)
        leaves, plan = [], []
        for leaf, start, end in zip(self.leaves, self.offsets, ends):
            a, b = max(lo, start), min(hi, end)
            if a < b:
                leaves.append(leaf)
                plan.append((start - lo,
                             _row_blocks(leaf, a - start, b - start)))
        return leaves, tuple(plan)


def deserialize_tree(buf: bytes | bytearray | memoryview, manifest: Manifest,
                     treedef) -> Any:
    """Rebuild the pytree from the byte buffer (numpy leaves; caller casts
    / device_puts with the right shardings)."""
    mv = memoryview(buf)
    leaves = []
    for path, shape, dtype, offset, nbytes in manifest.entries:
        raw = mv[offset:offset + nbytes]
        if dtype == "bfloat16":
            arr = np.frombuffer(raw, np.uint16).reshape(shape).view(jnp.bfloat16)
        else:
            arr = np.frombuffer(raw, np.dtype(dtype)).reshape(shape)
        leaves.append(arr)
    return jax.tree_util.tree_unflatten(treedef, leaves)
