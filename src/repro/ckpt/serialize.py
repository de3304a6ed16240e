"""Pytree <-> flat bytes serialization for checkpoint striping.

The train state (params + optimizer + step) is flattened to one contiguous
byte buffer plus a JSON-able manifest (paths, shapes, dtypes, offsets).
The buffer is what the erasure-coding layer stripes; the manifest is tiny
and stored replicated (the paper's coordinator holds stripe metadata the
same way).
"""
from __future__ import annotations

import dataclasses
import json
from typing import Any

import jax
import numpy as np

from repro import obs


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


def serialize_tree(tree: Any) -> tuple[bytes, Manifest, Any]:
    """-> (buffer, manifest, treedef). Leaves in tree-flatten order; a
    device leaf's copy to the host is counted in `obs.TRANSFERS`."""
    leaves_with_path, treedef = jax.tree_util.tree_flatten_with_path(tree)
    entries = []
    chunks = []
    offset = 0
    with obs.span("ckpt.serialize", bytes=sum(
            _nbytes(leaf) for _path, leaf in leaves_with_path)):
        for path, leaf in leaves_with_path:
            arr = obs.to_host(leaf)
            if arr.dtype == jax.numpy.bfloat16:
                raw = arr.view(np.uint16).tobytes()
                dt = "bfloat16"
            else:
                raw = arr.tobytes()
                dt = str(arr.dtype)
            entries.append((_path_str(path), tuple(arr.shape), dt, offset,
                            len(raw)))
            chunks.append(raw)
            offset += len(raw)
        buf = b"".join(chunks)
    return buf, Manifest(tuple(entries), str(treedef), offset), treedef


def deserialize_tree(buf: bytes | bytearray | memoryview, manifest: Manifest,
                     treedef) -> Any:
    """Rebuild the pytree from the byte buffer (numpy leaves; caller casts
    / device_puts with the right shardings)."""
    import jax.numpy as jnp
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
