"""Block stores for the EC checkpoint layer.

Mirrors the paper's prototype (§4.2): a coordinator holds metadata; proxies
(one per cluster) hold blocks on nodes. Here a *cluster* is a TPU pod / ICI
island and a *node* is a host. Two stores:

  * BlockStore      — in-memory (the "in-memory group-local redundancy"
                      tier; also what tests/benchmarks drive),
  * DiskBlockStore  — one directory per node (the durable tier).

Both track per-node failure and per-node latency (straggler simulation) so
degraded reads, reconstruction, and straggler-avoiding reads are exercised
for real. Traffic accounting distinguishes inner- vs cross-cluster bytes —
the quantity the paper's topology locality minimises — plus the
aggregated tier: cross bytes that shipped as gateway-pre-folded blocks.

A checksummed deployment keeps each block's CRC32C per chunk beside it
(`set_checksums` / `checksums`; a `.crc` sidecar file on disk); writing a
block without them drops the old ones, so none go stale. `corrupt` is the
silent fault a checksum exists for: stored bytes change, their CRCs do
not.

The cluster/node model itself lives in `repro.topo.Topology` (this
module's former private `ClusterTopology`, folded into the shared
topology subsystem; the old name is kept as an alias).
"""
from __future__ import annotations

import contextlib
import dataclasses
import os
import pathlib
import threading
from collections.abc import Callable, Iterator

import numpy as np

from repro import obs
from repro.topo import Topology

# Deprecated alias — the cluster/node model's one public name is
# `repro.topo.Topology`. Kept only so external code importing the
# historical `ckpt.ClusterTopology` keeps working; in-repo call sites
# were migrated (the repo lint flags new uses, rule RA005).
ClusterTopology = Topology


class NodeFailure(Exception):
    """Raised when reading a block from a failed node."""


@dataclasses.dataclass
class TrafficDelta:
    """Thread-local traffic attribution window (see TrafficStats.scoped):
    only bytes moved by the OPENING thread while the scope is active land
    here, so one shard's flush can account its own traffic exactly while
    other shards move bytes concurrently."""
    inner_bytes: int = 0
    cross_bytes: int = 0
    aggregated_bytes: int = 0
    reads: int = 0


@dataclasses.dataclass
class TrafficStats:
    inner_bytes: int = 0
    cross_bytes: int = 0
    aggregated_bytes: int = 0   # subset of cross_bytes: pre-folded blocks
    reads: int = 0

    def __post_init__(self):
        # Mutation is locked (the sharded front-end reads from worker
        # threads); the per-thread scope stack rides a threading.local so
        # scoped attribution never sees another thread's bytes.
        self._lock = threading.Lock()
        self._scopes = threading.local()

    def _scope_stack(self) -> list[TrafficDelta]:
        stack = getattr(self._scopes, "stack", None)
        if stack is None:
            stack = self._scopes.stack = []
        return stack

    @contextlib.contextmanager
    def scoped(self) -> Iterator[TrafficDelta]:
        """Thread-local delta collector: every add/add_many/add_shipped
        issued by THIS thread inside the scope also lands on the yielded
        `TrafficDelta`. The concurrent-safe replacement for the
        before/after field-snapshot idiom, which under the shard worker
        pool would fold every other shard's traffic into the delta."""
        delta = TrafficDelta()
        stack = self._scope_stack()
        stack.append(delta)
        try:
            yield delta
        finally:
            stack.remove(delta)

    def add(self, nbytes: int, cross: bool):
        with self._lock:
            self.reads += 1
            if cross:
                self.cross_bytes += nbytes
            else:
                self.inner_bytes += nbytes
        for delta in self._scope_stack():
            delta.reads += 1
            if cross:
                delta.cross_bytes += nbytes
            else:
                delta.inner_bytes += nbytes

    def add_many(self, reads: int, inner_bytes: int, cross_bytes: int):
        """One accounting pass for a whole `get_many` batch."""
        with self._lock:
            self.reads += reads
            self.inner_bytes += inner_bytes
            self.cross_bytes += cross_bytes
        for delta in self._scope_stack():
            delta.reads += reads
            delta.inner_bytes += inner_bytes
            delta.cross_bytes += cross_bytes

    def add_shipped(self, nbytes: int):
        """A gateway-pre-folded block crossing into the reader's cluster:
        cross-tier bytes that never touched the store's read path (the
        fold output ships, not its inputs)."""
        with self._lock:
            self.cross_bytes += nbytes
            self.aggregated_bytes += nbytes
        for delta in self._scope_stack():
            delta.cross_bytes += nbytes
            delta.aggregated_bytes += nbytes


class BlockStore:
    """In-memory block store with failure + straggler simulation."""

    def __init__(self, topo: Topology):
        self.topo = topo
        self._blocks: dict[tuple, bytes] = {}       # (stripe, block) -> bytes
        self._block_node: dict[tuple, int] = {}
        self._crcs: dict[tuple, bytes] = {}         # -> uint32 CRC per chunk
        self._failed: set[int] = set()
        self._latency: dict[int, float] = {}        # node -> simulated sec
        self.traffic = TrafficStats()
        self._mutation_listeners: list[
            tuple[Callable[[int, int], None],
                  Callable[[list[tuple[int, int]]], None] | None]] = []

    # -- mutation listeners --------------------------------------------------
    def add_mutation_listener(
            self, cb: Callable[[int, int], None], *,
            batch: Callable[[list[tuple[int, int]]], None] | None = None
            ) -> None:
        """Register `cb(stripe, block)` to fire on EVERY content mutation
        of that block — put (write, update, rebuild re-place), drop, and
        node-wide delete. The hot-block cache hangs its invalidation here,
        which is what makes cached/uncached byte-identity an invariant
        rather than a convention: no mutation path can forget to
        invalidate, because the store itself notifies.

        `batch` optionally handles bulk mutations: `put_many` delivers
        its whole [(stripe, block), ...] list in ONE call instead of
        firing `cb` once per block (a 210-block stripe would otherwise
        cost 210 listener round-trips per streamed window). Listeners
        without a batch handler still see every pair, one call each —
        exactness is never traded for batching."""
        self._mutation_listeners.append((cb, batch))

    def _notify_mutation(self, stripe: int, block: int) -> None:
        for cb, _batch in self._mutation_listeners:
            cb(stripe, block)

    def _notify_mutation_many(self, pairs: list[tuple[int, int]]) -> None:
        for cb, batch in self._mutation_listeners:
            if batch is not None:
                batch(pairs)
            else:
                for stripe, block in pairs:
                    cb(stripe, block)

    # -- placement ---------------------------------------------------------
    def _put_nolisten(self, stripe: int, block: int, node: int, data):
        """Store one payload + index entry WITHOUT notifying listeners —
        the shared body of `put` (per-block notify) and `put_many` (one
        batched notify). A block's old CRCs go with its old bytes."""
        key = (stripe, block)
        if key in self._crcs:
            self._drop_crc(key, self._block_node[key])
        self._write(key, node, data)
        self._block_node[key] = node

    def _write(self, key: tuple[int, int], node: int, data) -> None:
        """Land one payload — the only point where the in-memory and disk
        tiers differ on the write path."""
        self._blocks[key] = bytes(data)

    # -- checksums -----------------------------------------------------------
    def set_checksums(self, items) -> None:
        """Keep `(stripe, block), crcs` pairs beside their blocks: `crcs`
        is the block's uint32 CRC32C per chunk (an array or its bytes)."""
        for key, crcs in items:
            self._put_crc(key, self._block_node[key], bytes(crcs))

    def checksums(self, pairs) -> list[bytes | None]:
        """The stored CRCs of each pair, None where a block has none."""
        return [self._crcs.get(key) for key in pairs]

    def _put_crc(self, key: tuple[int, int], node: int, crcs: bytes) -> None:
        self._crcs[key] = crcs

    def _drop_crc(self, key: tuple[int, int], node: int) -> None:
        self._crcs.pop(key, None)

    def corrupt(self, stripe: int, block: int, offset: int, nbytes: int,
                pattern: int | bytes = 0xFF) -> None:
        """Silent corruption, as a latent sector error or a bit flip on the
        way to the platter: XOR `pattern` (one byte repeated, or `nbytes`
        of them) into bytes [offset, offset + nbytes) of a stored block,
        leaving its CRCs and its index entry as they were. No listener is
        told: nothing wrote through the store."""
        key = (stripe, block)
        node = self._block_node[key]
        mask = (np.full(nbytes, pattern, np.uint8) if isinstance(pattern, int)
                else np.frombuffer(pattern, np.uint8))
        data = np.frombuffer(self._payload(key, node), np.uint8).copy()
        if (mask.size != nbytes or not mask.any() or offset < 0
                or offset + nbytes > data.size):
            raise ValueError(f"a non-zero pattern of {nbytes} bytes at "
                             f"{offset} of a {data.size}-byte block")
        data[offset:offset + nbytes] ^= mask
        self._write(key, node, data.tobytes())

    def put(self, stripe: int, block: int, node: int, data: bytes):
        self._put_nolisten(stripe, block, node, data)
        self._notify_mutation(stripe, block)

    def put_many(self, entries) -> int:
        """Bulk landing: place every `(stripe, block, node, data)` entry,
        then fire ONE batched mutation notification for the whole set.
        `data` is anything `bytes()` accepts (numpy row views included —
        the streamed checkpoint writer hands codeword views straight
        through, no per-block `.tobytes()` staging). Per-entry semantics
        are identical to `put`; only the listener fan-out is batched.
        Returns the number of blocks placed."""
        entries = list(entries)
        pairs: list[tuple[int, int]] = []
        with obs.span("store.put_many", blocks=len(entries)):
            for stripe, block, node, data in entries:
                self._put_nolisten(stripe, block, node, data)
                pairs.append((stripe, block))
            if pairs:
                self._notify_mutation_many(pairs)
        return len(pairs)

    def node_of(self, stripe: int, block: int) -> int:
        return self._block_node[(stripe, block)]

    def blocks_on_node(self, node: int) -> list[tuple]:
        return [k for k, nd in self._block_node.items() if nd == node]

    def nodes_holding(self, stripe: int) -> set[int]:
        """Nodes currently holding any block of `stripe` — the public view
        the rebuild engine consults to avoid co-locating re-placed blocks
        of one stripe (the invariant StripeCodec's constructor validates)."""
        return {nd for (s, _b), nd in self._block_node.items() if s == stripe}

    def nodes_holding_many(self, stripes: set[int]) -> dict[int, set[int]]:
        """nodes_holding for many stripes in ONE index pass — the rebuild
        engine heals S stripes per call, and a per-stripe scan would make
        node repair O(S * total_blocks)."""
        out: dict[int, set[int]] = {s: set() for s in stripes}
        for (s, _b), nd in self._block_node.items():
            if s in stripes:
                out[s].add(nd)
        return out

    # -- failure / straggler injection --------------------------------------
    def fail_node(self, node: int):
        self._failed.add(node)

    def heal_node(self, node: int):
        self._failed.discard(node)

    def set_latency(self, node: int, seconds: float):
        self._latency[node] = seconds

    @property
    def failed_nodes(self) -> frozenset:
        return frozenset(self._failed)

    def available(self, stripe: int, block: int) -> bool:
        key = (stripe, block)
        return key in self._blocks and self._block_node[key] not in self._failed

    def latency_of(self, stripe: int, block: int) -> float:
        return self._latency.get(self._block_node[(stripe, block)], 0.0)

    # -- reads --------------------------------------------------------------
    def _payload(self, key: tuple[int, int], node: int) -> bytes:
        """Fetch the stored bytes for an index entry known to be live —
        the only point where the in-memory and disk tiers differ."""
        return self._blocks[key]

    def get(self, stripe: int, block: int, *,
            reader_cluster: int | None = None) -> bytes:
        key = (stripe, block)
        node = self._block_node.get(key)
        if node is None:
            raise KeyError(key)
        if node in self._failed:
            raise NodeFailure(f"node {node} (stripe {stripe} block {block})")
        data = self._payload(key, node)
        cross = (reader_cluster is not None
                 and self.topo.cluster_of(node) != reader_cluster)
        self.traffic.add(len(data), cross)
        return data

    def get_many(self, pairs, *, reader_cluster: int | None = None
                 ) -> dict[tuple[int, int], bytes]:
        """Batched read of many (stripe, block) pairs (deduplicated).

        ONE failure-set check for the whole batch — every pair is
        validated against the index and the failed-node set before any
        payload is touched, so a doomed batch raises with zero traffic
        recorded — and ONE TrafficStats pass at the end instead of a
        per-block `add`. This is the read path under the batched engine:
        a plan group's sources across S stripes are one call here."""
        keys = dict.fromkeys(pairs)
        with obs.span("store.get_many", blocks=len(keys)):
            nodes: dict[tuple[int, int], int] = {}
            for key in keys:
                node = self._block_node.get(key)
                if node is None:
                    raise KeyError(key)
                nodes[key] = node
            for (stripe, block), node in nodes.items():
                if node in self._failed:
                    raise NodeFailure(
                        f"node {node} (stripe {stripe} block {block})")
            out: dict[tuple[int, int], bytes] = {}
            inner = cross = 0
            cluster_of = self.topo.cluster_of
            for key, node in nodes.items():
                data = self._payload(key, node)
                out[key] = data
                if reader_cluster is not None \
                        and cluster_of(node) != reader_cluster:
                    cross += len(data)
                else:
                    inner += len(data)
            self.traffic.add_many(len(out), inner, cross)
        return out

    def drop_block(self, stripe: int, block: int):
        """Simulate loss of a single block replica (latent sector error /
        scrub-detected corruption) while its node stays up. Lets tests and
        failure injection construct arbitrary per-stripe erasure patterns."""
        if (stripe, block) in self._crcs:
            self._drop_crc((stripe, block), self._block_node[(stripe, block)])
        self._blocks.pop((stripe, block), None)
        self._block_node.pop((stripe, block), None)
        self._notify_mutation(stripe, block)

    def delete_node_blocks(self, node: int):
        """Simulate permanent loss of a node's disks."""
        for key in self.blocks_on_node(node):
            self._drop_crc(key, node)
            del self._blocks[key]
            del self._block_node[key]
            self._notify_mutation(*key)


class DiskBlockStore(BlockStore):
    """Durable tier: blocks live under root/node_<i>/s<stripe>_b<block>,
    their CRCs, if any, beside them in s<stripe>_b<block>.crc.

    Inherits the in-memory index for placement/failure bookkeeping but
    persists payloads to disk, so a process restart (the checkpoint/restart
    drill in examples/train_with_failures.py) can re-open the store.
    """

    def __init__(self, topo: Topology, root: str | os.PathLike):
        super().__init__(topo)
        self.root = pathlib.Path(root)
        self.root.mkdir(parents=True, exist_ok=True)

    def _path(self, stripe: int, block: int, node: int) -> pathlib.Path:
        d = self.root / f"node_{node:04d}"
        d.mkdir(exist_ok=True)
        return d / f"s{stripe:06d}_b{block:04d}"

    def _write(self, key: tuple[int, int], node: int, data) -> None:
        # put/put_many inherit from BlockStore and keep their listener
        # semantics; only the payload landing differs (file vs dict).
        self._path(*key, node).write_bytes(data)
        self._blocks[key] = b""                       # payload on disk

    def _crc_path(self, key: tuple[int, int], node: int) -> pathlib.Path:
        path = self._path(*key, node)
        return path.with_name(path.name + ".crc")

    def _put_crc(self, key: tuple[int, int], node: int, crcs: bytes) -> None:
        self._crc_path(key, node).write_bytes(crcs)
        super()._put_crc(key, node, crcs)

    def _drop_crc(self, key: tuple[int, int], node: int) -> None:
        if key in self._crcs:
            self._crc_path(key, node).unlink(missing_ok=True)
        super()._drop_crc(key, node)

    def _payload(self, key: tuple[int, int], node: int) -> bytes:
        return self._path(key[0], key[1], node).read_bytes()

    def reopen(self):
        """Rebuild the index from the directory tree (restart path)."""
        self._blocks.clear()
        self._block_node.clear()
        self._crcs.clear()
        for nd in sorted(self.root.glob("node_*")):
            node = int(nd.name.split("_")[1])
            for f in nd.iterdir():
                name, crc = f.name.removesuffix(".crc"), f.suffix == ".crc"
                s, b = name[1:].split("_b")
                key = (int(s), int(b))
                if crc:
                    self._crcs[key] = f.read_bytes()
                else:
                    self._blocks[key] = b""
                    self._block_node[key] = node

    def drop_block(self, stripe: int, block: int):
        node = self._block_node.get((stripe, block))
        if node is not None:
            p = self._path(stripe, block, node)
            if p.exists():
                p.unlink()
        super().drop_block(stripe, block)

    def delete_node_blocks(self, node: int):
        for key in self.blocks_on_node(node):
            s, b = key
            p = self._path(s, b, node)
            if p.exists():
                p.unlink()
            self._drop_crc(key, node)
            del self._blocks[key]
            del self._block_node[key]
            self._notify_mutation(s, b)
