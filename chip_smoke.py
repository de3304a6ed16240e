"""Chip smoke: the UniLRC store's main path, once, on one TPU chip.

It drives the entry points a user calls, `CheckpointManager` and
`ShardedFrontend`, at a deployment's size:

  180-of-210 UniLRC(2,10), the paper's widest scheme, with 1 MiB blocks on
  Topology(10 clusters x 24 nodes); each 21-block local group sits in one
  cluster. The payload is a train state with Llama 3.2 3B widths cut to 4
  layers: bf16 weights, f32 norm scales and an f32 first moment, about
  2.25 GiB, made on the device from --seed.
    save -> healthy restore
    -> one node down: degraded restore (the XOR fold)
    -> a second node of the same local group down: restore (MXU decode)
    -> reconstruct_failures -> healthy restore
  Every restore must be bit-identical to the state that was saved. Then a
  4-shard ShardedFrontend serves client and degraded reads with a node
  down, each checked against the original bytes, and one checkpoint
  write; and one stripe's codeword is checked byte for byte against the
  NumpyBackend oracle.

  30-of-42 UniLRC(1,6) on Topology(6 x 8), one layer of weights (about
  190 MiB): save, one node down, degraded restore (a 6-way XOR fold).

Run it on the machine with the chip:

    python chip_smoke.py [--seed N]

It runs every phase in this one process. It exits non-zero, and prints no
result line, when JAX's first device is not a TPU or when any check
fails. The phase times it prints are wall seconds of one run, compiles
included unless the compile cache already holds them: smoke timings,
not benchmark numbers. The last line of stdout is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import math
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.ckpt import BlockStore, CheckpointManager  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.core.codes import make_unilrc  # noqa: E402
from repro.io import Priority, ShardedFrontend  # noqa: E402
from repro.io.backend import NumpyBackend  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.topo import Topology  # noqa: E402

GiB = 1 << 30
ARCH = "llama3.2-3b"           # the payload's widths, from repro.configs


@dataclasses.dataclass(frozen=True)
class Deployment:
    """One store configuration and the train state it checkpoints."""
    alpha: int                 # UniLRC(alpha, z)
    z: int
    clusters: int
    nodes_per_cluster: int
    block_size: int
    smoke_widths: bool         # ARCH's cut-down widths (CPU tests)
    layers: int
    moment: str | None         # dtype of the optimizer's first moment
    min_payload: int           # bytes the payload must reach
    min_windows: int           # write windows the save must span


WIDE = Deployment(alpha=2, z=10, clusters=10, nodes_per_cluster=24,
                  block_size=1 << 20, smoke_widths=False, layers=4,
                  moment="float32", min_payload=2 * GiB, min_windows=4)
NARROW = Deployment(alpha=1, z=6, clusters=6, nodes_per_cluster=8,
                    block_size=1 << 20, smoke_widths=False, layers=1,
                    moment=None, min_payload=128 << 20, min_windows=1)


class SmokeFailure(RuntimeError):
    """A check of the smoke run failed."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


class Phases:
    """Wall seconds per phase, printed as smoke timings as they end."""

    def __init__(self, log):
        self.log = log
        self.seconds: dict[str, float] = {}

    @contextlib.contextmanager
    def __call__(self, name: str):
        t0 = time.perf_counter()
        yield
        dt = time.perf_counter() - t0
        self.seconds[name] = dt
        self.log(f"smoke timing (one run, not a benchmark): "
                 f"{name} {dt:.3f} s")


# -- payload ------------------------------------------------------------------

def make_state(dep: Deployment, seed: int):
    """Train-state pytree made on the device: per layer the attention and
    MLP weights (bf16) and two norm scales (f32), plus an optional first
    moment of every parameter and an int32 step."""
    cfg = get_config(ARCH, smoke=dep.smoke_widths)
    d, ff = cfg.d_model, cfg.d_ff
    hd = cfg.head_dim or d // cfg.num_heads
    q, kv = cfg.num_heads * hd, cfg.num_kv_heads * hd
    shapes = {"wq": (d, q), "wk": (d, kv), "wv": (d, kv), "wo": (q, d),
              "w_gate": (d, ff), "w_up": (d, ff), "w_down": (ff, d)}
    key = jax.random.key(seed)

    def leaf(shape, dtype):
        nonlocal key
        key, sub = jax.random.split(key)
        return 0.02 * jax.random.normal(sub, shape, dtype)

    def layer(wdtype, ndtype):
        out = {name: leaf(s, wdtype) for name, s in shapes.items()}
        out["attn_norm"] = leaf((d,), ndtype)
        out["mlp_norm"] = leaf((d,), ndtype)
        return out

    state = {"params": [layer(jnp.bfloat16, jnp.float32)
                        for _ in range(dep.layers)],
             "step": jnp.int32(seed)}
    if dep.moment is not None:
        state["mu"] = [layer(dep.moment, dep.moment)
                       for _ in range(dep.layers)]
    return state


def flat_bytes(tree) -> np.ndarray:
    """The tree's leaves, in flatten order, as one host byte array: the
    reference every restore and read is compared with."""
    leaves = jax.tree_util.tree_leaves(tree)
    out = np.empty(sum(x.nbytes for x in leaves), np.uint8)
    off = 0
    for x in leaves:
        b = np.asarray(x).reshape(-1).view(np.uint8)
        out[off:off + b.size] = b
        off += b.size
    return out


def check_identical(restored, ref: np.ndarray, specs: list, what: str):
    leaves = jax.tree_util.tree_leaves(restored)
    check(len(leaves) == len(specs), f"{what}: {len(leaves)} leaves, "
          f"saved {len(specs)}")
    off = 0
    for x, (shape, dtype) in zip(leaves, specs):
        check(x.shape == shape and x.dtype == dtype,
              f"{what}: leaf {x.shape} {x.dtype}, saved {shape} {dtype}")
        b = np.ascontiguousarray(x).reshape(-1).view(np.uint8)
        check(np.array_equal(b, ref[off:off + b.size]),
              f"{what}: bytes differ in the leaf at offset {off}")
        off += b.size
    check(off == ref.size, f"{what}: {off} bytes restored, saved {ref.size}")


# -- passes ---------------------------------------------------------------------

def _store_and_manager(dep: Deployment):
    code = make_unilrc(dep.alpha, dep.z)
    store = BlockStore(Topology(dep.clusters, dep.nodes_per_cluster))
    mgr = CheckpointManager(store, code, block_size=dep.block_size,
                            backend="kernels")
    return code, store, mgr


def _save(dep: Deployment, mgr, seed: int, phase: Phases, log):
    """Make the payload, save it, and return (reference bytes, leaf specs,
    stripe metas)."""
    with phase(f"{mgr.code.name} make payload"):
        state = make_state(dep, seed)
        jax.block_until_ready(state)
    specs = [(x.shape, np.dtype(x.dtype))
             for x in jax.tree_util.tree_leaves(state)]
    ref = flat_bytes(state)
    log(f"{mgr.code.name} payload: {len(specs)} leaves, {ref.size} bytes "
        f"= {ref.size / GiB:.3f} GiB")
    check(ref.size >= dep.min_payload,
          f"payload {ref.size} bytes, under {dep.min_payload}")
    with phase(f"{mgr.code.name} save"):
        nstripes = mgr.save(state, step=0)
    del state
    stripe_payload = mgr.code.k * dep.block_size
    windows = math.ceil(nstripes / mgr.codec.max_batch_stripes)
    tail = ref.size % stripe_payload
    log(f"{mgr.code.name} save: {nstripes} stripes in {windows} windows "
        f"of <= {mgr.codec.max_batch_stripes}, padded tail "
        f"{stripe_payload - tail if tail else 0} bytes")
    check(windows >= dep.min_windows,
          f"save spans {windows} windows, under {dep.min_windows}")
    check(tail != 0, "payload is a whole number of stripes: no padded tail")
    return ref, specs, mgr.stripes_of(0)


def _restore(mgr, ref, specs, phase: Phases, what: str):
    with phase(f"{mgr.code.name} {what} restore"):
        state, report = mgr.restore(0)
    check_identical(state, ref, specs, f"{mgr.code.name} {what} restore")
    return report


def _launched(name: str, before: dict[str, int]) -> int:
    return ops.kernel_launch_snapshot().get(name, 0) - before.get(name, 0)


def wide_pass(dep: Deployment, seed: int, log, phase: Phases) -> None:
    code, store, mgr = _store_and_manager(dep)
    ref, specs, metas = _save(dep, mgr, seed, phase, log)
    k, n, bs = code.k, code.n, dep.block_size

    report = _restore(mgr, ref, specs, phase, "healthy")
    check(report.degraded_blocks == 0, "healthy restore read degraded")

    # One node down: the first stripe's block 0 and whatever else it holds.
    sid0 = metas[0].stripe_id
    node1 = store.node_of(sid0, 0)
    store.fail_node(node1)
    before = ops.kernel_launch_snapshot()
    report = _restore(mgr, ref, specs, phase, "one-failure degraded")
    log(f"{code.name} one-failure restore: {report.degraded_blocks} "
        f"degraded blocks, cross-cluster bytes {report.cross_cluster_bytes}")
    check(report.degraded_blocks > 0, "no block was read degraded")
    check(report.cross_cluster_bytes == 0, "degraded restore crossed clusters")
    check(_launched("xor_reduce", before) > 0, "no XOR fold ran")

    # A second node of the same local group: the first stripe now has two
    # erasures in one group, which only the multi-erasure decode recovers.
    group = next(g for g in code.groups if 0 in g)
    b2 = next(b for b in group if b != 0 and code.block_type[b] == "d")
    node2 = store.node_of(sid0, b2)
    store.fail_node(node2)
    before = ops.kernel_launch_snapshot()
    report = _restore(mgr, ref, specs, phase, "two-failure")
    log(f"{code.name} two-failure restore: {report.degraded_blocks} "
        f"degraded blocks")
    check(_launched("gf_bitmatmul", before) > 0, "no MXU decode ran")

    with phase(f"{code.name} reconstruct_failures"):
        rebuilt = mgr.reconstruct_failures()
    log(f"{code.name} reconstruct_failures: {rebuilt} blocks rebuilt")
    check(rebuilt > 0, "nothing was rebuilt")
    check(not store.failed_nodes, "failed nodes remain after rebuild")
    check(all(store.available(m.stripe_id, b) for m in metas
              for b in range(n)), "a block is missing after rebuild")
    report = _restore(mgr, ref, specs, phase, "post-rebuild")
    check(report.degraded_blocks == 0, "post-rebuild restore read degraded")

    serve(code, store, mgr, metas, ref, seed, log, phase)

    # The NumpyBackend oracle on one full stripe, rebuilt blocks included.
    with phase(f"{code.name} numpy oracle, one stripe"):
        data = ref[:k * bs].reshape(1, k, bs)
        expect = NumpyBackend().encode_many(code, data)[0]
        stored = np.stack([np.frombuffer(store.get(sid0, b), np.uint8)
                           for b in range(n)])
    check(np.array_equal(stored, expect),
          f"stripe {sid0} differs from the NumpyBackend encoding")
    log(f"{code.name} oracle: stripe {sid0}, {n} blocks, byte-identical")


def serve(code, store, mgr, metas, ref, seed: int, log,
          phase: Phases) -> None:
    """4-shard front-end with a node down: client reads of every stripe,
    two rounds of degraded reads of every block the node held, then one
    checkpoint write read back once the node is back."""
    k, bs = code.k, mgr.block_size
    index = {m.stripe_id: i for i, m in enumerate(metas)}
    node = store.node_of(metas[-1].stripe_id, 0)
    cluster = store.topo.cluster_of(node)
    lost = sorted(p for p in store.blocks_on_node(node) if p[0] in index)
    held = {p: store.get(*p) for p in lost}      # the bytes before the loss
    store.fail_node(node)

    def original(sid: int) -> bytes:
        i = index[sid]
        return ref[i * k * bs:(i + 1) * k * bs].tobytes()

    with ShardedFrontend(mgr.codec, num_shards=4) as fe, \
            phase(f"{code.name} serve"):
        client = [(m, fe.submit_client_read(m)) for m in metas]
        degraded = [(p, fe.submit_degraded_read(metas[index[p[0]]], p[1],
                                                reader_cluster=cluster))
                    for _ in range(2) for p in lost]
        fe.drain()
        for m, h in client:
            check(h.result() == original(m.stripe_id)[:m.nbytes],
                  f"client read of stripe {m.stripe_id} differs")
        for p, h in degraded:
            check(h.result() == held[p], f"degraded read of {p} differs")
        store.heal_node(node)

        stripe_payload = k * bs
        size = stripe_payload + stripe_payload // 2 + 7
        buf = np.asarray(jax.random.bits(jax.random.key(seed + 1), (size,),
                                         jnp.uint8)).tobytes()
        start = max(m.stripe_id for m in metas) + 1
        write = fe.submit_checkpoint_write(buf, start)
        fe.drain()
        new = write.result()
        reads = [fe.submit_client_read(m) for m in new]
        fe.drain()
        got = b"".join(h.result() for h in reads)
        check(got == buf, "checkpoint write through the front-end differs")
        stats = fe.stats
    requests = sum(s.requests for s in stats.values())
    log(f"{code.name} serve: {len(client) + len(reads)} client reads, "
        f"{len(degraded)} degraded reads of node {node}, 1 checkpoint "
        f"write of {len(new)} stripes; {requests} requests served")
    for prio, s in stats.items():
        check(s.failed_requests == 0, f"{prio.name}: failed requests")
        check(s.shed_requests == 0, f"{prio.name}: shed requests")
    check(len(degraded) > 0, "the failed node held no block to read")
    check(stats[Priority.DEGRADED_READ].cross_bytes == 0,
          "degraded reads crossed clusters")


def narrow_pass(dep: Deployment, seed: int, log, phase: Phases) -> None:
    code, store, mgr = _store_and_manager(dep)
    ref, specs, metas = _save(dep, mgr, seed, phase, log)
    store.fail_node(store.node_of(metas[0].stripe_id, 0))
    before = ops.kernel_launch_snapshot()
    report = _restore(mgr, ref, specs, phase, "one-failure degraded")
    log(f"{code.name} one-failure restore: {report.degraded_blocks} "
        f"degraded blocks, cross-cluster bytes {report.cross_cluster_bytes}")
    check(report.degraded_blocks > 0, "no block was read degraded")
    check(report.cross_cluster_bytes == 0, "degraded restore crossed clusters")
    check(_launched("xor_reduce", before) > 0, "no XOR fold ran")


def run(wide: Deployment, narrow: Deployment, seed: int, log=print) -> dict:
    """Both passes; returns the phase seconds and the kernel launches."""
    ops.reset_kernel_launch_counts()
    phase = Phases(log)
    wide_pass(wide, seed, log, phase)
    narrow_pass(narrow, seed, log, phase)
    launches = ops.kernel_launch_snapshot()
    log(f"KERNEL_LAUNCHES: {json.dumps(launches, sort_keys=True)}")
    for name in ("gf_bitmatmul", "xor_reduce"):
        check(launches.get(name, 0) > 0, f"no {name} launch")
    return {"seconds": phase.seconds, "launches": launches}


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: no TPU found; JAX's first device is on "
              f"platform {platform!r}", file=sys.stderr)
        return 1
    kind = devices[0].device_kind
    print(f"device: {kind}, count {len(devices)}; compile cache "
          f"{enable_compile_cache()}")
    run(WIDE, NARROW, args.seed)
    stats = devices[0].memory_stats() or {}
    print(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use')}")
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": kind, "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
