"""What every traffic kind shares: the store, the manager, the failure the
traffic file names, and the count of kernel launches.

A traffic file's `kind` names `bench/kinds/<kind>.py`, which defines
`Kind(Base)` with `setup`, `warmup` (the cell's own operation, through the
entry point, so every shape the window uses is compiled in set-up),
`window(run, clock)`, `check(run)` and `close`. Times are seconds from the
window's start. A kind records raw counts in `run.counters` (operations,
stripes coded, blocks folded and their sources, bytes read); each
per-layer metric works out what it needs from them. Every call into the
program sits in a `bench.<what>` span, so a traced run can say what the
host was doing in each idle gap of the device.

Failures a traffic file can name (`fail`):
  none      no node fails
  node      the node holding block 0 of the cell's first stripe
  cluster   every node of cluster `cluster`
"""
from __future__ import annotations

import sys
import traceback

import jax
import numpy as np

from . import reference


def launches(ops) -> int:
    return sum(ops.kernel_launch_snapshot().values())


def failed(what: str) -> None:
    print(f"bench: {what} failed:", file=sys.stderr)
    traceback.print_exc(file=sys.stderr)


class Base:
    """Shared set-up: the store, the manager and the failure."""

    def __init__(self, cfg: dict, traffic: dict, seed: int, backend="kernels"):
        # the system under test, imported when a cell is built
        from repro.ckpt import BlockStore, CheckpointManager
        from repro.core.codes import make_unilrc
        from repro.kernels import ops
        from repro.topo import Topology
        self.ops = ops
        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        self.rng = np.random.default_rng(seed)
        self.code = make_unilrc(cfg["alpha"], cfg["z"])
        if (self.code.n, self.code.k) != (cfg["n"], cfg["k"]):
            raise ValueError(f"{self.code.name} is not {cfg['k']}-of-"
                             f"{cfg['n']}")
        self.B = cfg["block_size"]
        self.store = BlockStore(Topology(cfg["clusters"],
                                         cfg["nodes_per_cluster"]))
        self.mgr = CheckpointManager(self.store, self.code,
                                     block_size=self.B, backend=backend)
        self.A, self.groups = reference.unilrc(cfg["alpha"], cfg["z"])
        # JAX keys take 32 bits; fold the rest in, so no two seeds share data
        self.key = jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF),
                                      seed >> 32)
        self.failed: list[int] = []

    def fail(self, first_stripe: int) -> None:
        """The failure the traffic file names."""
        how = self.traffic["fail"]
        npc = self.cfg["nodes_per_cluster"]
        if how == "none":
            self.failed = []
        elif how == "node":
            self.failed = [self.store.node_of(first_stripe, 0)]
        elif how == "cluster":
            c = self.traffic["cluster"]
            self.failed = list(range(c * npc, (c + 1) * npc))
        else:
            raise ValueError(f"unknown failure {how!r}")
        for node in self.failed:
            self.store.fail_node(node)

    def close(self) -> None:
        pass
