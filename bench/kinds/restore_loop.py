"""One save in set-up, then the failure the traffic file names, then
back-to-back `CheckpointManager.restore` + `device_put` of the leaves +
`block_until_ready`: a job resuming on the chip.

Counters: `restores`, `launches`, `store_bytes` (what the block store
handed out), `degraded_blocks` (the program's own report), and the repair
the failure leaves to each restore, counted from the placement and the
code's local groups, not from the program:
  blocks_folded, fold_sources    lost data blocks in a group that lost
                                 only them, and the group's live blocks
                                 that rebuild them by XOR;
  stripes_decoded, blocks_decoded  stripes with a group that lost two
                                 blocks or more, and their lost data
                                 blocks, rebuilt from k sources.

The check holds every restore's leaves on the device, bit for bit, to the
state saved, and the set-up save, sampled before the failure while every
node is up, to the plain reference as `save_loop` holds its saves
(`save_check`): `stored_bytes_wrong`, `blocks_missing`, `placement_faults`.
A parity that no repair reads is seen only there.
"""
from __future__ import annotations

import sys
import time

import jax

from bench.harness import payload as pl
from bench.harness import save_check
from bench.harness.kind import Base, failed, launches
from bench.harness.record import Check, Op
from bench.harness.trace import span


class Kind(Base):
    def setup(self) -> None:
        with span("make_state"):
            self.state0 = pl.state_maker(self.cfg)(self.key)
            jax.block_until_ready(self.state0)
        self.nbytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(
            self.state0))
        self.specs = [(x.shape, x.dtype) for x in
                      jax.tree_util.tree_leaves(self.state0)]
        self.sampled = save_check.Samples(self.store, self.code, self.B,
                                          self.rng)
        with span("save"):
            self.mgr.save(self.state0, step=0)
        metas = self.mgr.stripes_of(0)
        self.stripes = len(metas)
        t0 = time.perf_counter()
        with span("sample"):
            self.sampled.take(0, metas, drop=False)
        print(f"bench: set-up save sampled in {time.perf_counter() - t0:.3f}"
              " s", file=sys.stderr)
        self.fail(metas[0].stripe_id)
        self.repair = self._repair(metas)
        self.wrong: list = []
        self.bad_shape = 0

    def _repair(self, metas) -> dict[str, int]:
        """What one restore has to rebuild, stripe by stripe."""
        k = self.code.k
        out = dict(blocks_folded=0, fold_sources=0, stripes_decoded=0,
                   blocks_decoded=0)
        down = set(self.failed)
        for meta in metas:
            lost = {b for b in range(self.code.n)
                    if self.store.node_of(meta.stripe_id, b) in down}
            groups = [(g, lost & set(g)) for g in self.groups]
            if any(len(gone) > 1 for _g, gone in groups):
                out["stripes_decoded"] += 1
                out["blocks_decoded"] += sum(b < k for b in lost)
                continue
            for g, gone in groups:
                if gone and min(gone) < k:
                    out["blocks_folded"] += 1
                    out["fold_sources"] += len(g) - 1
        return out

    def traffic_bytes(self) -> int:
        t = self.store.traffic
        return t.inner_bytes + t.cross_bytes

    def _restore(self):
        with span("restore"):
            state, report = self.mgr.restore(0)
        with span("device_put"):
            dev = jax.device_put(state)
            jax.block_until_ready(dev)
        return dev, report

    def _compare(self, dev) -> None:
        """Bit for bit against the state saved, on the device."""
        with span("compare"):
            leaves = jax.tree_util.tree_leaves(dev)
            if [(x.shape, x.dtype) for x in leaves] != self.specs:
                self.bad_shape += 1
                return
            self.wrong.append(pl.mismatched_words(self.state0, dev))

    def warmup(self) -> None:
        dev, _ = self._restore()
        self._compare(dev)

    def window(self, run, clock) -> None:
        l0, b0 = launches(self.ops), self.traffic_bytes()
        degraded = 0
        while clock() < run.seconds:
            op = Op("restore", None, self.nbytes)
            run.ops.append(op)
            try:
                dev, report = self._restore()
            except Exception:
                failed("restore")
                break
            op.done = clock()
            degraded += report.degraded_blocks
            self._compare(dev)
            del dev
        run.end = max((op.done for op in run.ops if op.done is not None),
                      default=clock())
        restores = sum(op.done is not None for op in run.ops)
        run.counters.update(
            restores=restores, launches=launches(self.ops) - l0,
            store_bytes=self.traffic_bytes() - b0, degraded_blocks=degraded,
            **{key: restores * v for key, v in self.repair.items()})

    def check(self, run) -> None:
        wrong = sum(int(w) for ws in self.wrong for w in ws)
        flat = pl.flat_bytes(self.state0, self.stripes * self.code.k * self.B)
        bad, faults = self.sampled.wrong(flat, None, self.A, self.groups,
                                         self.cfg["nodes_per_cluster"])
        run.checks += [
            Check("restores_failed", sum(op.done is None for op in run.ops),
                  0),
            Check("restores_with_wrong_leaves", self.bad_shape, 0),
            Check("restored_words_wrong", wrong, 0),
            Check("blocks_missing", self.sampled.missing, 0),
            Check("stored_bytes_wrong", bad, 0),
            Check("placement_faults", faults, 0)]
