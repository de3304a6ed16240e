"""Back-to-back `CheckpointManager.save` of the seeded train state, each
save's state changed in every element, as a training step would. Saves
older than the last `keep_saves` are sampled for the check and dropped, so
host memory stays bounded.

Counters: `saves`, `stripes_encoded`, `launches`.

The check holds every save, the warm-up's too, to the plain reference:
  stored_bytes_wrong          every block of every stripe at the first,
                              the last and `SAVE_CHECK_COLUMNS` columns
                              drawn from the seed, taken as each save is
                              dropped or at the end (`save_check`);
  stored_bytes_wrong_in_full  every byte of every block of the saves still
                              held at the end: data against the state's
                              bytes, parities against the reference's
                              exact encode of them, on the device;
and the placement guarantees of every stripe.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness import payload as pl
from bench.harness import reference, save_check
from bench.harness.kind import Base, failed, launches
from bench.harness.record import Check, Op
from bench.harness.trace import span


@jax.jit
def saved_stripes(flat, low, c):
    """The bytes a save with byte `c` stores, as (stripes, k, B): the
    state's `flat` bytes, padded to whole stripes, with `c` XORed into its
    `low` ones."""
    return flat ^ (low * c)


@jax.jit
def stripe_bytes_wrong(a_bits, stored, saved, i):
    """Bytes of a stored stripe (n, B) that differ from stripe `i` of the
    saved bytes (stripes, k, B) followed by its parities."""
    data = jax.lax.dynamic_index_in_dim(saved, i, keepdims=False)
    k = data.shape[0]
    parity = reference.encode_on_device(a_bits, data)
    return (jnp.sum(stored[:k] != data, dtype=jnp.int32)
            + jnp.sum(stored[k:] != parity, dtype=jnp.int32))


class Kind(Base):
    def setup(self) -> None:
        with span("make_state"):
            self.state0 = pl.state_maker(self.cfg)(self.key)
            jax.block_until_ready(self.state0)
        self.nbytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(
            self.state0))
        self.stripes = math.ceil(self.nbytes / (self.code.k * self.B))
        self.keep = self.traffic["keep_saves"]
        self.live: list[tuple[int, list]] = []     # (xor byte, metas)
        self.sampled = save_check.Samples(self.store, self.code, self.B,
                                          self.rng)
        self.step = 0

    def _save(self) -> None:
        c = int(self.rng.integers(1, 256))
        with span("perturb"):
            state = pl.perturb(self.state0, np.uint8(c))
        with span("save"):
            self.mgr.save(state, step=self.step)
        self.live.append((c, self.mgr.stripes_of(self.step)))
        self.step += 1

    def _retire(self) -> None:
        with span("sample_and_drop"):
            while len(self.live) > self.keep:
                self.sampled.take(*self.live.pop(0), drop=True)

    def warmup(self) -> None:
        self._save()
        self._retire()

    def window(self, run, clock) -> None:
        l0 = launches(self.ops)
        while clock() < run.seconds:
            op = Op("save", None, self.nbytes)
            run.ops.append(op)
            try:
                self._save()
            except Exception:
                failed("save")
                break
            op.done = clock()
            self._retire()
        run.end = max((op.done for op in run.ops if op.done is not None),
                      default=clock())
        saves = sum(op.done is not None for op in run.ops)
        run.counters.update(saves=saves, stripes_encoded=saves * self.stripes,
                            launches=launches(self.ops) - l0)

    def _in_full(self, flat: np.ndarray, low: np.ndarray) -> int:
        """Bytes wrong in the saves still held, every byte of them, from
        the state's bytes on the host; compared on the device."""
        shape = (self.stripes, self.code.k, self.B)
        a_bits = jnp.asarray(reference.bit_matrix(self.A))
        flat = jax.device_put(flat.reshape(shape))
        low = jax.device_put(low.reshape(shape))
        wrong = 0
        for c, metas in self.live:
            saved = saved_stripes(flat, low, np.uint8(c))
            for i, meta in enumerate(metas):
                blocks, _ = save_check.blocks(self.store, self.code.n,
                                              meta.stripe_id)
                stored = np.stack([np.zeros(self.B, np.uint8) if x is None
                                   else x for x in blocks])
                wrong += int(stripe_bytes_wrong(a_bits, stored, saved, i))
        return wrong

    def check(self, run) -> None:
        for c, metas in self.live:
            self.sampled.take(c, metas, drop=False)
        size = self.stripes * self.code.k * self.B
        flat = pl.flat_bytes(self.state0, size)
        low = pl.low_bytes(self.state0, size)
        bad, faults = self.sampled.wrong(flat, low, self.A, self.groups,
                                         self.cfg["nodes_per_cluster"])
        del self.state0                  # room on the device for the bytes
        in_full = self._in_full(flat, low)
        run.checks += [
            Check("saves_failed", sum(op.done is None for op in run.ops), 0),
            Check("stripes_unchecked", self.step * self.stripes
                  - len(self.sampled.samples), 0),
            Check("blocks_missing", self.sampled.missing, 0),
            Check("stored_bytes_wrong", bad, 0),
            Check("stored_bytes_wrong_in_full", in_full, 0),
            Check("placement_faults", faults, 0)]
