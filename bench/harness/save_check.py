"""The sampled check of what a save stored, for every kind that saves: all
blocks of every stripe at the first, the last and `SAVE_CHECK_COLUMNS`
columns drawn from the seed, against the plain reference, and every
stripe's placement."""
from __future__ import annotations

import numpy as np

from . import reference

# Columns of every stored block that the sampled check compares with the
# reference: the first, the last and this many more, drawn from the seed.
SAVE_CHECK_COLUMNS = 2048


def blocks(store, n: int, sid: int) -> tuple[list, list[int]]:
    """A stripe's `n` blocks as the store holds them, and their nodes; a
    block the store does not hold is None, on node -1 - b."""
    out, nodes = [], []
    for b in range(n):
        try:
            out.append(np.frombuffer(store.get(sid, b), np.uint8))
            nodes.append(store.node_of(sid, b))
        except (KeyError, IndexError):
            out.append(None)
            nodes.append(-1 - b)
    return out, nodes


class Samples:
    """The checked columns of saves' stored blocks, kept until the check.
    The columns are the first draw from `rng`."""

    def __init__(self, store, code, B: int, rng):
        self.store, self.n, self.k, self.B = store, code.n, code.k, B
        self.cols = np.unique(np.concatenate([
            [0, B - 1], rng.integers(0, B, SAVE_CHECK_COLUMNS)]))
        # (xor byte, stripe index in its save, (n, cols) bytes, nodes)
        self.samples: list[tuple[int, int, np.ndarray, list[int]]] = []
        self.missing = 0

    def take(self, c: int, metas: list, drop: bool) -> None:
        """Keep the checked columns of the blocks of a save whose state had
        byte `c` XORed into its low bytes, then maybe drop them."""
        empty = np.zeros(self.cols.size, np.uint8)
        for i, meta in enumerate(metas):
            got, nodes = blocks(self.store, self.n, meta.stripe_id)
            self.missing += sum(x is None for x in got)
            self.samples.append((c, i, np.stack(
                [empty if x is None else x[self.cols] for x in got]), nodes))
            if drop:
                for b in range(self.n):
                    self.store.drop_block(meta.stripe_id, b)

    def wrong(self, flat: np.ndarray, low: np.ndarray | None, A, groups,
              nodes_per_cluster: int) -> tuple[int, int]:
        """Sampled bytes that differ from the reference, and placement
        faults. The saved bytes are `flat` (`payload.flat_bytes`) with each
        sample's byte XORed into the `low` ones (`payload.low_bytes`); with
        no `low`, `flat` itself."""
        k, B = self.k, self.B
        bad = faults = 0
        for c, i, got, nodes in self.samples:
            pos = (i * k + np.arange(k))[:, None] * B + self.cols[None, :]
            data = flat[pos]
            if low is not None:
                data = data ^ (low[pos] * np.uint8(c))
            expect = np.concatenate([data, reference.encode_columns(A, data)])
            bad += int(np.count_nonzero(expect != got))
            faults += reference.placement_faults(nodes, groups,
                                                 nodes_per_cluster)
        return bad, faults
