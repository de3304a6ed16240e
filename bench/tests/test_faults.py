"""With the timed path broken underneath, a whole run (past the look for a
chip) reports `correct` false: once for each fault a cell can have.

  flipped          an answer altered where it is produced: one byte of
                   every encode, fold or decode the kernels return, at an
                   interior column drawn from a seed
  half_batch       half of each batched kernel call left out: the later
                   half of its stripes come back as zeros
  corrupt_store    one byte of every block the store hands out, at a
                   place that differs from block to block (flips at one
                   place would cancel in an XOR fold of an even count)
  lands_nothing    a save that leaves the store as it was (saves only)

A cell on one chip has no exchange between chips to leave out.
"""
from __future__ import annotations

import numpy as np
import pytest

from repro.io.backend import KernelBackend

from .tiny import CELLS, run_tiny


FLIP_AT = int(np.random.default_rng(2**31 + 5).integers(1 << 30))


def _flip(x) -> np.ndarray:
    x = np.array(x)
    x[..., 1 + FLIP_AT % (x.shape[-1] - 2)] ^= 1
    return x


def _half(x) -> np.ndarray:
    x = np.array(x)
    x[x.shape[0] // 2:] = 0
    return x


class Flipped(KernelBackend):
    name = "flipped"

    def encode_many_lazy(self, code, data):
        return _flip(KernelBackend.encode_many_lazy(self, code, data))

    def encode_many(self, code, data):
        return self.encode_many_lazy(code, data)

    def recover_many(self, plan, stacked):
        return _flip(super().recover_many(plan, stacked))

    def apply_decode_many(self, plan, stacked):
        return {e: _flip(v) for e, v in
                super().apply_decode_many(plan, stacked).items()}


class HalfBatch(KernelBackend):
    name = "half_batch"

    def encode_many_lazy(self, code, data):
        out = np.array(KernelBackend.encode_many_lazy(self, code, data))
        out[:, code.k:] = _half(out[:, code.k:])
        return out

    def encode_many(self, code, data):
        return self.encode_many_lazy(code, data)

    def recover_many(self, plan, stacked):
        return _half(super().recover_many(plan, stacked))

    def apply_decode_many(self, plan, stacked):
        return {e: _half(v) for e, v in
                super().apply_decode_many(plan, stacked).items()}


def _corrupt_store(monkeypatch):
    from repro.ckpt.store import BlockStore
    real = BlockStore._payload

    def flipped(self, key, node):
        data = bytearray(real(self, key, node))
        data[(7 * key[0] + key[1]) % len(data)] ^= 1
        return bytes(data)
    monkeypatch.setattr(BlockStore, "_payload", flipped)


def _lands_nothing(monkeypatch):
    from repro.ckpt.store import BlockStore
    monkeypatch.setattr(BlockStore, "put_many",
                        lambda self, entries: 0)


STORE_FAULTS = {"corrupt_store": _corrupt_store,
                "lands_nothing": _lands_nothing}
BACKEND_FAULTS = {"flipped": Flipped, "half_batch": HalfBatch}

CASES = [(c, f) for c in CELLS
         for f in ("flipped", "half_batch", "corrupt_store")]
CASES += [("wide-save", "lands_nothing")]


@pytest.mark.parametrize(("cell_name", "fault"), CASES)
def test_a_fault_makes_the_run_incorrect(cell_name, fault, monkeypatch):
    backend = "kernels"
    if fault in BACKEND_FAULTS:
        backend = BACKEND_FAULTS[fault]()
    else:
        STORE_FAULTS[fault](monkeypatch)
    out = run_tiny(cell_name, backend=backend)
    assert out["correct"] is False, out["checks"]
    assert any(c["value"] > c["limit"] for c in out["checks"].values())
