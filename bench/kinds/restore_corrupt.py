"""`restore_loop` on a checksummed store with latent sector errors: one
save in set-up by a manager that keeps the config's checksums, its sampled
check while every block is sound, then one latent error in each stripe:
a `sector_bytes`-aligned sector of `sector_bytes` (cut to the block if it
is smaller) of one data block, the block and the sector drawn from the
seed, every byte XORed with a non-zero byte drawn from it too, injected
through `BlockStore.corrupt`, so the stored CRCs stay as they were. The
corruption stays in the store: every restore has to find it and rebuild
each corrupt block from its local group, by the XOR fold.

A failed restore, in the warm-up too, is recorded as failed.

Counters, besides `restore_loop`'s, per restore from the corruption
injected and the code's groups, not from the program:
  blocks_folded, fold_sources  each corrupt block, and its group's other
                               blocks, which rebuild it by XOR;
  blocks_to_verify             the blocks a restore must check at the
                               least: every stripe's data blocks and the
                               fold's parity sources;
and, from the program, `corrupt_reported` (`RestoreReport.corrupt_blocks`)
and `obs.CHECKSUMS` read around the window: `bytes_verified`,
`corrupt_chunks`, `corrupt_blocks`.

Checks, besides `restore_loop`'s (each limit 0):
  warmup_restores_failed
  blocks_unverified       of the blocks the window's restores had to
                          check at the least, how many bytes_verified
                          leaves out, in blocks: the fold's parity
                          sources are checked like the data;
  stored_crc_wrong        every stored CRC of the set-up save against
                          `crc32c_reference` of the bytes that should be
                          stored: the state's data blocks and the
                          reference's parities, on the device;
  corrupt_blocks_missed   injected but not reported, summed over restores;
  clean_blocks_reported   reported but not injected.
"""
from __future__ import annotations

import functools
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness import crc32c_reference
from bench.harness import payload as pl
from bench.harness import reference, spec
from bench.harness.kind import failed
from bench.harness.record import Check
from bench.harness.trace import span

CHECKSUMS = ("bytes_verified", "corrupt_chunks", "corrupt_blocks")


@functools.partial(jax.jit, static_argnames="nbytes")
def stripe_crcs(a_bits, data, *, nbytes: int):
    """The CRCs a stripe of data (k, B) should be stored with: its data
    blocks' and its reference parities', (n, B // nbytes)."""
    parity = reference.encode_on_device(a_bits, data)
    return crc32c_reference.chunk_crcs(jnp.concatenate([data, parity]),
                                       nbytes)


class Kind(spec.kind("restore_loop")):
    def __init__(self, cfg: dict, traffic: dict, seed: int,
                 backend="kernels"):
        super().__init__(cfg, traffic, seed, backend=backend)
        from repro import obs
        from repro.ckpt import CheckpointManager
        from repro.core.crc32c import BYTES_PER_CHECKSUM
        if cfg["bytes_per_checksum"] != BYTES_PER_CHECKSUM:
            raise ValueError(f"the store checksums {BYTES_PER_CHECKSUM}-byte "
                             f"chunks, the config asks for "
                             f"{cfg['bytes_per_checksum']}")
        self.obs = obs
        self.mgr = CheckpointManager(
            self.store, self.code, block_size=self.B, backend=backend,
            checksum=cfg["checksum"])
        self.reports: list[set] = []
        self.warmup_failed = 0

    def setup(self) -> None:
        super().setup()
        with span("corrupt"):
            self.injected = self._inject(self.mgr.stripes_of(0))
        sources = [len(g) - 1 for g in self._groups_of(self.injected)]
        parities = [sum(b >= self.code.k for b in g) for g in
                    self._groups_of(self.injected)]
        self.repair.update(
            blocks_folded=len(self.injected), fold_sources=sum(sources),
            blocks_to_verify=self.stripes * self.code.k + sum(parities))

    def _groups_of(self, pairs) -> list[tuple[int, ...]]:
        return [next(g for g in self.groups if b in g) for _sid, b in pairs]

    def _inject(self, metas) -> set[tuple[int, int]]:
        """One latent sector error in a data block of each stripe."""
        k, z = self.code.k, len(self.groups)
        sector = min(self.traffic["sector_bytes"], self.B)
        out = set()
        for meta in metas:
            group = self.groups[int(self.rng.integers(z))]
            data = [b for b in group if b < k]
            block = data[int(self.rng.integers(len(data)))]
            offset = sector * int(self.rng.integers(self.B // sector))
            pattern = self.rng.integers(1, 256, sector, dtype=np.uint8)
            self.store.corrupt(meta.stripe_id, block, offset, sector,
                               pattern.tobytes())
            out.add((meta.stripe_id, block))
        return out

    def _restore(self):
        dev, report = super()._restore()
        self.reports.append(set(report.corrupt_blocks))
        return dev, report

    def warmup(self) -> None:
        try:
            super().warmup()
        except Exception:
            failed("warm-up restore")
            self.warmup_failed += 1

    def window(self, run, clock) -> None:
        c0 = self.obs.checksum_snapshot()
        reports0 = len(self.reports)
        super().window(run, clock)
        c1 = self.obs.checksum_snapshot()
        run.counters.update({key: c1[key] - c0[key] for key in CHECKSUMS})
        run.counters["corrupt_reported"] = sum(
            len(r) for r in self.reports[reports0:])

    def _stored_crc_wrong(self) -> int:
        """Stored CRCs of the set-up save that differ from the reference's
        (a block with none counts all its chunks)."""
        k, n, B = self.code.k, self.code.n, self.B
        nbytes = self.cfg["bytes_per_checksum"]
        flat = pl.flat_bytes(self.state0, self.stripes * k * B)
        a_bits = jnp.asarray(reference.bit_matrix(self.A))
        wrong = 0
        for i, meta in enumerate(self.mgr.stripes_of(0)):
            expect = np.asarray(stripe_crcs(
                a_bits, jax.device_put(flat[i * k * B:(i + 1) * k * B]
                                       .reshape(k, B)), nbytes=nbytes))
            stored = self.store.checksums(
                [(meta.stripe_id, b) for b in range(n)])
            for b, crcs in enumerate(stored):
                wrong += (expect.shape[1] if crcs is None else int(
                    np.count_nonzero(np.frombuffer(crcs, np.uint32)
                                     != expect[b])))
        return wrong

    def check(self, run) -> None:
        super().check(run)
        missed = sum(len(self.injected - r) for r in self.reports)
        extra = sum(len(r - self.injected) for r in self.reports)
        c = run.counters
        unverified = -(-max(0, c["blocks_to_verify"] * self.B
                            - c["bytes_verified"]) // self.B)
        t0 = time.perf_counter()
        crc_wrong = self._stored_crc_wrong()
        print(f"bench: stored CRCs checked in {time.perf_counter() - t0:.3f}"
              " s", file=sys.stderr)
        run.checks += [
            Check("warmup_restores_failed", self.warmup_failed, 0),
            Check("blocks_unverified", unverified, 0),
            Check("stored_crc_wrong", crc_wrong, 0),
            Check("corrupt_blocks_missed", missed, 0),
            Check("clean_blocks_reported", extra, 0)]
