"""`wide-restore-corrupt`: the counts its kind leaves beside what the
program reports, its checks failing when verification is skipped or the
parities are rounded, the two readers it adds, and the reference CRC."""
from __future__ import annotations

import numpy as np
import pytest

from bench.harness import cell as run_cell
from bench.harness import controls, crc32c_reference, spec
from bench.harness.record import Op, Run
from bench.harness.trace import Summary

from .tiny import SECONDS, TINY, run_tiny, tiny_cell

CELL = "wide-restore-corrupt"


def test_the_kind_counts_what_the_program_reports():
    """Every restore finds the one corrupt block of each stripe, folds it
    from its group's 20 others and checks each block it reads once: the
    data blocks and the fold's three parities."""
    cfg = spec.cell(CELL).config
    run = run_cell.execute(tiny_cell(CELL), 2**31 + 41, SECONDS, False, 0.0)
    assert run.checks and all(ch.ok for ch in run.checks)
    c = run.counters
    restores, B = c["restores"], TINY["block_size"]
    stripes = c["blocks_folded"] // restores
    assert restores > 0 and stripes > 0 and c["degraded_blocks"] == 0
    assert c["corrupt_reported"] == c["corrupt_blocks"] == c["blocks_folded"]
    assert c["fold_sources"] == 20 * c["blocks_folded"]
    assert c["blocks_to_verify"] == restores * stripes * (cfg["k"] + 3)
    assert c["bytes_verified"] == c["blocks_to_verify"] * B
    assert c["store_bytes"] == c["bytes_verified"]
    # a 4 KiB sector cut to the 1 KiB block: both its chunks fail
    assert c["corrupt_chunks"] == c["blocks_folded"] * B // 512
    assert c["launches"] == 3 * restores * stripes


def test_skipping_verification_makes_the_run_incorrect(monkeypatch):
    """A program that trusted every block would hand back the corrupt
    bytes: the leaves come back wrong and no corruption is reported."""
    from repro.io.backend import KernelBackend
    monkeypatch.setattr(
        KernelBackend, "verify_many",
        lambda self, blocks, expect, nbytes: np.zeros(len(blocks), np.int32))
    out = run_tiny(CELL)
    checks = out["checks"]
    assert out["correct"] is False
    assert checks["restored_words_wrong"]["value"] > 0
    assert checks["corrupt_blocks_missed"]["value"] > 0


def test_unverified_parity_sources_make_the_run_incorrect(monkeypatch):
    """A program that checked the data blocks but trusted the fold's
    parity sources would restore the right bytes here, since only data
    blocks are corrupted; `blocks_unverified` holds it to the guarantee
    that every block read is checked."""
    from repro.io import engine
    verify, k = engine._Verified._verify, spec.cell(CELL).config["k"]

    def data_only(self, pairs, got):
        data = [p for p in pairs if p[1] < k]
        failing = dict(zip(data, verify(self, data, got) if data else []))
        return [failing.get(p, 0) for p in pairs]

    monkeypatch.setattr(engine._Verified, "_verify", data_only)
    out = run_tiny(CELL)
    checks = out["checks"]
    assert out["correct"] is False
    assert checks["blocks_unverified"]["value"] > 0
    assert checks["restored_words_wrong"]["value"] == 0


def crc_bf16():
    """A control: the kernel backend with its CRCs computed by the
    reference with every sum rounded to bfloat16, the precision below
    the float32 the CRC's sums of up to 4,096 bits need, the same way
    for the CRCs stored and for those checked on a read."""
    import jax
    from repro.io.backend import KernelBackend
    crcs = jax.jit(crc32c_reference.chunk_crcs, static_argnums=(1, 2))

    class CrcBf16(KernelBackend):
        name = "crc_bf16"

        def checksum_many(self, blocks, nbytes):
            *lead, B = blocks.shape
            return crcs(jax.numpy.asarray(blocks).reshape(-1, B), nbytes,
                        7).reshape(*lead, B // nbytes)

        def verify_many(self, blocks, expect, nbytes):
            got = np.asarray(self.checksum_many(blocks, nbytes))
            return np.sum(got != expect, axis=-1, dtype=np.int32)

    return CrcBf16()


def test_crcs_rounded_to_bfloat16_make_the_run_incorrect():
    """Rounded sums lose most bits of the CRC: the stored CRCs are wrong,
    and even though they are checked by the same rounded CRC the
    corruption gets through to the leaves. The parities are right."""
    out = run_tiny(CELL, backend=crc_bf16())
    checks = out["checks"]
    assert out["correct"] is False
    assert checks["stored_crc_wrong"]["value"] > 0
    assert checks["corrupt_blocks_missed"]["value"] > 0
    assert checks["restored_words_wrong"]["value"] > 0
    assert checks["stored_bytes_wrong"]["value"] == 0


def test_rounded_parities_fail_the_stored_crcs():
    out = run_tiny(CELL, backend=controls.control("bf16_accumulate"))
    checks = out["checks"]
    assert out["correct"] is False
    assert checks["stored_bytes_wrong"]["value"] > 0
    assert checks["stored_crc_wrong"]["value"] > 0


def test_a_failed_warmup_is_recorded(monkeypatch):
    """Every block read fails its CRCs: each restore is beyond the code,
    the warm-up's too, and the run says so instead of crashing."""
    from repro.io.backend import KernelBackend
    monkeypatch.setattr(
        KernelBackend, "verify_many",
        lambda self, blocks, expect, nbytes: np.ones(len(blocks), np.int32))
    out = run_tiny(CELL)
    assert out["correct"] is False and out["failed"] > 0
    assert out["checks"]["warmup_restores_failed"]["value"] == 1


def test_the_reference_crc_is_crc32c():
    import jax
    assert crc32c_reference.crc32c(b"123456789") == 0xE3069283
    rng = np.random.default_rng(2**31 + 43)
    blocks = rng.integers(0, 256, (3, 1536), dtype=np.uint8)
    blocks[1] = 0xFF
    got = np.asarray(jax.jit(crc32c_reference.chunk_crcs,
                             static_argnums=1)(blocks, 512))
    assert got.tolist() == [[crc32c_reference.crc32c(bytes(b[j:j + 512]))
                             for j in range(0, 1536, 512)] for b in blocks]


@pytest.mark.parametrize(("metric", "counters", "expect"), [
    ("gf_bitmatmul_roofline.verify", {"blocks_to_verify": 2379},
     100 * 2379 * (1 << 20) * (1 + 1 / 128) / 819e9),
    ("verified_bytes_per_byte.restore", {"bytes_verified": 2379 << 20},
     2379 * (1 << 20) / 2_416_115_716),
])
def test_the_new_readers(metric, counters, expect):
    run = Run(seconds=1.0, config=spec.cell(CELL).config, counters=counters,
              device_kind="TPU v5 lite",
              ops=[Op("restore", 0.5, 2_416_115_716)])
    run.trace = Summary(window_s=1.0, busy_s=1.0, devices=1,
                        module_s={"jit_gf_bitmatmul_batched": 1.0},
                        module_runs={}, op_s={}, idle_gaps=[])
    assert spec.Metric(metric, "", "").reader()(run) == pytest.approx(expect)
