"""Kinds of traffic found by name, the raw counts they leave for the
metrics, the roofline readers' floors, and the reach of the save checks."""
from __future__ import annotations

import numpy as np
import pytest

from bench.harness import cell as run_cell
from bench.harness import reference, spec
from bench.harness.record import Run
from bench.harness.trace import Summary
from bench.harness.save_check import SAVE_CHECK_COLUMNS

from .tiny import CELLS, SECONDS, TINY, run_tiny, tiny_cell

RESTORES = [c for c in CELLS
            if spec.cell(c).traffic["kind"] == "restore_loop"]


def _both_kernels_ran(seconds: float) -> Summary:
    return Summary(window_s=seconds, busy_s=seconds, devices=1,
                   module_s={"jit_gf_bitmatmul_batched": 1.0,
                             "jit_xor_reduce_batched": 1.0},
                   module_runs={}, op_s={}, idle_gaps=[])


@pytest.mark.parametrize("cell_name", RESTORES)
def test_the_repair_counted_is_what_the_program_reports(cell_name):
    run = run_cell.execute(tiny_cell(cell_name), 2**31 + 21, SECONDS, False,
                           0.0)
    c = run.counters
    assert c["restores"] > 0
    assert c["blocks_folded"] + c["blocks_decoded"] == c["degraded_blocks"]
    if spec.cell(cell_name).traffic["fail"] == "none":
        assert c["degraded_blocks"] == 0
    else:
        assert c["degraded_blocks"] > 0


def test_a_restore_with_no_failure():
    """`"fail": "none"`: every restore healthy, nothing folded or decoded,
    so the coding kernels' rooflines find nothing to read."""
    cell = tiny_cell("wide-restore-1down", fail="none")
    run = run_cell.execute(cell, 2**31 + 22, SECONDS, False, 0.0)
    assert run.checks and all(ch.ok for ch in run.checks)
    c = run.counters
    assert c["restores"] > 0 and c["degraded_blocks"] == 0
    assert c["blocks_folded"] == c["stripes_decoded"] == 0
    run.device_kind = "TPU v5 lite"
    run.trace = _both_kernels_ran(run.end)
    out = run_cell.result(cell, run, True, log=lambda _line: None)
    assert "xor_reduce_roofline.restore" not in out["metrics"]
    assert out["metrics"]["kernel_launches_per_restore"]["value"] == 0


@pytest.mark.parametrize(("metric", "counters", "blocks"), [
    ("gf_bitmatmul_roofline.save", {"stripes_encoded": 3}, 3 * 210),
    ("gf_bitmatmul_roofline.restore",
     {"stripes_decoded": 2, "blocks_decoded": 36}, 2 * 180 + 36),
    ("xor_reduce_roofline.restore",
     {"blocks_folded": 7, "fold_sources": 140}, 7 + 140),
])
def test_a_roofline_reader_counts_its_own_floor(metric, counters, blocks):
    run = Run(seconds=1.0, config=spec.config("unilrc-180of210"),
              counters=counters, device_kind="TPU v5 lite")
    run.trace = _both_kernels_ran(1.0)
    value = spec.Metric(metric, "%", "device_trace").reader()(run)
    assert value == pytest.approx(100 * blocks * (1 << 20) / 819e9)


def _sampled_columns(seed: int, B: int) -> set[int]:
    """The save check's sampled columns: a kind's first draw from the
    seed."""
    rng = np.random.default_rng(seed)
    return {0, B - 1, *rng.integers(0, B, SAVE_CHECK_COLUMNS).tolist()}


def _land_wrong(monkeypatch, block: int, col: int) -> None:
    """Every save lands byte `col` of block `block` of each stripe wrong."""
    from repro.ckpt.store import BlockStore
    real = BlockStore.put_many

    def put_many(self, entries):
        out = []
        for stripe, b, node, data in entries:
            if b == block:
                data = bytearray(bytes(data))
                data[col] ^= 1
            out.append((stripe, b, node, bytes(data)))
        return real(self, out)
    monkeypatch.setattr(BlockStore, "put_many", put_many)


@pytest.mark.parametrize("block", [3, 200], ids=["data", "parity"])
def test_a_byte_the_sample_skips_fails_the_save(block, monkeypatch):
    """One byte of one block of every stripe landed wrong, at a column the
    sampled check skips: the check of every byte still finds it."""
    seed, B = 2**31 + 31, TINY["block_size"]
    free = sorted(set(range(B)) - _sampled_columns(seed, B))
    _land_wrong(monkeypatch, block, free[len(free) // 2])
    out = run_tiny("wide-save", seed=seed)
    checks = out["checks"]
    assert checks["stored_bytes_wrong"]["value"] == 0
    assert checks["stored_bytes_wrong_in_full"]["value"] > 0
    assert out["correct"] is False


@pytest.mark.parametrize("cell_name", ["wide-restore-1down",
                                       "wide-restore-healthy"])
def test_a_parity_no_repair_reads_fails_the_setup_save(cell_name,
                                                       monkeypatch):
    """One byte of a global parity of the last local group landed wrong in
    the set-up save of every stripe, at a sampled column. The node down in
    1down holds a block of the first group, so no repair reads that
    parity and every restore comes back right: only the set-up save's
    check sees the byte."""
    seed, B = 2**31 + 33, TINY["block_size"]
    cfg = spec.cell(cell_name).config
    _A, groups = reference.unilrc(cfg["alpha"], cfg["z"])
    parity = next(b for b in groups[-1] if b >= cfg["k"])
    cols = sorted(_sampled_columns(seed, B) - {0, B - 1})
    _land_wrong(monkeypatch, parity, cols[len(cols) // 2])
    out = run_tiny(cell_name, seed=seed)
    checks = out["checks"]
    assert checks["restored_words_wrong"]["value"] == 0
    assert checks["stored_bytes_wrong"]["value"] > 0
    assert out["correct"] is False
