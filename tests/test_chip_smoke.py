"""chip_smoke.py off the chip: its phases at a tiny size in interpret mode,
and its refusal to report a result when JAX finds no TPU."""
import dataclasses
import importlib.util
import json
import pathlib
import sys

import pytest


SCRIPT = pathlib.Path(__file__).resolve().parents[1] / "chip_smoke.py"


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    module = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = module
    try:
        spec.loader.exec_module(module)
        yield module
    finally:
        del sys.modules["chip_smoke"]


def _tiny(dep):
    return dataclasses.replace(dep, block_size=1024, smoke_widths=True,
                               layers=min(dep.layers, 2), min_payload=0,
                               min_windows=1)


def test_phases_pass_at_tiny_size(chip_smoke):
    lines = []
    out = chip_smoke.run(_tiny(chip_smoke.WIDE), _tiny(chip_smoke.NARROW),
                         seed=3, log=lines.append)
    assert out["launches"]["gf_bitmatmul"] > 0
    assert out["launches"]["xor_reduce"] > 0
    phases = out["seconds"]
    for what in ("save", "healthy restore", "one-failure degraded restore",
                 "two-failure restore", "reconstruct_failures",
                 "post-rebuild restore", "serve", "numpy oracle, one stripe"):
        assert f"UniLRC(210,180,20) {what}" in phases
    assert "UniLRC(42,30,6) one-failure degraded restore" in phases
    assert any(line.startswith("KERNEL_LAUNCHES") for line in lines)


def test_a_corrupt_block_fails_the_run(chip_smoke, monkeypatch):
    """A wrong byte anywhere on the read path must fail the smoke, not
    pass silently: corrupt every block the store hands out."""
    from repro.ckpt.store import BlockStore
    real = BlockStore._payload

    def flipped(self, key, node):
        data = bytearray(real(self, key, node))
        data[0] ^= 1
        return bytes(data)
    monkeypatch.setattr(BlockStore, "_payload", flipped)
    with pytest.raises(chip_smoke.SmokeFailure, match="restore"):
        chip_smoke.run(_tiny(chip_smoke.WIDE), _tiny(chip_smoke.NARROW),
                       seed=0, log=lambda _line: None)


def test_main_refuses_without_a_tpu(chip_smoke, capsys):
    assert chip_smoke.main([]) != 0
    out, err = capsys.readouterr()
    assert "no TPU found" in err and "'cpu'" in err
    for line in out.splitlines():
        with pytest.raises(ValueError):
            json.loads(line)

