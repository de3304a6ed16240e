"""Cells of `BENCHMARK.json` cut to a size the CPU runs in a second or two:
1 KiB blocks and a one-layer payload of small widths, with the kernels in
Pallas interpret mode. The code, the topology, the failures and the mix
stay as the cell has them."""
from __future__ import annotations

import dataclasses
import json
import time

from bench.harness import cell as run_cell
from bench.harness import spec

CELLS = tuple(w["name"] for w in json.loads(
    (spec.ROOT / "BENCHMARK.json").read_text())["workloads"])

TINY = {"block_size": 1024, "hidden_size": 64, "intermediate_size": 128,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "num_hidden_layers": 1}
SECONDS = 0.25


def tiny_cell(name: str, **traffic) -> spec.Cell:
    """The cell at the tiny size, its traffic mix changed by `traffic`."""
    c = spec.cell(name)
    cfg = {k: TINY.get(k, v) for k, v in c.config.items()}
    return dataclasses.replace(c, config=cfg, traffic={**c.traffic,
                                                       **traffic})


def run_tiny(name: str, seed: int = 2**31 + 7, backend="kernels",
             **traffic) -> dict:
    """One whole run of the cell, as `bench/run.py` makes it, past the
    look for a chip."""
    return run_cell.measure(tiny_cell(name, **traffic), seed, SECONDS,
                            False, time.perf_counter(), backend=backend,
                            log=lambda _line: None)
