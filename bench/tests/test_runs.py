"""Each cell, cut to a tiny size, runs one short window on the CPU and
prints a result line that meets the benchmark's contract; `run.py` refuses
to run without a TPU; and `BENCHMARK.json` names only what exists."""
from __future__ import annotations

import json

import pytest

from bench import run as run_py
from bench.harness import cell as run_cell
from bench.harness import spec
from bench.harness.record import Run
from bench.harness.trace import Summary

from .tiny import CELLS, SECONDS, tiny_cell

DOCS = (spec.ROOT / "BENCHMARK.json",)


def test_a_run_prints_a_contract_line(cell_name, capsys):
    cell = tiny_cell(cell_name)
    out = run_cell.measure(cell, 2**31 + 11, SECONDS, False, 0.0,
                           log=lambda _line: None)
    run_cell.report(out)
    stdout, stderr = capsys.readouterr()
    line = json.loads(stdout.splitlines()[-1])
    assert line["correct"] is True
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {m.name for m in cell.end_to_end}
    units = {m.name: m.unit for m in cell.end_to_end}
    for name, metric in line["metrics"].items():
        assert metric["value"] > 0 and metric["unit"] == units[name]
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(
        line["device"])
    # every number compared beside its limit: last in the line, and the
    # last lines of stderr
    assert list(line)[-1] == "checks" and line["checks"]
    tail = stderr.strip().splitlines()[-len(line["checks"]):]
    for text, (name, check) in zip(tail, line["checks"].items()):
        assert text == (f"check {name}: {check['value']} "
                        f"(limit {check['limit']})")
        assert check["value"] <= check["limit"]


def test_every_per_layer_metric_reads_from_a_traced_window(cell_name):
    """With a device trace in hand (here a made-up one: both coding
    kernels ran for a second), every per-layer metric of the cell finds
    something to read, as a `--trace 1` line must."""
    cell = tiny_cell(cell_name)
    run = run_cell.execute(cell, 2**31 + 12, SECONDS, False, 0.0)
    run.device_kind = "TPU v5 lite"
    run.trace = Summary(
        window_s=run.end, busy_s=run.end / 2, devices=1,
        module_s={"jit_gf_bitmatmul_batched": 1.0,
                  "jit_xor_reduce_batched": 1.0},
        module_runs={}, op_s={"fusion": run.end / 2},
        idle_gaps=[("bench.window", run.end / 2)])
    out = run_cell.result(cell, run, True, log=lambda _line: None)
    assert set(out["metrics"]) == {m.name for m in cell.per_layer}
    assert out["device"]["busy_s"] > 0 and "breakdown" in out


@pytest.mark.parametrize("doc", DOCS, ids=lambda p: p.name)
def test_readers_find_nothing_in_an_empty_run(doc):
    """A reader with nothing to read returns None, never 0."""
    benchmark = json.loads(doc.read_text())
    for m in benchmark["end_to_end"] + benchmark["per_layer"]:
        if m["name"] == "setup_s":
            continue
        reader = spec.Metric(m["name"], m["unit"], m["source"]).reader()
        assert reader(Run(seconds=1.0)) is None, m["name"]


def test_run_refuses_without_a_tpu(capsys):
    rc = run_py.main(["--workload", CELLS[0], "--seed", str(2**31 + 1),
                      "--seconds", "1", "--trace", "0"])
    out, err = capsys.readouterr()
    assert rc != 0
    assert "no TPU" in err
    for line in out.splitlines():
        with pytest.raises(ValueError):
            json.loads(line)


@pytest.mark.parametrize("doc", DOCS, ids=lambda p: p.name)
def test_benchmark_names_only_what_exists(doc):
    benchmark = json.loads(doc.read_text())
    e2e = {m["name"]: m for m in benchmark["end_to_end"]}
    configs = {c["name"] for c in benchmark["configs"]}
    for c in benchmark["configs"]:
        assert (spec.ROOT / c["file"]).is_file()
        cfg = json.loads((spec.ROOT / c["file"]).read_text())
        assert set(c["reduced"]) <= set(cfg.get("published", {}))
    for w in benchmark["workloads"]:
        assert w["config"] in configs
        assert callable(spec.kind(spec.traffic(w["traffic"])["kind"]))
        cell = spec.cell(w["name"], doc)
        reported = {m.name for m in cell.end_to_end}
        assert "setup_s" in reported and len(reported) >= 2
        assert cell.per_layer
        for m in cell.per_layer:
            assert m.moves in reported
    for m in benchmark["per_layer"]:
        assert m["moves"] in e2e
        assert callable(spec.Metric(m["name"], m["unit"],
                                    m["source"]).reader())
    for m in e2e.values():
        assert callable(spec.Metric(m["name"], m["unit"],
                                    m["source"]).reader())
