"""`BENCHMARK.json` and the files it names, found by name: a config in
`bench/configs/<name>.json`, a traffic mix in `bench/traffic/<name>.json`,
the kind of traffic it names in `bench/kinds/<kind>.py`, and a metric's
reader in `bench/metrics/<name>.py`."""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
import pathlib

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


@functools.cache
def _module(path: pathlib.Path, name: str):
    """The Python file at `path`, loaded once per process."""
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@dataclasses.dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    source: str
    moves: str | None = None

    def reader(self):
        """The metric's `read(run) -> float | None`, from
        `bench/metrics/<name>.py`."""
        return _module(BENCH / "metrics" / f"{self.name}.py",
                       f"bench_metric_{self.name}").read


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: tuple[Metric, ...]
    per_layer: tuple[Metric, ...]


def load_json(path: pathlib.Path) -> dict:
    return json.loads(path.read_text())


def config(name: str) -> dict:
    return load_json(BENCH / "configs" / f"{name}.json")


def traffic(name: str) -> dict:
    return load_json(BENCH / "traffic" / f"{name}.json")


def kind(name: str) -> type:
    """The class that runs traffic of kind `name`."""
    return _module(BENCH / "kinds" / f"{name}.py", f"bench_kind_{name}").Kind


def cell(name: str, benchmark: pathlib.Path = ROOT / "BENCHMARK.json") -> Cell:
    """The cell `name` with its config, traffic mix and metrics. An
    end-to-end metric without a `workloads` list belongs to every cell; a
    per-layer metric without one to every cell that reports what it
    moves."""
    doc = load_json(benchmark)
    entry = next((w for w in doc["workloads"] if w["name"] == name), None)
    if entry is None:
        raise KeyError(f"no workload {name!r} in {benchmark}")

    def has(m: dict) -> bool:
        return name in m.get("workloads", [name])

    e2e = tuple(Metric(m["name"], m["unit"], m["source"])
                for m in doc["end_to_end"] if has(m))
    reported = {m.name for m in e2e}
    layer = tuple(Metric(m["name"], m["unit"], m["source"], m["moves"])
                  for m in doc["per_layer"]
                  if (name in m["workloads"] if "workloads" in m
                      else m["moves"] in reported))
    return Cell(name, entry["chips"], config(entry["config"]),
                traffic(entry["traffic"]), e2e, layer)
