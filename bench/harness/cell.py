"""One run of one cell: set-up, warm-up, the measured window, the check
against the reference, and the metrics the cell reports."""
from __future__ import annotations

import contextlib
import json
import os
import shutil
import sys
import tempfile
import time

import jax

from . import spec
from . import trace as tr
from .record import Run

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


@contextlib.contextmanager
def counting_compiles(counter: list[int]):
    """Counts every backend compile, or fetch from the persistent cache,
    while open."""
    def listener(event, duration, **kwargs):
        if event == COMPILE_EVENT:
            counter[0] += 1
    jax.monitoring.register_event_duration_secs_listener(listener)
    try:
        yield
    finally:
        jax.monitoring.unregister_event_duration_listener(listener)


def tile_plans(cfg: dict) -> dict:
    """The coding kernels' tile plans for this config, with their source,
    so a stray measured-plan cache cannot change tiles unseen."""
    from repro.kernels import autotune
    k, n, B = cfg["k"], cfg["n"], cfg["block_size"]
    r = cfg["alpha"] * cfg["z"]
    enc = autotune.plan_matmul_tiles(k, n - k, B)
    fold = autotune.plan_xor_tiles(r, B)
    return {"gf_bitmatmul_encode": [enc.block_b, enc.source],
            "xor_reduce_fold": [fold.block_b, fold.source]}


def execute(cell: spec.Cell, seed: int, seconds: float, traced: bool,
            started: float, backend="kernels") -> Run:
    """Set-up, warm-up, the window (profiled when `traced`), then the
    device's peak memory, the check, and the trace's reduction."""
    kind = spec.kind(cell.traffic["kind"])(cell.config, cell.traffic, seed,
                                           backend=backend)
    log_dir = tempfile.mkdtemp(prefix="bench-trace-") if traced else None
    try:
        kind.setup()
        kind.warmup()
        run = Run(seconds=seconds, config=cell.config,
                  device_kind=jax.devices()[0].device_kind)
        compiles = [0]
        if traced:
            jax.profiler.start_trace(log_dir, profiler_options=tr.options())
        with counting_compiles(compiles):
            t0 = time.perf_counter()
            run.setup_s = t0 - started
            with tr.span("window"):
                kind.window(run, lambda: time.perf_counter() - t0)
        if traced:
            jax.profiler.stop_trace()
        run.compiles_in_window = compiles[0]
        run.peak_bytes = max(
            (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
            for d in jax.local_devices()[:cell.chips])
        kind.check(run)
        if traced:
            run.trace = tr.reduce(tr.load(log_dir))
    finally:
        kind.close()
        if log_dir:
            shutil.rmtree(log_dir, ignore_errors=True)
    return run


def result(cell: spec.Cell, run: Run, traced: bool, log=print) -> dict:
    """The result line's object: with `traced` the cell's per-layer
    metrics, otherwise its end-to-end ones; a metric whose reader finds
    nothing to read is left out."""
    log(f"tile plans: {json.dumps(tile_plans(cell.config))}")
    log(f"compiles in window: {run.compiles_in_window}")
    log(f"peak_bytes_in_use: {run.peak_bytes}")
    log(f"counters: {json.dumps(run.counters, sort_keys=True)}")
    log(f"operations: {len(run.ops)} in {run.end:.3f} s "
        f"(window {run.seconds} s)")
    metrics = {}
    for m in (cell.per_layer if traced else cell.end_to_end):
        value = m.reader()(run)
        if value is not None:
            metrics[m.name] = {"value": value, "unit": m.unit}
    d0 = jax.devices()[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": run.peak_bytes}
    out = {"correct": bool(run.ops) and all(c.ok for c in run.checks),
           "attempted": len(run.ops),
           "failed": sum(op.done is None for op in run.ops),
           "metrics": metrics, "device": device}
    if traced:
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        top = sorted(run.trace.op_s.items(), key=lambda kv: -kv[1])[:10]
        out["breakdown"] = {"device_ops": [list(kv) for kv in top],
                            "idle_gaps": [list(g) for g in
                                          run.trace.idle_gaps]}
    out["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                     for c in run.checks}
    return out


def measure(cell: spec.Cell, seed: int, seconds: float, traced: bool,
            started: float, backend="kernels", log=print) -> dict:
    """Runs the cell once and returns the result line's object."""
    run = execute(cell, seed, seconds, traced, started, backend=backend)
    return result(cell, run, traced, log=log)


def report(out: dict) -> None:
    """The checks as the last lines of stderr, the result as the last line
    of stdout."""
    err = sys.stderr
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})", file=err)
    err.flush()
    print(json.dumps(out), flush=True)


def cache_dir(root) -> str:
    """JAX's persistent compile cache, on as the program turns it on, at a
    fixed path inside the checkout whatever `JAX_COMPILATION_CACHE_DIR`
    says: a directory set from outside can outlive the checkout and be
    shared by both sides of a comparison, and a run that finds its
    programs there compiles nothing where it should."""
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    path = os.path.join(root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
