"""Run one cell of the benchmark once, profiled, and read the program's own
spans and host<->device byte counters in its window.

    python bench/profile_cell.py --workload <cell> --seed <n> --seconds <s>

The run is `bench/run.py --trace 1`'s: the cell's set-up and warm-up, a
profiled window of `--seconds`, then the check against the reference. It
prints the program's view of that window, as one JSON object on the last
line of stdout:

  correct, rate_GiBps     the check, and the rate of the window's
                          operations (`save_GiBps` or `restore_GiBps`)
  metrics                 each span of SPAN_METRICS as a percent of the
                          window, and `host_link_bytes_per_byte.<kind>`:
                          the bytes `repro.obs` counted both ways between
                          host and device in the window per payload byte
  spans                   every `repro.` span: [inclusive s, self s, count]
  idle                    the device's idle share and seconds (on a TPU
                          only), the share of them inside at
                          least one `repro.` span, and the ten longest
                          gaps named by the innermost span open in each
  counters                the window's transfer bytes

It exits non-zero, printing no result, when JAX's devices are not TPUs.
"""
from __future__ import annotations

import time

STARTED = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]

# per-layer readings of program spans: (metric, the operation, the span)
SPAN_METRICS = (
    ("serialize_pct.save", "save", "repro.ckpt.serialize"),
    ("encode_wait_pct.save", "save", "repro.engine.encode_wait"),
    ("land_pct.save", "save", "repro.store.put_many"),
    ("store_read_pct.restore", "restore", "repro.store.get_many"),
    ("recover_pct.restore", "restore", "repro.engine.recover"),
    ("assemble_pct.restore", "restore", "repro.ckpt.assemble"),
)


def execute(cell, seed: int, seconds: float, started: float,
            backend="kernels"):
    """Set-up, warm-up, the profiled window with the transfer counters
    read around it, then the check. Returns the run, the trace as
    `ProfileData` and the window's transfer bytes."""
    import jax

    from bench.harness import spec
    from bench.harness import trace as tr
    from bench.harness.record import Run
    from repro import obs

    kind = spec.kind(cell.traffic["kind"])(cell.config, cell.traffic, seed,
                                           backend=backend)
    log_dir = tempfile.mkdtemp(prefix="bench-spans-")
    try:
        kind.setup()
        kind.warmup()
        run = Run(seconds=seconds, config=cell.config,
                  device_kind=jax.devices()[0].device_kind)
        jax.profiler.start_trace(log_dir, profiler_options=tr.options())
        x0 = obs.snapshot()
        t0 = time.perf_counter()
        run.setup_s = t0 - started
        with tr.span("window"):
            kind.window(run, lambda: time.perf_counter() - t0)
        x1 = obs.snapshot()
        jax.profiler.stop_trace()
        kind.check(run)
        data = tr.load(log_dir)
    finally:
        kind.close()
        shutil.rmtree(log_dir, ignore_errors=True)
    return run, data, {f"{d}_bytes": x1[d] - x0[d] for d in x0}


def summary(run, data, counters: dict[str, int]) -> dict:
    """The result object from what `execute` returns."""
    from bench.harness import spans
    from bench.harness import trace as tr
    from bench.harness.record import rate_GiBps

    what = run.ops[0].kind if run.ops else None
    ops = run.of(what) if what else []
    totals = spans.reduce(data)
    lo, hi = spans.window(spans.host_spans(data))
    window_s = (hi - lo) / 1e9
    metrics = {name: 100.0 * totals[span].inclusive_s / window_s
               for name, op, span in SPAN_METRICS
               if op == what and span in totals}
    if ops and all(op.done is not None for op in ops):
        metrics[f"host_link_bytes_per_byte.{what}"] = sum(
            counters.values()) / sum(op.nbytes for op in ops)
    out = {"correct": bool(ops) and all(c.ok for c in run.checks),
           "operation": what, "rate_GiBps": rate_GiBps(ops),
           "metrics": metrics,
           "spans": {name: [t.inclusive_s, t.self_s, t.count]
                     for name, t in sorted(totals.items())},
           "counters": counters, "window_s": window_s}
    g = spans.gaps(data)
    if g is not None:
        out["idle"] = {"device_idle_pct": tr.reduce(data).idle_pct,
                       "idle_s": g.idle_s, "in_program_s": g.in_program_s,
                       "in_program_pct": g.in_program_pct,
                       "longest": [list(x) for x in g.longest]}
    out["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                     for c in run.checks}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    for p in (str(ROOT), str(ROOT / "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    from bench import run as run_py
    from bench.harness import cell as run_cell
    from bench.harness import spec

    cell = spec.cell(args.workload)
    try:
        run_py.check_devices(cell.chips)
    except run_py.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    print(f"compile cache: {run_cell.cache_dir(ROOT)}", flush=True)
    out = summary(*execute(cell, args.seed, args.seconds, STARTED))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
