"""Run one cell of the benchmark once, on the machine's TPU.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is looked up in `BENCHMARK.json` at the checkout's root. Set-up
makes the cell's data from the seed and warms up every shape through the
cell's own operation; then the window measures for `--seconds`; then
what the window produced is compared with the plain reference. The last
line of stdout is the result object (`correct`, `attempted`, `failed`,
`metrics`, `device`, with `--trace 1` also `breakdown`, and `checks`:
each number compared beside its limit, which are also the last lines of
stderr). With `--trace 0` the metrics are the cell's end-to-end ones,
with `--trace 1` its per-layer ones, from a profile of the window.

It exits non-zero, printing no result, when JAX's devices are not TPUs or
are fewer than the cell asks for. JAX's compile cache is kept in
`<checkout>/.jax_cache`.
"""
from __future__ import annotations

import time

STARTED = time.perf_counter()

import argparse  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def check_devices(chips: int) -> None:
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX's first device is on platform "
                     f"{devices[0].platform!r}")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips; JAX has "
                     f"{len(devices)}")


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    for p in (str(ROOT), str(ROOT / "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    from bench.harness import cell as run_cell
    from bench.harness import spec

    cell = spec.cell(args.workload)
    try:
        check_devices(cell.chips)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    print(f"compile cache: {run_cell.cache_dir(ROOT)}", flush=True)
    out = run_cell.measure(cell, args.seed, args.seconds, bool(args.trace),
                           STARTED)
    run_cell.report(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
