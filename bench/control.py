"""Show that a cell's check fails: run the cell with a control backend in the
program's place, for several seeds in one process, on the chip.

    python bench/control.py --workload <cell> --control <name> \
        --seeds 11,12,13 --seconds <s>

`<name>` is one of `bench/harness/controls.py`, or `none` for the program
itself: its readings are the lower ones a limit is set above. Each seed
prints one line with its `correct` and its checks; a sound control reads
`"correct": false`. The benchmark's own runs never use a control.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--control", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench.harness import cell as run_cell
    from bench.harness import controls, spec
    from bench.run import NoChip, check_devices

    cell = spec.cell(args.workload)
    try:
        check_devices(cell.chips)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    run_cell.cache_dir(ROOT)

    def backend():
        if args.control == "none":
            return "kernels"
        return controls.control(args.control)
    for seed in (int(s) for s in args.seeds.split(",")):
        out = run_cell.measure(cell, seed, args.seconds, False,
                               time.perf_counter(),
                               backend=backend(),
                               log=lambda _line: None)
        print(json.dumps({"seed": seed, "control": args.control,
                          "correct": out["correct"],
                          "checks": out["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
