"""Record a small trace with the program's own spans, on the chip, for
`test_spans.py` to read:

    python bench/tests/record_spans.py <out.xplane.pb>

One profiled window (`bench.window`) on the TPU, through
`CheckpointManager` with the 30-of-42 code at 64 KiB blocks on 6 x 8
nodes, each shape warmed up first: a save of 2 stripes inside
`bench.save`, then, with the node holding block 0 of the save's first
stripe down, a degraded restore inside `bench.restore`. Prints the span
totals, the device's idle gaps named by them, and bounds on the offset
between the trace's device and host clocks.
"""
from __future__ import annotations

import glob
import pathlib
import shutil
import sys
import tempfile
import time

ROOT = pathlib.Path(__file__).resolve().parents[2]
BLOCK = 1 << 16


def manager():
    """The window's save and degraded restore through the manager, as two
    calls, after one of each as warm-up."""
    import jax
    import jax.numpy as jnp

    from repro.ckpt import BlockStore, CheckpointManager
    from repro.core.codes import make_unilrc
    from repro.topo import Topology

    store = BlockStore(Topology(6, 8))
    mgr = CheckpointManager(store, make_unilrc(1, 6), block_size=BLOCK)
    state = {"w": jnp.arange(600_000, dtype=jnp.float32) * 0.5,
             "b": jnp.ones((300, 1000), jnp.bfloat16)}
    jax.block_until_ready(state)

    def save(step):
        mgr.save(state, step=step)
        down = store.node_of(mgr.stripes_of(step)[0].stripe_id, 0)
        store.fail_node(down)
        return down

    def restore(step, down):
        mgr.restore(step)
        store.heal_node(down)

    restore(0, save(0))
    down = []
    return (lambda: down.append(save(1)), lambda: restore(1, down[0]))


def clock_offset(data) -> tuple[float, float] | None:
    """Bounds, in ms, on the device clock's reading minus the host's, from
    the runs of compiled modules: each started on the device after its
    host `PJRT_LoadedExecutable_Execute` began, and ended before the
    `bench.save` or `bench.restore` span around that call closed. None
    when the trace's executes and module runs do not pair one to one."""
    from bench.harness import spans
    from bench.harness import trace as tr
    runs, executes = [], []
    for plane in data.planes:
        device = tr.DEVICE.match(plane.name)
        for line in plane.lines:
            for ev in line.events:
                if device and line.name == tr.MODULES_LINE:
                    runs.append((int(ev.start_ns), int(ev.end_ns)))
                elif ev.name == "PJRT_LoadedExecutable_Execute":
                    executes.append(int(ev.start_ns))
    calls = [(s, e) for s, e, n, _t in spans.host_spans(data)
             if n in ("bench.save", "bench.restore")]
    if not runs or len(runs) != len(executes):
        return None
    pairs = list(zip(sorted(runs), sorted(executes)))
    high = min(start - issued for (start, _end), issued in pairs)
    low = max(end - min(e for s, e in calls if s <= issued <= e)
              for (_start, end), issued in pairs)
    return low / 1e6, high / 1e6


def main(argv=None) -> int:
    out = pathlib.Path((argv or sys.argv[1:])[0])
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from jax import profiler

    from bench.harness import spans
    from bench.harness import trace as tr

    save, restore = manager()
    log_dir = tempfile.mkdtemp(prefix="bench-record-")
    try:
        profiler.start_trace(log_dir, profiler_options=tr.options())
        with tr.span("window"):
            time.sleep(0.02)
            with tr.span("save"):
                save()
            time.sleep(0.02)
            with tr.span("restore"):
                restore()
            time.sleep(0.02)
        profiler.stop_trace()
        path, = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
        out.parent.mkdir(parents=True, exist_ok=True)
        shutil.copy(path, out)
        data = tr.load(log_dir)
        for name, total in sorted(spans.reduce(data).items()):
            print(name, total)
        print(spans.gaps(data))
        print("device clock minus host clock, ms (low, high):",
              clock_offset(data))
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
