"""Record the small trace that `test_trace.py` reads, on the chip:

    python bench/tests/record_trace.py <out.xplane.pb>

One profiled window (`bench.window`) on the TPU: an encode of 2 stripes of
the 30-of-42 code at 64 KiB blocks inside `bench.save`, then an XOR fold
of 3 stripes x 6 sources inside `bench.restore`, each shape warmed up
first. Prints the reduction's summary of what it recorded.
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


def main(argv=None) -> int:
    out = pathlib.Path((argv or sys.argv[1:])[0])
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax.numpy as jnp
    import numpy as np
    from jax import profiler

    from bench.harness import trace as tr
    from repro.core.codes import make_unilrc
    from repro.kernels import ops

    code = make_unilrc(1, 6)
    rng = np.random.default_rng(0)
    data = jnp.asarray(rng.integers(0, 256, (2, code.k, BLOCK), np.uint8))
    src = jnp.asarray(rng.integers(0, 256, (3, 6, BLOCK), np.uint8))
    for _ in range(2):
        ops.encode_many(code, data).block_until_ready()
        ops.xor_fold_many(src).block_until_ready()
    log_dir = tempfile.mkdtemp(prefix="bench-record-")
    try:
        profiler.start_trace(log_dir, profiler_options=tr.options())
        # 20 ms of host time around each call: the trace's device clock
        # sat about 2.5 ms before the host's on a v5e
        with tr.span("window"):
            time.sleep(0.02)
            with tr.span("save"):
                ops.encode_many(code, data).block_until_ready()
            time.sleep(0.02)
            with tr.span("restore"):
                ops.xor_fold_many(src).block_until_ready()
            time.sleep(0.02)
        profiler.stop_trace()
        path, = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
        out.parent.mkdir(parents=True, exist_ok=True)
        shutil.copy(path, out)
        print(tr.reduce(tr.load(log_dir)))
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
