"""From a profiler trace to device numbers: busy time, idle gaps, and the
time each jitted wrapper's XLA module ran on the device.

The trace is JAX's `.xplane.pb`, read with `jax.profiler.ProfileData`.
Device planes are named `/device:TPU:<i>`; on each, the line `XLA Ops`
holds one event per operation that ran and `XLA Modules` one event per
run of a compiled module, named after the jitted function
(`jit_gf_bitmatmul_batched(...)`). The window is the host span
`bench.window` that the benchmark opens around its measured operations.

  busy      the union of a device's op intervals inside the window
  idle %    100 * (1 - busy / window), averaged over the devices
  module s  the summed device time of a module's runs inside the window
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

WINDOW = "bench.window"
DEVICE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                       # averaged over devices
    devices: int
    module_s: dict[str, float]          # module name -> device seconds
    module_runs: dict[str, int]
    op_s: dict[str, float]              # op name -> device seconds
    idle_gaps: list[tuple[str, float]]  # (what the host was doing, s)

    @property
    def idle_pct(self) -> float:
        return 100.0 * (1.0 - self.busy_s / self.window_s)

    def kernel_s(self, module: str) -> float | None:
        """Device seconds of the module whose name starts `jit_<module>`;
        None when it never ran."""
        s = sum(v for k, v in self.module_s.items()
                if module_name(k) == f"jit_{module}")
        return s if s > 0 else None


def span(what: str):
    """A host span `bench.<what>` in the profiler's trace; nearly free when
    no trace is being taken."""
    import jax
    return jax.profiler.TraceAnnotation(f"bench.{what}")


def module_name(event_name: str) -> str:
    """`jit_xor_reduce_batched(123)` -> `jit_xor_reduce_batched`."""
    return event_name.split("(", 1)[0]


def options():
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0        # the benchmark's spans are TraceMe
    return opts


def load(log_dir: str):
    """The newest profile under `log_dir`, as `jax.profiler.ProfileData`."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return ProfileData.from_file(max(files, key=os.path.getmtime))


def _union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _clip(s: int, e: int, lo: int, hi: int) -> tuple[int, int] | None:
    s, e = max(s, lo), min(e, hi)
    return (s, e) if e > s else None


def reduce(data, gaps: int = 10) -> Summary:
    """The window's device numbers from a `ProfileData`."""
    host_spans: list[tuple[int, int, str]] = []
    devices = []
    for plane in data.planes:
        if DEVICE.match(plane.name):
            devices.append(plane)
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("bench."):
                    host_spans.append((int(ev.start_ns), int(ev.end_ns),
                                       ev.name))
    windows = [(s, e) for s, e, n in host_spans if n == WINDOW]
    if not windows:
        raise ValueError(f"no {WINDOW} span in the trace")
    lo, hi = windows[0]
    if not devices:
        raise ValueError("no TPU device plane in the trace")

    busy = 0
    module_s: dict[str, float] = {}
    module_runs: dict[str, int] = {}
    op_s: dict[str, float] = {}
    idle: list[tuple[int, int]] = []
    for plane in devices:
        ops = []
        for line in plane.lines:
            if line.name == OPS_LINE:
                for ev in line.events:
                    iv = _clip(int(ev.start_ns), int(ev.end_ns), lo, hi)
                    if iv:
                        ops.append(iv)
                        op_s[ev.name] = op_s.get(ev.name, 0.0) + (
                            iv[1] - iv[0]) / 1e9
            elif line.name == MODULES_LINE:
                for ev in line.events:
                    iv = _clip(int(ev.start_ns), int(ev.end_ns), lo, hi)
                    if iv:
                        name = module_name(ev.name)
                        module_s[name] = module_s.get(name, 0.0) + (
                            iv[1] - iv[0]) / 1e9
                        module_runs[name] = module_runs.get(name, 0) + 1
        merged = _union(ops)
        busy += sum(e - s for s, e in merged)
        edges = [lo] + [t for iv in merged for t in iv] + [hi]
        idle += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]

    def doing(s: int, e: int) -> str:
        """The innermost benchmark span covering the gap's middle."""
        mid = (s + e) // 2
        inner = [(se - ss, n) for ss, se, n in host_spans
                 if ss <= mid <= se and n != WINDOW]
        return min(inner)[1] if inner else "bench.window"

    longest = sorted(idle, key=lambda iv: iv[0] - iv[1])[:gaps]
    return Summary(
        window_s=(hi - lo) / 1e9, busy_s=busy / 1e9 / len(devices),
        devices=len(devices), module_s=module_s, module_runs=module_runs,
        op_s=op_s,
        idle_gaps=[(doing(s, e), (e - s) / 1e9) for s, e in longest])
