"""The reduction from a profiler trace to device numbers: on a trace made
here with known intervals, and on one recorded on the chip."""
from __future__ import annotations

import pathlib

import numpy as np
import pytest
from jax.profiler import ProfileData

from bench.harness import trace
from bench.harness.record import Run, idle_pct, roofline_pct

US = 1_000_000      # picoseconds per microsecond


def _events(spans):
    return "\n".join(f"events {{ metadata_id: {m} offset_ps: {s * US} "
                     f"duration_ps: {d * US} }}" for m, s, d in spans)


def _meta(names):
    return "\n".join(f'event_metadata {{ key: {i} value {{ id: {i} '
                     f'name: "{n}" }} }}' for i, n in names.items())


def _profile(host, devices) -> ProfileData:
    """Host spans and per-device (ops, modules), each a list of
    (name, start us, duration us), all on one clock."""
    planes = []

    def plane(pid, name, lines):
        names = {}
        body = []
        for lid, (line, spans) in enumerate(lines, 1):
            ids = [names.setdefault(n, len(names) + 1) for n, _, _ in spans]
            body.append(f'lines {{ id: {lid} name: "{line}" timestamp_ns: 0 '
                        + _events([(i, s, d) for i, (_, s, d)
                                   in zip(ids, spans)]) + " }")
        planes.append(f'planes {{ id: {pid} name: "{name}" '
                      + " ".join(body) + " "
                      + _meta({i: n for n, i in names.items()}) + " }")

    plane(1, "/host:CPU", [("python", host)])
    for i, (ops, modules) in enumerate(devices):
        plane(2 + i, f"/device:TPU:{i}",
              [("XLA Ops", ops), ("XLA Modules", modules)])
    text = "\n".join(planes)
    return ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(text))


HOST = [("bench.window", 100, 1000),
        ("bench.save", 100, 500), ("bench.device_put", 600, 400),
        ("bench.before", 0, 100)]
OPS = [("fusion.1", 50, 100),          # clipped to 100..150
       ("fusion.2", 200, 100),         # 200..300
       ("fusion.3", 250, 150),         # overlaps: union 200..400
       ("copy", 900, 250),             # clipped to 900..1100
       ("late", 1200, 10)]             # outside the window
MODULES = [("jit_gf_bitmatmul_batched(7)", 200, 200),
           ("jit_xor_reduce_batched(3)", 900, 100),
           ("jit_xor_reduce_batched(3)", 1050, 100)]


def test_busy_is_the_union_of_ops_inside_the_window():
    s = trace.reduce(_profile(HOST, [(OPS, MODULES)]))
    assert s.window_s == pytest.approx(1000e-6)
    # 100..150, 200..400, 900..1100
    assert s.busy_s == pytest.approx(450e-6)
    assert s.idle_pct == pytest.approx(55.0)
    assert s.op_s["fusion.3"] == pytest.approx(150e-6)
    assert "late" not in s.op_s


def test_kernel_time_is_summed_per_module_inside_the_window():
    s = trace.reduce(_profile(HOST, [(OPS, MODULES)]))
    assert s.kernel_s("gf_bitmatmul_batched") == pytest.approx(200e-6)
    assert s.kernel_s("xor_reduce_batched") == pytest.approx(150e-6)
    assert s.module_runs["jit_xor_reduce_batched"] == 2
    assert s.kernel_s("xor_reduce") is None


def test_idle_gaps_name_what_the_host_was_doing():
    s = trace.reduce(_profile(HOST, [(OPS, MODULES)]))
    # gaps: 150..200 (save), 400..900 (save, then device_put), none after
    assert [round(g * 1e6) for _, g in s.idle_gaps] == [500, 50]
    assert s.idle_gaps[0][0] == "bench.device_put"      # middle at 650
    assert s.idle_gaps[1][0] == "bench.save"


def test_busy_is_averaged_over_devices():
    other = [("fusion.9", 100, 1000)]
    s = trace.reduce(_profile(HOST, [(OPS, MODULES), (other, [])]))
    assert s.devices == 2
    assert s.busy_s == pytest.approx((450e-6 + 1000e-6) / 2)


def test_a_trace_without_the_window_or_a_device_is_refused():
    with pytest.raises(ValueError, match="bench.window"):
        trace.reduce(_profile(HOST[1:], [(OPS, MODULES)]))
    with pytest.raises(ValueError, match="device"):
        trace.reduce(_profile(HOST, []))


def test_roofline_is_the_hbm_floor_over_kernel_time():
    run = Run(seconds=1.0, device_kind="TPU v5 lite")
    run.trace = trace.reduce(_profile(HOST, [(OPS, MODULES)]))
    # 81.9 MB at 819 GB/s is 100 us; the fold ran for 150 us
    assert roofline_pct(run, "xor_reduce", 81_900_000) == pytest.approx(
        100 * 100 / 150)
    assert roofline_pct(run, "gf_bitmatmul", 0) is None   # no work counted
    assert idle_pct(run) == pytest.approx(55.0)
    run.device_kind = "TPU v9"
    with pytest.raises(KeyError):
        roofline_pct(run, "xor_reduce", 81_900_000)


RECORDED = pathlib.Path(__file__).parent / "data" / "v5e_small.xplane.pb"
BLOCK = 1 << 16


def test_a_recorded_v5e_trace():
    """One window recorded on a TPU v5e (`TPU v5 lite`): an encode of 2
    stripes of the 30-of-42 code at 64 KiB blocks (`bench.save`), then a
    fold of 3 stripes x 6 sources (`bench.restore`)."""
    data = ProfileData.from_file(str(RECORDED))
    s = trace.reduce(data)
    # busy counted again, a nanosecond at a time
    (lo, hi), = [(int(e.start_ns), int(e.end_ns)) for p in data.planes
                 for line in p.lines for e in line.events
                 if e.name == trace.WINDOW]
    mask = np.zeros(hi - lo, bool)
    for p in data.planes:
        if p.name == "/device:TPU:0":
            for line in p.lines:
                if line.name == "XLA Ops":
                    for e in line.events:
                        a = max(int(e.start_ns), lo) - lo
                        b = min(int(e.end_ns), hi) - lo
                        mask[a:max(a, b)] = True
    assert s.devices == 1
    assert s.busy_s == pytest.approx(mask.sum() / 1e9)
    assert 0 < s.busy_s < s.window_s
    assert s.module_runs["jit_gf_bitmatmul_batched"] == 1
    assert s.module_runs["jit_xor_reduce_batched"] == 1
    run = Run(seconds=s.window_s, device_kind="TPU v5 lite")
    run.trace = s
    floors = {"gf_bitmatmul": 2 * (30 + 12) * BLOCK,
              "xor_reduce": 3 * (6 + 1) * BLOCK}
    for kernel, floor in floors.items():
        assert 0 < roofline_pct(run, kernel, floor) <= 100
    assert {g for g, _ in s.idle_gaps} <= {"bench.window", "bench.save",
                                            "bench.restore"}
