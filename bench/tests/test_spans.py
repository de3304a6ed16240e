"""The program's `repro.` spans in a profiler trace: their totals per name
and the device's idle gaps named by them, on traces made here with known
intervals and on a checkpoint window profiled here; and `profile_cell.py`,
which reads them in a cell's window."""
from __future__ import annotations

import jax
import pytest
from jax.profiler import ProfileData

from bench import profile_cell
from bench.harness import spans, spec, trace
from bench.harness.record import Op, Run

from .test_trace import HOST, MODULES, OPS, RECORDED, _events, _meta
from .tiny import SECONDS, tiny_cell


def _profile(threads, devices) -> ProfileData:
    """Host threads, each a list of spans, and per-device (ops, modules),
    each a list of (name, start us, duration us), all on one clock."""
    planes = []

    def plane(pid, name, lines):
        names = {}
        body = []
        for lid, (line, events) in enumerate(lines, 1):
            ids = [names.setdefault(n, len(names) + 1) for n, _, _ in events]
            body.append(f'lines {{ id: {lid} name: "{line}" timestamp_ns: 0 '
                        + _events([(i, s, d) for i, (_, s, d)
                                   in zip(ids, events)]) + " }")
        planes.append(f'planes {{ id: {pid} name: "{name}" '
                      + " ".join(body) + " "
                      + _meta({i: n for n, i in names.items()}) + " }")

    plane(1, "/host:CPU", [(f"thread/{i}", t) for i, t in enumerate(threads)])
    for i, (ops, modules) in enumerate(devices):
        plane(2 + i, f"/device:TPU:{i}",
              [("XLA Ops", ops), ("XLA Modules", modules)])
    return ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace("\n".join(planes)))


# the window is 100..1100 us; (name, start us, duration us)
MAIN = [("bench.window", 100, 1000),
        ("bench.save", 100, 500),              # not a program span
        ("repro.ckpt.save", 50, 500),          # clipped to 100..550
        ("repro.ckpt.serialize", 60, 100),     # clipped to 100..160
        ("repro.engine.land", 300, 100),
        ("repro.store.put_many", 320, 50),     # inside land
        ("repro.ckpt.restore", 900, 400),      # clipped to 900..1100
        ("repro.store.get_many", 1050, 100),   # clipped to 1050..1100
        ("repro.late", 1200, 10)]              # outside the window
WORKER = [("repro.store.get_many", 200, 100)]  # another thread, in save
DEVICE = [([("fusion", 100, 10)], [])]


def _totals(*threads) -> dict[str, spans.Total]:
    return spans.reduce(_profile([MAIN, *threads], DEVICE))


def _us(total: spans.Total) -> tuple[int, int, int]:
    return (round(total.inclusive_s * 1e6), round(total.self_s * 1e6),
            total.count)


def test_spans_are_clipped_to_the_window():
    t = _totals()
    assert _us(t["repro.ckpt.serialize"]) == (60, 60, 1)
    assert _us(t["repro.ckpt.restore"])[0] == 200
    assert _us(t["repro.store.get_many"]) == (50, 50, 1)
    assert "repro.late" not in t and "bench.save" not in t


def test_self_time_leaves_out_the_children():
    t = _totals()
    # save 450 less serialize 60 and land 100; land 100 less put_many 50
    assert _us(t["repro.ckpt.save"]) == (450, 290, 1)
    assert _us(t["repro.engine.land"]) == (100, 50, 1)
    assert _us(t["repro.store.put_many"]) == (50, 50, 1)
    assert _us(t["repro.ckpt.restore"]) == (200, 150, 1)


def test_spans_on_two_threads_are_kept_apart():
    t = _totals(WORKER)
    assert _us(t["repro.ckpt.save"]) == (450, 290, 1)
    assert _us(t["repro.store.get_many"]) == (150, 150, 2)


def test_idle_gaps_are_named_by_the_innermost_program_span():
    """The gaps `trace.reduce` finds, with the same lengths, each named by
    the innermost `bench.` or `repro.` span open at its middle."""
    program = [("repro.ckpt.save", 120, 300),      # 150..200 and 400..420
               ("repro.engine.land", 600, 100)]    # 600..700, middle 650
    g = spans.gaps(_profile([HOST + program], [(OPS, MODULES)]))
    plain = trace.reduce(_profile([HOST], [(OPS, MODULES)]))
    assert g.longest == [("repro.engine.land", plain.idle_gaps[0][1]),
                         ("repro.ckpt.save", plain.idle_gaps[1][1])]
    assert g.window_s == pytest.approx(plain.window_s)
    assert g.idle_s == pytest.approx(plain.window_s - plain.busy_s)
    # idle 150..200 and 400..900; the spans cover 50 + 20 + 100 us of it
    assert g.in_program_s == pytest.approx(170e-6)
    assert g.in_program_pct == pytest.approx(100 * 170 / 550)
    assert spans.gaps(_profile([HOST], [])) is None


def test_idle_inside_program_spans_is_averaged_over_devices():
    """Device 1 is busy all through the window; half of device 0's 500 us
    idle lies inside a program span."""
    host = [("bench.window", 100, 1000), ("repro.ckpt.save", 100, 250)]
    g = spans.gaps(_profile([host], [([("fusion", 600, 500)], []),
                                     ([("fusion", 100, 1000)], [])]))
    assert g.idle_s == pytest.approx(250e-6)
    assert g.in_program_s == pytest.approx(125e-6)


# What each per-layer metric of the benchmark reads on the v5e trace
# recorded before the program had spans of its own, with the counts a cell
# of the recorded shapes leaves (30-of-42, 64 KiB blocks).
BEFORE = [
    ("device_idle_pct.save", {}, 99.85293748245219),
    ("device_idle_pct.restore", {}, 99.85293748245219),
    ("gf_bitmatmul_roofline.save", {"stripes_encoded": 2},
     9.834580926216256),
    ("gf_bitmatmul_roofline.restore",
     {"stripes_decoded": 2, "blocks_decoded": 4}, 7.493014039021911),
    ("xor_reduce_roofline.restore",
     {"blocks_folded": 3, "fold_sources": 18}, 31.51557120049243),
    ("kernel_launches_per_save", {"launches": 2}, 2.0),
    ("kernel_launches_per_restore", {"launches": 2}, 2.0),
    ("store_read_amplification.restore", {"store_bytes": 150}, 1.5),
]


@pytest.mark.parametrize(("metric", "counters", "value"), BEFORE,
                         ids=[m for m, _, _ in BEFORE])
def test_every_existing_metric_reads_as_before(metric, counters, value):
    data = ProfileData.from_file(str(RECORDED))
    run = Run(seconds=1.0, config={"k": 30, "n": 42, "block_size": 1 << 16},
              counters=counters, device_kind="TPU v5 lite",
              ops=[Op("save", 1.0, 100), Op("restore", 1.0, 100)])
    run.trace = trace.reduce(data)
    assert spans.reduce(data) == {}
    assert spec.Metric(metric, "%", "device_trace").reader()(run) == value


SPANS = {"repro.ckpt.save", "repro.ckpt.restore", "repro.ckpt.serialize",
         "repro.ckpt.assemble", "repro.stripe.write_stream",
         "repro.stripe.read", "repro.engine.flush",
         "repro.engine.encode_dispatch", "repro.engine.encode_wait",
         "repro.engine.land", "repro.engine.recover",
         "repro.backend.encode_many_lazy", "repro.backend.recover_many",
         "repro.store.get_many", "repro.store.put_many"}


def _brute_force_totals(data) -> dict[str, tuple[int, int, int]]:
    """The reduction again, span by span: a span's children are the
    `repro.` spans on its thread that lie inside it and inside no other
    span that does."""
    found = spans.host_spans(data)
    lo, hi = spans.window(found)
    program = [sp for sp in found if sp[2].startswith("repro.")]

    def inside(s, e):
        return max(0, min(e, hi) - max(s, lo))

    def within(a, b):
        return a is not b and a[3] == b[3] and b[0] <= a[0] and a[1] <= b[1]

    out: dict[str, list[int]] = {}
    for sp in program:
        inner = [c for c in program if within(c, sp)]
        children = [c for c in inner
                    if not any(within(c, d) for d in inner)]
        ns = inside(sp[0], sp[1])
        if ns:
            t = out.setdefault(sp[2], [0, 0, 0])
            t[0] += ns
            t[1] += ns - sum(inside(c[0], c[1]) for c in children)
            t[2] += 1
    return {k: tuple(v) for k, v in out.items()}


def test_a_checkpoint_window_profiled_here(tmp_path):
    """The window `record_spans.py` records, profiled on the CPU: every
    span of a save and a degraded restore, each total as the brute-force
    reduction has it."""
    from .record_spans import manager
    save, restore = manager()
    with jax.profiler.trace(str(tmp_path)):
        with trace.span("window"):
            with trace.span("save"):
                save()
            with trace.span("restore"):
                restore()
    data = trace.load(str(tmp_path))
    totals = spans.reduce(data)
    assert SPANS <= set(totals), SPANS - set(totals)
    expect = _brute_force_totals(data)
    assert set(expect) == set(totals)
    for name, t in totals.items():
        inclusive, self_, count = expect[name]
        assert t.count == count
        assert t.inclusive_s == pytest.approx(inclusive / 1e9)
        assert t.self_s == pytest.approx(self_ / 1e9)
        assert 0 <= t.self_s <= t.inclusive_s
    assert totals["repro.ckpt.save"].count == 1
    assert totals["repro.ckpt.restore"].count == 1
    assert totals["repro.stripe.read"].count == 2


@pytest.mark.parametrize("name", ["wide-save", "wide-restore-1down"])
def test_profile_cell_reads_the_program_in_a_window(name):
    """A tiny cell's window, profiled on the CPU: every operation's spans
    and its host-link bytes per payload byte; a save moves each leaf from
    the device, each stripe's k data blocks to it and its n blocks back."""
    cell = tiny_cell(name)
    run, data, counters = profile_cell.execute(cell, 2**31 + 21, SECONDS,
                                               0.0)
    out = profile_cell.summary(run, data, counters)
    assert out["correct"] and out["rate_GiBps"] > 0
    assert "idle" not in out                        # no TPU plane here
    what = out["operation"]
    expect = {m for m, op, _ in profile_cell.SPAN_METRICS if op == what}
    assert expect | {f"host_link_bytes_per_byte.{what}"} == set(
        out["metrics"])
    assert all(0 < v < 100 for m, v in out["metrics"].items()
               if m in expect)
    assert out["spans"][f"repro.ckpt.{what}"][2] == len(run.ops)
    ratio = out["metrics"][f"host_link_bytes_per_byte.{what}"]
    if what == "save":
        cfg = cell.config
        stripes = run.counters["stripes_encoded"]
        moved = sum(op.nbytes for op in run.ops) + stripes * (
            cfg["k"] + cfg["n"]) * cfg["block_size"]
        assert ratio == moved / sum(op.nbytes for op in run.ops)
    else:
        assert 0 < ratio < 1
