"""What one run measured: the operations of its window, the counters read
around it, its trace, and the numbers compared for `correct`."""
from __future__ import annotations

import dataclasses

GiB = float(1 << 30)


@dataclasses.dataclass
class Op:
    """One operation of the window: when it ended, in seconds from the
    window's start, or None when it failed."""
    kind: str
    done: float | None
    nbytes: int


@dataclasses.dataclass
class Check:
    """One number compared for `correct`, with the largest it may read."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


@dataclasses.dataclass
class Run:
    seconds: float                    # the window asked for
    config: dict = dataclasses.field(default_factory=dict)
    ops: list[Op] = dataclasses.field(default_factory=list)
    end: float = 0.0                  # last completion, from window start
    counters: dict[str, float] = dataclasses.field(default_factory=dict)
    trace: object = None              # trace.Summary in a traced run
    checks: list[Check] = dataclasses.field(default_factory=list)
    setup_s: float = 0.0
    compiles_in_window: int = 0
    peak_bytes: int = 0               # peak_bytes_in_use, fullest chip
    device_kind: str = ""

    def of(self, kind: str) -> list[Op]:
        return [op for op in self.ops if op.kind == kind]


def rate_GiBps(ops: list[Op]) -> float | None:
    """Bytes of every operation over the time from the window's start to
    the last completion; None when one failed or none ran."""
    if not ops or any(op.done is None for op in ops):
        return None
    return sum(op.nbytes for op in ops) / GiB / max(op.done for op in ops)


def roofline_pct(run: Run, kernel: str, floor_bytes: float) -> float | None:
    """The kernel's share of its HBM roofline: `floor_bytes`, what its work
    in the window must move at the least, over the chip's HBM bandwidth,
    against the device time of the kernel's module in the trace. A coding
    kernel's GF(2^8) work has no operation count that does not depend on
    how it is computed, so its bound is memory by definition."""
    from .peaks import peaks
    if run.trace is None or not floor_bytes:
        return None
    seconds = run.trace.kernel_s(f"{kernel}_batched")
    if seconds is None:
        return None
    floor_s = floor_bytes / peaks(run.device_kind).hbm_bytes_per_s
    return 100.0 * floor_s / seconds


def idle_pct(run: Run) -> float | None:
    return None if run.trace is None else run.trace.idle_pct
