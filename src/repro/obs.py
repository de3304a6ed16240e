"""Program spans and host<->device byte counters, on the JAX profiler.

`span(name, **attrs)` is a `jax.profiler.TraceAnnotation` named
`repro.<name>`. While a trace runs (`jax.profiler.trace(dir)`, or a
profiler server), it lands in the same `.xplane.pb` as the device planes,
on the profiler's clock, with `attrs` as the event's stats; otherwise it
costs about a microsecond. Spans wrap calls made once per save, restore,
window, stripe or plan group, never once per block or leaf.

TRANSFERS counts the bytes the coding path copies between host memory and
the device, under `to_device` and `from_device`. `to_device` and `to_host`
make the copies and count them; `_count` is the one mutation point, under
a lock (the sharded front-end flushes from a worker pool); `snapshot()`
copies the totals, so a caller takes a delta.
"""
from __future__ import annotations

import collections
import threading

import jax
import numpy as np

TRANSFERS: collections.Counter = collections.Counter()
_LOCK = threading.Lock()


def span(name: str, **attrs) -> jax.profiler.TraceAnnotation:
    return jax.profiler.TraceAnnotation(f"repro.{name}", **attrs)


def _count(direction: str, nbytes: int) -> None:
    with _LOCK:
        TRANSFERS[direction] += int(nbytes)


def snapshot() -> dict[str, int]:
    with _LOCK:
        return {d: TRANSFERS[d] for d in ("to_device", "from_device")}


def to_device(x) -> jax.Array:
    """`x` on the default device; host bytes copied there are counted."""
    if isinstance(x, jax.Array):
        return x
    x = np.asarray(x)
    _count("to_device", x.nbytes)
    return jax.device_put(x)


def to_host(x) -> np.ndarray:
    """`x` in host memory; bytes copied from a device are counted."""
    if isinstance(x, jax.Array):
        _count("from_device", x.nbytes)
    return np.asarray(x)
