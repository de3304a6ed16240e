"""Request-level I/O layer: backend abstraction, coalescing op engine
(with gateway XOR pre-folds), priority-classed shard-parallel front-end
with admission control, a degraded-read hot-block cache, and the
Zipf/virtual-time workload machinery that drives it at saturation. Sits
between the kernels and the stripe planner:
topo → core → kernels → io → ckpt → launch."""
from .backend import (BACKENDS, Backend, KernelBackend, NumpyBackend,
                      resolve_backend)
from .cache import CacheStats, HotBlockCache
from .engine import CodingEngine, CorruptionError, FlushStats, OpHandle
# Priority/ClassStats canonically live in repro.priority; re-exported
# here because the io layer is where most consumers meet them.
from .frontend import (ClassStats, MergedHandle, Priority, RequestFrontend,
                       RequestHandle, RequestShed, ScrubReport,
                       ServiceSample, ShardedFrontend)
from .workload import (Arrival, CompletedRequest, ServiceModel,
                       VirtualClock, ZipfWorkload, drive_open_loop)

__all__ = ["BACKENDS", "Backend", "KernelBackend", "NumpyBackend",
           "resolve_backend",
           "CacheStats", "HotBlockCache",
           "CodingEngine", "CorruptionError", "FlushStats", "OpHandle",
           "ClassStats", "MergedHandle", "Priority", "RequestFrontend",
           "RequestHandle", "RequestShed", "ScrubReport", "ServiceSample",
           "ShardedFrontend",
           "Arrival", "CompletedRequest", "ServiceModel", "VirtualClock",
           "ZipfWorkload", "drive_open_loop"]
