"""Tile/batch planner for the coding kernels.

Every Pallas launch in this repo tiles its lane (byte/int32) dimension.
Historically the tile was the hard-coded `DEFAULT_BLOCK_B` (512 bytes
for the GF matmul, 2048 int32 lanes for the XOR fold), which (a) pads
every block up to a 512-multiple — pure wasted bytes and MXU cycles for
the paper grid's smaller blocks — and (b) leaves free VMEM on the table
for narrow codes, where a bigger tile means fewer grid steps per
stripe. This module owns the decision instead; `ops.py` routes every
default through it, and the repo lint (rule RA008) flags hard-coded
tiles anywhere else.

Analytic VMEM model (the budget math from gf_bitmatmul.py's header):
one grid step of the bit-plane coding matmul holds, per (m, k, Bt),

    A_bits fp32   8m * 8k * 4   resident coefficient tile
    x_bits fp32   8k * Bt * 4   unpacked data bit-planes
    acc    fp32   8m * Bt * 4   MXU accumulator
    bytes  uint8  (k + m) * Bt  in/out byte tiles

and the XOR fold holds (s + 1) * Bt_words 4-byte words. The budget
defaults to 8 MiB, which leaves room for Pallas double-buffering of the
streamed operands. The v5e compiler accepts the tiles this budget plans
at 1 MiB blocks for 180-of-210 and 30-of-42 (tests/test_tpu_compile.py);
that is the compiler's verdict on these shapes, not a measured VMEM
ceiling.

Tile selection: lane tiles must be multiples of 128 (TPU lane count);
among the candidates that fit the budget the planner first minimises
padded size — ceil(B / Bt) * Bt, i.e. wasted work — and then takes the
LARGEST such tile, i.e. the fewest grid steps. 128 always achieves the
minimum possible padding, so the padding term never loses to the
grid-step term; the seed behaviour (B already a 512-multiple, widest
code) is reproduced exactly, while e.g. a 384-byte block pads to 384
instead of 512 and a 1 MiB block on a narrow code rides 4096-byte
tiles instead of 2048 grid steps of 512.

Measured-timings cache: the analytic model is exact about *capacity*
but interpret mode (this container) says nothing about real MXU/VPU
throughput. On hardware, `measure_matmul_tiles` times the feasible
candidates once and `save_timings` persists the winners as JSON:

    {"version": 1,
     "entries": {"gfmm:k=180:m=30:B=1048576":
                     {"block_b": 1024, "seconds": 0.00213},
                 "xor:s=5:lanes=262144": {"block_b": 2048, ...}}}

Point `REPRO_AUTOTUNE_CACHE` at that file and every subsequent run
resolves the same keys through the measurements (still clamped to the
VMEM budget) instead of the model — tune once, serve forever. Without
the env var nothing is read or written; interpret-mode CI stays
deterministic and file-free.
"""
from __future__ import annotations

import dataclasses
import functools
import json
import os
import pathlib
import time

LANE = 128                       # TPU lane count: tiles are multiples
MAX_MATMUL_BLOCK_B = 4096        # bytes — grid-step floor for huge B
MAX_XOR_BLOCK_LANES = 8192       # 4-byte words (32 KiB)
DEFAULT_VMEM_BUDGET = 8 << 20    # bytes, see module docstring
CACHE_ENV = "REPRO_AUTOTUNE_CACHE"
_TIMINGS_VERSION = 1


@dataclasses.dataclass(frozen=True)
class TilePlan:
    """One lane-dimension tiling decision.

    `block_b` and `padded` are in the kernel's lane units: bytes for
    the GF matmul, 4-byte words for the XOR fold. `pad` is the wasted
    lane-units per row (`padded - size`); `grid_steps` the per-stripe
    grid extent; `vmem_bytes` the modeled residency of one grid step.
    `source` records whether the choice came from the analytic model
    or a persisted measurement."""
    block_b: int
    padded: int
    pad: int
    grid_steps: int
    vmem_bytes: int
    source: str = "model"


def matmul_vmem_bytes(k: int, m: int, block_b: int) -> int:
    """Modeled VMEM bytes of one gf_bitmatmul grid step (header math)."""
    a_bits = (8 * m) * (8 * k) * 4
    x_bits = (8 * k) * block_b * 4
    acc = (8 * m) * block_b * 4
    byte_tiles = (k + m) * block_b
    return a_bits + x_bits + acc + byte_tiles


def xor_vmem_bytes(s: int, block_lanes: int) -> int:
    """Modeled VMEM bytes of one xor_reduce grid step: the (s, 4 * Bt)
    byte source tile plus the (4 * Bt,) fold output."""
    return (s + 1) * block_lanes * 4


def _padded(size: int, tile: int) -> int:
    return -(-max(size, 1) // tile) * tile


def _select(size: int, max_tile: int, fits) -> int:
    """The largest LANE-multiple tile <= max_tile that fits the budget
    AND achieves the minimum possible padding of `size`. At least one
    candidate (LANE itself) is always considered feasible — a budget so
    small that a single 128-lane tile overflows is a configuration
    error upstream, not something to tile around."""
    pad_floor = _padded(size, LANE)
    best = LANE
    for tile in range(2 * LANE, max_tile + 1, LANE):
        if _padded(size, tile) == pad_floor and fits(tile):
            best = tile
    return best


# -- measured-timings cache ---------------------------------------------------

def timings_path() -> pathlib.Path | None:
    """The persisted-timings file, or None when tuning is disabled
    (no REPRO_AUTOTUNE_CACHE in the environment)."""
    p = os.environ.get(CACHE_ENV)
    return pathlib.Path(p) if p else None


def load_timings(path: pathlib.Path | None = None) -> dict[str, dict]:
    """Measured entries from `path` (default: the env-pointed file);
    {} when absent, unreadable, or version-mismatched."""
    path = path or timings_path()
    if path is None or not path.exists():
        return {}
    try:
        doc = json.loads(path.read_text())
    except (OSError, ValueError):
        return {}
    if doc.get("version") != _TIMINGS_VERSION:
        return {}
    entries = doc.get("entries", {})
    return entries if isinstance(entries, dict) else {}


def save_timings(entries: dict[str, dict],
                 path: pathlib.Path | None = None) -> pathlib.Path:
    """Merge `entries` into the timings file (creating it) and return
    its path. Raises ValueError when no path is given and the env var
    is unset — persisting measurements is always an explicit ask."""
    path = path or timings_path()
    if path is None:
        raise ValueError(
            f"no timings path: pass path= or set {CACHE_ENV}")
    merged = load_timings(path)
    merged.update(entries)
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(
        {"version": _TIMINGS_VERSION, "entries": merged}, indent=2))
    invalidate_plan_cache()
    return path


def matmul_key(k: int, m: int, B: int) -> str:
    return f"gfmm:k={k}:m={m}:B={B}"


def xor_key(s: int, lanes: int) -> str:
    return f"xor:s={s}:lanes={lanes}"


@functools.lru_cache(maxsize=1)
def _timings() -> dict[str, dict]:
    return load_timings()


def invalidate_plan_cache() -> None:
    """Drop memoized plans + the loaded timings file (call after
    changing REPRO_AUTOTUNE_CACHE or persisting new measurements)."""
    _timings.cache_clear()
    plan_matmul_tiles.cache_clear()
    plan_xor_tiles.cache_clear()


def _measured_block_b(key: str) -> int | None:
    entry = _timings().get(key)
    if isinstance(entry, dict):
        bb = entry.get("block_b")
        if isinstance(bb, int) and bb >= LANE and bb % LANE == 0:
            return bb
    return None


# -- planners -----------------------------------------------------------------

@functools.lru_cache(maxsize=512)
def plan_matmul_tiles(k: int, m: int, B: int, *,
                      vmem_budget: int = DEFAULT_VMEM_BUDGET) -> TilePlan:
    """Lane tile for a (m, k) GF coding matmul over B-byte blocks."""
    def fits(tile: int) -> bool:
        return matmul_vmem_bytes(k, m, tile) <= vmem_budget

    measured = _measured_block_b(matmul_key(k, m, B))
    if measured is not None and measured <= MAX_MATMUL_BLOCK_B \
            and fits(measured):
        bb, source = measured, "measured"
    else:
        bb, source = _select(B, MAX_MATMUL_BLOCK_B, fits), "model"
    padded = _padded(B, bb)
    return TilePlan(block_b=bb, padded=padded, pad=padded - B,
                    grid_steps=padded // bb,
                    vmem_bytes=matmul_vmem_bytes(k, m, bb), source=source)


@functools.lru_cache(maxsize=512)
def plan_xor_tiles(s: int, nbytes: int, *,
                   vmem_budget: int = DEFAULT_VMEM_BUDGET) -> TilePlan:
    """Lane tile, in 4-byte words, for an s-source XOR fold of B-byte
    rows. ops.py runs the kernel on the bytes with a 4 * block_b tile."""
    lanes = -(-max(nbytes, 1) // 4)

    def fits(tile: int) -> bool:
        return xor_vmem_bytes(s, tile) <= vmem_budget

    measured = _measured_block_b(xor_key(s, lanes))
    if measured is not None and measured <= MAX_XOR_BLOCK_LANES \
            and fits(measured):
        bb, source = measured, "measured"
    else:
        bb, source = _select(lanes, MAX_XOR_BLOCK_LANES, fits), "model"
    padded = _padded(lanes, bb)
    return TilePlan(block_b=bb, padded=padded, pad=padded - lanes,
                    grid_steps=padded // bb,
                    vmem_bytes=xor_vmem_bytes(s, bb), source=source)


def plan_stream_windows(k: int, n: int, block_size: int, *,
                        host_budget_bytes: int = 1 << 31,
                        cap: int = 64) -> int:
    """Stripe-batch window: the default `max_batch_stripes` of
    `StripeCodec`, and so the window of the streaming checkpoint write.

    The double-buffered pipeline holds at most TWO windows of (n,
    block_size) codewords plus one (k, block_size) input view per
    stripe; pick the largest window (<= cap) whose staging fits
    `host_budget_bytes`. Always >= 1.

    The same bound keeps the device side in one chip's HBM: a window's
    encode holds its (k, B) input, (m, B) parities and (n, B) codeword
    per stripe, (k + m + n) * B < (2n + k) * B, so two live windows stay
    under 2 * host_budget_bytes (4 GiB by default; a v5e has 16 GiB).
    The 180-of-210 code at 1 MiB blocks gets 3 stripes; a fixed 64 would
    put two ~13 GiB codeword windows on the device."""
    per_stripe = (2 * n + k) * block_size
    return max(1, min(cap, host_budget_bytes // max(per_stripe, 1)))


# -- measurement (real-TPU tuning) --------------------------------------------

def measure_matmul_tiles(k: int, m: int, B: int, *,
                         vmem_budget: int = DEFAULT_VMEM_BUDGET,
                         repeat: int = 3,
                         interpret: bool | None = None) -> dict[str, dict]:
    """Time every feasible lane tile for a (m, k) x B coding matmul and
    return a one-entry timings dict for the winner (merge with
    `save_timings`). Meant for real hardware — interpret mode's timings
    reflect the Python grid loop, not the MXU — but runs anywhere,
    which is how the unit tests exercise the cache round trip."""
    import numpy as np

    from .gf_bitmatmul import gf_bitmatmul
    from .ops import _bits, _pad_to, default_interpret

    if interpret is None:
        interpret = default_interpret()
    rng = np.random.default_rng(0xEC)
    A = rng.integers(0, 256, (m, k), dtype=np.uint8)
    data = rng.integers(0, 256, (k, B), dtype=np.uint8)
    a_bits = _bits(A, f"autotune:{k}x{m}")
    pad_floor = _padded(B, LANE)
    candidates = [
        t for t in range(LANE, MAX_MATMUL_BLOCK_B + 1, LANE)
        if _padded(B, t) == pad_floor
        and matmul_vmem_bytes(k, m, t) <= vmem_budget] or [LANE]
    best_bb, best_s = candidates[0], float("inf")
    for bb in candidates:
        padded, _ = _pad_to(data, bb, axis=1)
        out = gf_bitmatmul(a_bits, padded, block_b=bb, interpret=interpret)
        out.block_until_ready()                      # compile + warm
        t0 = time.perf_counter()
        for _ in range(repeat):
            gf_bitmatmul(a_bits, padded, block_b=bb,
                         interpret=interpret).block_until_ready()
        dt = (time.perf_counter() - t0) / repeat
        if dt < best_s:
            best_bb, best_s = bb, dt
    return {matmul_key(k, m, B): {"block_b": best_bb,
                                  "seconds": best_s}}
