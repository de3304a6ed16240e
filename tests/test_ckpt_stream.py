"""Checkpoint-scale streaming write fast path.

`StripeCodec.write_stream` (and `CheckpointManager.write_checkpoint` /
the front-ends' `submit_checkpoint_write` on top of it) must be
BYTE-IDENTICAL to the synchronous per-window `write` path on both
backends — the pipeline overlaps encode dispatch with store landing and
batches the per-block puts, it never changes the stripes. The
deterministic sweep below runs everywhere; the hypothesis section
(skipped when hypothesis is absent, like the other property modules)
drives arbitrary buffer sizes including non-multiples of the stripe
capacity and of the kernel tile.

Also pinned here: the streamed path's launch budget (exactly
ceil(S/window) encode launches), its O(window) — not O(buffer) — host
staging memory, and the `put_many` batched mutation-listener protocol
the landing path rides (one notification per window, hot-block cache
invalidation stays exact).
"""
import glob
import math
import tracemalloc

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro import obs
from repro.ckpt import BlockStore, CheckpointManager, DiskBlockStore
from repro.ckpt import serialize
from repro.ckpt.serialize import (DeviceStripes, serialize_tree,
                                  stripes_on_device)
from repro.ckpt.stripe import StripeCodec
from repro.core.codes import make_unilrc
from repro.io.cache import HotBlockCache
from repro.io.frontend import RequestFrontend, ShardedFrontend
from repro.topo import Topology

CODE = make_unilrc(1, 4)                 # small: k=8, fast under pytest
BS = 1 << 10


def make_codec(backend="kernels", *, store=None, block_size=BS,
               max_batch_stripes=3):
    store = store or BlockStore(Topology(4, 6))
    codec = StripeCodec(CODE, store, block_size=block_size,
                        backend=backend,
                        max_batch_stripes=max_batch_stripes)
    return codec, store


def stripes_identical(store_a, store_b, metas, n):
    return all(store_a.get(m.stripe_id, b) == store_b.get(m.stripe_id, b)
               for m in metas for b in range(n))


# ---------------------------------------------------------------------------
# byte identity: streamed == seed per-window write
# ---------------------------------------------------------------------------

stripe_payload = CODE.k * BS
SIZES = [1, 37, BS - 1, BS + 1, stripe_payload - 7, stripe_payload,
         stripe_payload + 1, 3 * stripe_payload + 123, 7 * stripe_payload]


@pytest.mark.parametrize("backend", ["kernels", "numpy"])
def test_write_stream_byte_identical(backend):
    rng = np.random.default_rng(0)
    for size in SIZES:
        buf = rng.integers(0, 256, size, dtype=np.uint8).tobytes()
        codec_a, store_a = make_codec(backend)
        codec_b, store_b = make_codec(backend)
        metas_a = codec_a.write(buf)
        metas_b = codec_b.write_stream(buf)
        assert [(m.stripe_id, m.nbytes) for m in metas_a] \
            == [(m.stripe_id, m.nbytes) for m in metas_b]
        assert stripes_identical(store_a, store_b, metas_a, CODE.n)
        assert codec_b.read_all(metas_b)[:size] == buf


@pytest.mark.parametrize("window", [1, 2, 3])
def test_write_stream_window_sizes(window):
    """Every window split lands the same stripes (tail windows, windows
    clamped to max_batch_stripes, single-stripe windows)."""
    rng = np.random.default_rng(1)
    buf = rng.integers(0, 256, 5 * stripe_payload + 99,
                       dtype=np.uint8).tobytes()
    ref_codec, ref_store = make_codec("numpy")
    metas_ref = ref_codec.write(buf)
    codec, store = make_codec("numpy")
    metas = codec.write_stream(buf, window_stripes=window)
    assert len(metas) == len(metas_ref)
    assert stripes_identical(ref_store, store, metas_ref, CODE.n)


def test_write_stream_start_stripe_and_cursor():
    rng = np.random.default_rng(2)
    buf = rng.integers(0, 256, 2 * stripe_payload, dtype=np.uint8).tobytes()
    codec, store = make_codec("numpy")
    metas = codec.write_stream(buf, start_stripe=5)
    assert [m.stripe_id for m in metas] == [5, 6]
    assert codec.read_all(metas) == buf


# ---------------------------------------------------------------------------
# hypothesis property: arbitrary sizes (skipped without hypothesis)
# ---------------------------------------------------------------------------

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                      # pragma: no cover
    HAVE_HYPOTHESIS = False

if HAVE_HYPOTHESIS:
    @settings(max_examples=25, deadline=None)
    @given(size=st.integers(1, 6 * stripe_payload + 512),
           window=st.integers(1, 4),
           backend=st.sampled_from(["kernels", "numpy"]),
           seed=st.integers(0, 2**31 - 1))
    def test_write_stream_byte_identical_property(size, window, backend,
                                                  seed):
        rng = np.random.default_rng(seed)
        buf = rng.integers(0, 256, size, dtype=np.uint8).tobytes()
        codec_a, store_a = make_codec(backend)
        codec_b, store_b = make_codec(backend)
        metas_a = codec_a.write(buf)
        metas_b = codec_b.write_stream(buf, window_stripes=window)
        assert [(m.stripe_id, m.nbytes) for m in metas_a] \
            == [(m.stripe_id, m.nbytes) for m in metas_b]
        assert stripes_identical(store_a, store_b, metas_a, CODE.n)


# ---------------------------------------------------------------------------
# launch budget: exactly ceil(S / window) encode launches
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("nstripes,window", [(1, 3), (4, 2), (7, 3), (6, 3)])
def test_write_stream_launch_budget(kernel_counters, nstripes, window):
    rng = np.random.default_rng(3)
    buf = rng.integers(0, 256, nstripes * stripe_payload - 5,
                       dtype=np.uint8).tobytes()
    codec, _ = make_codec("kernels")
    codec.write_stream(buf, window_stripes=window)
    assert sum(kernel_counters.values()) == math.ceil(nstripes / window)


# ---------------------------------------------------------------------------
# memory: a streamed write stages O(window), not O(buffer)
# ---------------------------------------------------------------------------

def test_write_stream_memory_is_o_window(tmp_path):
    """tracemalloc peak during a multi-window streamed write stays well
    under the buffer size. DiskBlockStore (payload index holds b"") and
    the numpy backend keep retained store/device memory out of the
    measurement — what remains is the writer's own staging: windows of
    codewords plus the padded tail, all O(window)."""
    window = 2
    nstripes = 12
    store = DiskBlockStore(Topology(4, 6), tmp_path)
    codec, _ = make_codec("numpy", store=store,
                          max_batch_stripes=window)
    rng = np.random.default_rng(4)
    buf = rng.integers(0, 256, nstripes * stripe_payload,
                       dtype=np.uint8).tobytes()
    window_bytes = window * CODE.n * BS
    tracemalloc.start()
    try:
        codec.write_stream(buf, window_stripes=window)
        _, peak = tracemalloc.get_traced_memory()
    finally:
        tracemalloc.stop()
    # double buffer (2 windows of codewords) + tail staging + slack;
    # the seed bug was an O(buffer) copy of the whole input (~len(buf)).
    assert peak < 6 * window_bytes + len(buf) // 4, \
        f"peak {peak} vs window {window_bytes} (buffer {len(buf)})"


# ---------------------------------------------------------------------------
# put_many: batched listener protocol
# ---------------------------------------------------------------------------

def test_put_many_single_batched_notification():
    store = BlockStore(Topology(2, 4))
    per, batches = [], []
    store.add_mutation_listener(
        lambda s, b: per.append((s, b)),
        batch=lambda pairs: batches.append(list(pairs)))
    entries = [(0, b, store.topo.node_of(0, 0), bytes(8))
               for b in range(5)]
    assert store.put_many(entries) == 5
    assert per == []                     # batch handler consumed them
    assert batches == [[(0, b) for b in range(5)]]
    # per-put behavior unchanged: single puts still notify per pair
    store.put(1, 0, store.topo.node_of(0, 1), bytes(8))
    assert per == [(1, 0)]
    assert len(batches) == 1


def test_put_many_per_pair_fallback():
    """A listener registered without a batch handler still sees every
    pair of a bulk landing, exactly once each."""
    store = BlockStore(Topology(2, 4))
    seen = []
    store.add_mutation_listener(lambda s, b: seen.append((s, b)))
    entries = [(2, b, store.topo.node_of(1, 0), bytes(4))
               for b in range(3)]
    store.put_many(entries)
    assert seen == [(2, b) for b in range(3)]


@pytest.mark.parametrize("disk", [False, True])
def test_put_many_matches_put(tmp_path, disk):
    """Bulk landing is byte-equivalent to per-block puts on both store
    tiers, and accepts numpy row views (not just bytes)."""
    topo = Topology(2, 4)
    store_a = DiskBlockStore(topo, tmp_path / "a") if disk \
        else BlockStore(topo)
    store_b = DiskBlockStore(topo, tmp_path / "b") if disk \
        else BlockStore(topo)
    rng = np.random.default_rng(5)
    rows = rng.integers(0, 256, (4, 16), dtype=np.uint8)
    for b in range(4):
        store_a.put(0, b, topo.node_of(0, b % 2), rows[b].tobytes())
    store_b.put_many([(0, b, topo.node_of(0, b % 2), rows[b])
                      for b in range(4)])
    for b in range(4):
        assert store_a.get(0, b) == store_b.get(0, b) == rows[b].tobytes()


def test_put_many_invalidates_hot_block_cache_exactly():
    store = BlockStore(Topology(2, 4))
    cache = HotBlockCache(capacity_blocks=8).attach(store)
    cache.put(0, 1, b"old")
    cache.put(0, 2, b"old")
    cache.put(9, 9, b"unrelated")
    store.put_many([(0, 1, store.topo.node_of(0, 0), b"new"),
                    (0, 2, store.topo.node_of(0, 1), b"new")])
    assert not cache.contains(0, 1) and not cache.contains(0, 2)
    assert cache.contains(9, 9)          # untouched key survives
    assert cache.stats.invalidations == 2


# ---------------------------------------------------------------------------
# manager + front-end integration
# ---------------------------------------------------------------------------

def test_manager_write_checkpoint_roundtrip():
    store = BlockStore(Topology(4, 6))
    mgr = CheckpointManager(store, CODE, block_size=BS)
    rng = np.random.default_rng(6)
    buf = rng.integers(0, 256, 3 * stripe_payload + 11,
                       dtype=np.uint8).tobytes()
    metas = mgr.write_checkpoint(buf)
    assert [m.stripe_id for m in metas] == list(range(len(metas)))
    assert mgr.codec.read_all(metas)[:len(buf)] == buf
    # cursor advanced: a subsequent save starts after the streamed write
    metas2 = mgr.write_checkpoint(buf)
    assert metas2[0].stripe_id == len(metas)


@pytest.mark.parametrize("shards", [1, 2])
def test_frontend_checkpoint_write_background(shards):
    codec, store = make_codec("numpy")
    fe = ShardedFrontend(codec, num_shards=shards) if shards > 1 \
        else RequestFrontend(codec)
    rng = np.random.default_rng(7)
    buf = rng.integers(0, 256, 4 * stripe_payload + 5,
                       dtype=np.uint8).tobytes()
    handle = fe.submit_checkpoint_write(buf, 0)
    assert not handle.done
    fe.drain()
    metas = handle.result()
    assert len(metas) == 5
    assert codec.read_all(metas)[:len(buf)] == buf
    if shards > 1:
        fe.close()


# ---------------------------------------------------------------------------
# device path: a state on the device is striped and encoded there
# ---------------------------------------------------------------------------

# host platforms of a manager that takes the CPU for an accelerator, as a
# TPU is: a state on it takes the device path
ACCELERATOR = frozenset()

LEAF_CASES = [
    ("float32", ((serialize.ROW // 4) * 600 + 5,)),   # many rows, a tail
    ("float32", (300, 128)),
    ("bfloat16", (1000, 3)),
    ("int32", (7,)),                                  # under one row
    ("int32", ()),                                    # 0-d
    ("uint16", (513, 256)),
    ("int8", (33,)),
    ("bool", (130,)),
]


def _host_leaf(dtype, shape, seed=len(LEAF_CASES)):
    """Every bit pattern, NaNs and subnormals among them."""
    rng = np.random.default_rng(seed)
    bits = rng.integers(0, 256, int(np.prod(shape)) * np.dtype(
        jnp.dtype(dtype)).itemsize, dtype=np.uint8)
    if dtype == "bool":
        return (bits & 1).astype(bool).reshape(shape)
    return bits.view(jnp.dtype(dtype)).reshape(shape)


@pytest.mark.parametrize("dtype,shape", LEAF_CASES)
def test_leaf_bytes_are_numpys_bytes(dtype, shape):
    """Every bit pattern comes out as numpy's `tobytes` has it."""
    host = _host_leaf(dtype, shape)
    got = np.asarray(jax.jit(serialize.leaf_bytes)(jnp.asarray(host)))
    assert got.dtype == np.uint8
    assert got.tobytes() == host.tobytes()


@pytest.mark.parametrize("parts", [5, 13])
@pytest.mark.parametrize("dtype,shape", LEAF_CASES)
def test_windows_cut_anywhere_in_a_leaf_are_its_bytes(dtype, shape, parts):
    """One leaf laid out as stripes of 3 blocks of about 1/parts of it,
    two stripes a window: stripe and window boundaries fall mid-row and
    mid-element, and each window holds the leaf's bytes there,
    zero-padded after the last."""
    host = _host_leaf(dtype, shape)
    block_size = host.nbytes // parts + 1
    stripes, manifest, _ = DeviceStripes.of({"x": jnp.asarray(host)}, 3,
                                            block_size)
    size = 3 * block_size
    want = np.zeros(stripes.stripes * size, np.uint8)
    want[:host.nbytes] = np.frombuffer(host.tobytes(), np.uint8)
    for w0 in range(0, stripes.stripes, 2):
        wn = min(2, stripes.stripes - w0)
        got = stripes.window(w0, wn)
        assert got.shape == (wn, 3, block_size)
        assert np.array_equal(np.asarray(got).reshape(-1),
                              want[w0 * size:(w0 + wn) * size])


@pytest.mark.parametrize("shape,a,b", [((100_000,), 0, 400_000),
                                       ((100_000,), 4_099, 399_997),
                                       ((40, 70_000), 1_000, 11_199_997),
                                       ((3, 30_000_000), 5, 360_000_000)])
def test_row_blocks_cover_the_bytes_in_aligned_blocks(shape, a, b):
    """A leaf's bytes [a, b) as blocks of rows that cover them end to
    end: each block CHUNK bytes or one row, whichever is more, plus what
    rounding its ends to whole tiles of rows adds, and every cut on a
    tile's rows unless a row is too wide for CHUNK to hold a tile."""
    leaf = jax.ShapeDtypeStruct(shape, jnp.float32)
    rows, row_bytes = serialize._rows(leaf)
    align = serialize._align(leaf)
    if serialize.CHUNK // row_bytes < align:
        align = 1
    blocks = serialize._row_blocks(leaf, a, b)
    assert blocks[0][0] * row_bytes <= a and blocks[-1][1] * row_bytes >= b
    assert all(x[1] == y[0] for x, y in zip(blocks, blocks[1:]))
    for r0, r1 in blocks:
        assert r0 % align == 0 and (r1 % align == 0 or r1 == rows)
        assert 0 < (r1 - r0) * row_bytes <= max(serialize.CHUNK, row_bytes)
    assert blocks[0][0] > a // row_bytes - align
    assert blocks[-1][1] < -(-b // row_bytes) + align


def mixed_tree(uint8_bytes: int) -> dict:
    """uint8, bfloat16, float32 and int32 leaves and a 0-d one; 22,007
    bytes besides the `d_u8` leaf. The 3 bytes in front put every later
    stripe boundary off the elements' and the rows' boundaries. The
    float32 and bfloat16 leaves are whole rows of `leaf_bytes` plus a
    tail."""
    rng = np.random.default_rng(uint8_bytes)
    return {
        "_u8_odd": jnp.asarray(rng.integers(0, 256, 3), jnp.uint8),
        "a_bf16": jnp.asarray(rng.standard_normal((50, 100)),
                              jnp.bfloat16),
        "b_f32": jnp.asarray(rng.standard_normal(2000) * 1e3, jnp.float32),
        "c_i32": jnp.asarray(rng.integers(-2**31, 2**31, 1000), jnp.int32),
        "d_u8": jnp.asarray(rng.integers(0, 256, uint8_bytes), jnp.uint8),
        "e_step": jnp.int32(-7),
    }


# 4 stripes exactly, and 4 with a padded tail
TREES = {"exact": 4 * stripe_payload - 22_007,
         "padded_tail": 3 * stripe_payload - 22_007 + 1000}


def _stores_identical(mgr_a, store_a, mgr_b, store_b, step):
    metas = mgr_a.stripes_of(step)
    assert metas == mgr_b.stripes_of(step)
    for m in metas:
        for b in range(CODE.n):
            assert store_a.get(m.stripe_id, b) == store_b.get(m.stripe_id, b)
            assert store_a.node_of(m.stripe_id, b) \
                == store_b.node_of(m.stripe_id, b)


@pytest.mark.parametrize("window", [1, 2, 3])
@pytest.mark.parametrize("tree", sorted(TREES))
def test_device_stripes_store_what_host_bytes_store(tree, window):
    """Device stripes and host bytes of one tree: the same manifest, the
    same bytes window by window, and every block of every stripe stored
    the same, on the same node."""
    state = mixed_tree(TREES[tree])
    assert stripes_on_device(state, stripe_payload,
                             host_platforms=ACCELERATOR)
    data, manifest, treedef = DeviceStripes.of(state, CODE.k, BS)
    buf, manifest_h, treedef_h = serialize_tree(state)
    assert (manifest, treedef) == (manifest_h, treedef_h)
    assert data.stripes == 4 and data.nbytes == len(buf)
    padded = np.zeros(4 * stripe_payload, np.uint8)
    padded[:len(buf)] = np.frombuffer(buf, np.uint8)
    for w0 in range(0, 4, window):
        wn = min(window, 4 - w0)
        got = data.window(w0, wn)
        assert got.shape == (wn, CODE.k, BS)
        assert np.array_equal(np.asarray(got).reshape(-1), padded[
            w0 * stripe_payload:(w0 + wn) * stripe_payload])
    mgrs = [CheckpointManager(BlockStore(Topology(4, 6)), CODE,
                              block_size=BS) for _ in range(2)]
    metas = mgrs[0].write_checkpoint(data, window_stripes=window)
    metas_h = mgrs[1].write_checkpoint(buf, window_stripes=window)
    assert metas == metas_h
    assert stripes_identical(mgrs[0].store, mgrs[1].store, metas, CODE.n)


@pytest.mark.parametrize("tree", sorted(TREES))
def test_device_path_save_matches_host_path_and_restores(tree):
    """`save` of a device state and of its numpy copy: identical stores,
    placement, metas and manifests; the restore is bit-exact."""
    state = mixed_tree(TREES[tree])
    runs = []
    for leaves in (state, jax.tree_util.tree_map(np.asarray, state)):
        store = BlockStore(Topology(4, 6))
        mgr = CheckpointManager(store, CODE, block_size=BS,
                                host_platforms=ACCELERATOR)
        before = dict(obs.SAVE_PATHS)
        mgr.save(leaves, step=1)
        runs.append((mgr, store, {p: obs.SAVE_PATHS[p] - before.get(p, 0)
                                  for p in ("device", "host")}))
    (mgr_d, store_d, took_d), (mgr_h, store_h, took_h) = runs
    assert took_d == {"device": 1, "host": 0}
    assert took_h == {"device": 0, "host": 1}
    _stores_identical(mgr_d, store_d, mgr_h, store_h, 1)
    assert mgr_d._saved[1].manifest == mgr_h._saved[1].manifest
    back, _ = mgr_d.restore(1)
    for a, b in zip(jax.tree_util.tree_leaves(state),
                    jax.tree_util.tree_leaves(back)):
        assert a.dtype == b.dtype and a.shape == b.shape
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()


def test_only_a_row_or_a_stripe_past_the_limit_keeps_the_host_path(
        monkeypatch):
    """A leaf of FLAT_LIMIT bytes or more is flattened on the device a
    chunk at a time; a row of more than FLAT_LIMIT / 4 bytes, or a stripe
    of FLAT_LIMIT or more, is not: XLA would index a flat buffer past
    int32."""
    monkeypatch.setattr(serialize, "FLAT_LIMIT", 8192)

    def device(tree, stripe_bytes=4096):
        return stripes_on_device(tree, stripe_bytes,
                                 host_platforms=ACCELERATOR)
    assert device({"w": jnp.zeros(100, jnp.float32)})
    assert device({"w": jnp.zeros(4096, jnp.float32)})       # 16 KiB
    assert device({"w": jnp.zeros((8, 512), jnp.float32)})   # rows of 2 KiB
    assert not device({"w": jnp.zeros((2, 520), jnp.float32)})
    assert not device({"w": jnp.zeros(100, jnp.float32)}, 8192)


def _jax_state():
    return {"w": jnp.arange(3000, dtype=jnp.float32), "step": jnp.int32(3)}


# (state, backend, host platforms, the path its save takes)
PATH_CASES = {
    "jax_leaves": (_jax_state, "kernels", ACCELERATOR, "device"),
    "numpy_leaves": (lambda: jax.tree_util.tree_map(np.asarray,
                                                    _jax_state()),
                     "kernels", ACCELERATOR, "host"),
    "python_scalar_leaf": (lambda: {**_jax_state(), "step": 3},
                           "kernels", ACCELERATOR, "host"),
    "numpy_backend": (_jax_state, "numpy", ACCELERATOR, "host"),
    "jax_leaves_in_host_memory": (_jax_state, "kernels",
                                  serialize.HOST_PLATFORMS, "host"),
}


@pytest.fixture(scope="module")
def save_paths(tmp_path_factory):
    """Each case's save, in PATH_CASES order inside one trace: the
    SAVE_PATHS counts it added, and the `path` attribute of every
    `repro.ckpt.serialize` span in start order."""
    log_dir = str(tmp_path_factory.mktemp("paths"))
    counted = {}
    with jax.profiler.trace(log_dir):
        for name, (make, backend, host, _path) in PATH_CASES.items():
            mgr = CheckpointManager(BlockStore(Topology(4, 6)), CODE,
                                    block_size=BS, backend=backend,
                                    host_platforms=host)
            before = dict(obs.SAVE_PATHS)
            mgr.save(make(), step=0)
            counted[name] = {p: n - before.get(p, 0)
                             for p, n in obs.SAVE_PATHS.items()
                             if n != before.get(p, 0)}
    path, = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    events = sorted((ev.start_ns, dict(ev.stats)["path"])
                    for plane in ProfileData.from_file(path).planes
                    for line in plane.lines for ev in line.events
                    if ev.name == "repro.ckpt.serialize")
    return counted, [p for _start, p in events]


@pytest.mark.parametrize("case", list(PATH_CASES))
def test_save_takes_the_path_its_input_allows(save_paths, case):
    """Device leaves under the kernel backend take the device path;
    numpy leaves, a Python scalar leaf, the numpy backend or leaves whose
    device memory is host memory keep host bytes. Read from the counter
    and from the span's attribute."""
    counted, spans = save_paths
    path = PATH_CASES[case][3]
    assert counted[case] == {path: 1}
    assert spans[list(PATH_CASES).index(case)] == path
