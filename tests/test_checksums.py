"""A checksummed store (`checksum="crc32c"`, HDFS's default: CRC32C per
512 bytes): the CRC as the kernels and the host oracle compute it, blocks
found corrupt on a restore rebuilt from their groups and reported, more
corruption than the code tolerates refused, the CRCs kept on disk and
across rebuilds and updates, and a store without checksums saved by the
same backend calls as before."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.ckpt import (BlockStore, CheckpointManager, CorruptionError,
                        DiskBlockStore)
from repro.core.codes import make_unilrc
from repro.core.crc32c import CHECK, crc32c_bitwise, crc32c_chunks
from repro.io.backend import KernelBackend, NumpyBackend
from repro.topo import Topology

BLOCK = 2048
BACKENDS = {"kernels": KernelBackend, "numpy": NumpyBackend}


def _chunks(seed: int) -> np.ndarray:
    """Random 512-byte chunks, one all zero and one all 0xFF, as blocks
    of two chunks."""
    rng = np.random.default_rng(seed)
    data = rng.integers(0, 256, (5, 1024), dtype=np.uint8)
    data[1] = 0
    data[2] = 0xFF
    return data


@pytest.mark.parametrize("backend", sorted(BACKENDS))
def test_the_crc_is_crc32c(backend):
    be = BACKENDS[backend]()
    check = np.frombuffer(b"123456789", np.uint8)[None]
    assert crc32c_bitwise(b"123456789") == CHECK == 0xE3069283
    assert int(np.asarray(be.checksum_many(check, 9))[0, 0]) == CHECK
    data = _chunks(2**31 + 3)
    got = np.asarray(be.checksum_many(data, 512))
    expect = [[crc32c_bitwise(bytes(row[j:j + 512])) for j in (0, 512)]
              for row in data]
    assert got.dtype == np.uint32 and got.tolist() == expect
    assert np.array_equal(crc32c_chunks(data, 512), got)


@pytest.mark.parametrize("backend", sorted(BACKENDS))
def test_verify_counts_the_chunks_that_fail(backend):
    be = BACKENDS[backend]()
    data = _chunks(2**31 + 4)
    crcs = crc32c_chunks(data, 512)
    bad = data.copy()
    bad[3, 700] ^= 0x10                      # the second chunk of block 3
    bad[4, :1024] ^= 1                       # both chunks of block 4
    assert np.asarray(be.verify_many(data, crcs, 512)).tolist() == [0] * 5
    assert np.asarray(be.verify_many(bad, crcs, 512)).tolist() == [
        0, 0, 0, 1, 2]


def _state():
    return {"w": jnp.arange(30_000, dtype=jnp.float32) * 0.25,
            "b": jnp.full((40, 50), -1.5, jnp.bfloat16)}


def _same(a, b) -> bool:
    return all(np.array_equal(np.asarray(a[k]), np.asarray(b[k]))
               for k in a)


def _manager(store=None, **kw):
    """UniLRC(1, 4): 12 data blocks, groups of 3 data, a global and a
    local parity, one group per cluster."""
    store = store or BlockStore(Topology(4, 6))
    kw.setdefault("checksum", "crc32c")
    return CheckpointManager(store, make_unilrc(1, 4), block_size=BLOCK,
                             **kw), store


def _group_of(mgr, block):
    return next(g for g in mgr.code.groups if block in g)


@pytest.mark.parametrize("backend", ["kernels", "numpy"])
@pytest.mark.parametrize("case", ["data", "fold_source", "unread_parity"])
def test_a_restore_rebuilds_what_it_finds_corrupt(case, backend,
                                                  kernel_counters):
    """A corrupt data block is folded from its group; a second corrupt
    block in the group, one of the fold's sources, makes it a decode on
    the MXU; a corrupt parity of a group that lost nothing is read by no
    one, so no restore sees it. Each restore is exact and reports just
    the corrupt blocks it read."""
    mgr, store = _manager(backend=backend)
    state = _state()
    mgr.save(state, step=1)
    sid = mgr.stripes_of(1)[0].stripe_id
    k = mgr.code.k
    group = _group_of(mgr, 1)
    corrupt = {"data": [1], "fold_source": [1, group[-1]],
               "unread_parity": [_group_of(mgr, k - 1)[-1]]}[case]
    for i, b in enumerate(corrupt):
        store.corrupt(sid, b, 512 * i, 512, 0x5A)
    kernel_counters.clear()
    back, report = mgr.restore(1)
    assert _same(state, back)
    assert set(report.corrupt_blocks) == {
        (sid, b) for b in corrupt if case != "unread_parity"}
    if backend == "kernels":
        assert kernel_counters["xor_reduce"] == (case == "data")
        # one CRC check of the data blocks of each stripe, one of the
        # sources a rebuild reads, and the decode
        decodes = kernel_counters["gf_bitmatmul"] - len(mgr.stripes_of(1))
        assert decodes == {"data": 1, "fold_source": 2,
                           "unread_parity": 0}[case]
    back, again = mgr.restore(1)             # the store was not rewritten
    assert _same(state, back) and again.corrupt_blocks == report.corrupt_blocks


def test_corruption_beyond_the_code_raises():
    mgr, store = _manager()
    mgr.save(_state(), step=1)
    sid = mgr.stripes_of(1)[0].stripe_id
    for b in _group_of(mgr, 0) + _group_of(mgr, 11)[:2]:
        store.corrupt(sid, b, 0, 1, 1)
    with pytest.raises(CorruptionError):
        mgr.restore(1)


def test_a_block_without_crcs_fails_its_check():
    mgr, store = _manager()
    state = _state()
    mgr.save(state, step=1)
    sid = mgr.stripes_of(1)[0].stripe_id
    store._crcs.pop((sid, 2))
    back, report = mgr.restore(1)
    assert _same(state, back) and report.corrupt_blocks == ((sid, 2),)


def test_a_disk_store_keeps_its_crcs_across_reopen(tmp_path):
    mgr, store = _manager(DiskBlockStore(Topology(4, 6), tmp_path))
    state = _state()
    mgr.save(state, step=1)
    sid = mgr.stripes_of(1)[0].stripe_id
    before = store.checksums([(sid, b) for b in range(mgr.code.n)])
    assert all(c is not None and len(c) == 4 * BLOCK // 512 for c in before)
    store.corrupt(sid, 4, 1000, 24, 0xFF)
    store.reopen()
    assert store.checksums([(sid, b) for b in range(mgr.code.n)]) == before
    back, report = mgr.restore(1)
    assert _same(state, back) and report.corrupt_blocks == ((sid, 4),)
    store.drop_block(sid, 4)
    assert not list(tmp_path.glob(f"node_*/s{sid:06d}_b0004*"))


def test_rebuilt_and_updated_blocks_get_fresh_crcs():
    mgr, store = _manager()
    state = _state()
    mgr.save(state, step=1)
    meta = mgr.stripes_of(1)[0]
    store.fail_node(store.node_of(meta.stripe_id, 0))
    assert mgr.reconstruct_failures() > 0
    back, report = mgr.restore(1)
    assert _same(state, back) and not report.corrupt_blocks
    new = bytes(range(256)) * (BLOCK // 256)
    mgr.codec.update_block(meta, 5, new)
    read, stats = mgr.codec.read_stripe(meta)
    assert read[5 * BLOCK:6 * BLOCK] == new and not stats.corrupt
    group = _group_of(mgr, 5)
    store.corrupt(meta.stripe_id, group[-2], 0, 8, 1)
    with pytest.raises(CorruptionError):      # no delta on corrupt bytes
        mgr.codec.update_block(meta, 5, bytes(BLOCK))


@pytest.mark.parametrize("backend", sorted(BACKENDS))
def test_two_updates_of_one_stripe_in_one_flush_keep_exact_parities(backend):
    """Two blocks of one group updated in one flush share their parities:
    the second wave patches what the first wrote, so every parity is the
    encode of the new data, under CRCs of its bytes, and a block dropped
    afterwards is rebuilt from them."""
    mgr, store = _manager(backend=backend)
    mgr.save(_state(), step=1)
    meta = mgr.stripes_of(1)[0]
    sid, code = meta.stripe_id, mgr.code
    group = _group_of(mgr, 0)
    rng = np.random.default_rng(2**31 + 17)
    new = {b: rng.integers(0, 256, BLOCK, dtype=np.uint8).tobytes()
           for b in group if b < code.k}
    engine = mgr.codec.engine
    handles = [engine.submit_update(sid, b, data) for b, data in new.items()]
    stats = engine.flush()
    assert stats.update_waves == len(new) and not stats.corrupt
    assert all(h.result() > 0 for h in handles)
    stored = np.stack([np.frombuffer(store.get(sid, b), np.uint8)
                       for b in range(code.n)])
    for b, data in new.items():
        assert stored[b].tobytes() == data
    fresh = NumpyBackend().encode_many(code, stored[None, :code.k])[0]
    assert np.array_equal(stored, fresh)
    crcs = store.checksums([(sid, b) for b in range(code.n)])
    assert [np.frombuffer(c, np.uint32).tolist() for c in crcs] == \
        crc32c_chunks(stored, 512).tolist()
    store.drop_block(sid, group[0])
    read, stats = mgr.codec.read_stripe(meta)
    assert read[:code.k * BLOCK] == stored[:code.k].tobytes()
    assert not stats.corrupt


class _Calls(KernelBackend):
    """The kernel backend, recording which methods a save calls."""

    def __init__(self):
        self.calls = []

    def encode_many_lazy(self, code, data):
        self.calls.append("encode_many_lazy")
        return super().encode_many_lazy(code, data)

    def checksum_many(self, blocks, nbytes):
        self.calls.append("checksum_many")
        return super().checksum_many(blocks, nbytes)


@pytest.mark.parametrize("checksum", [None, "crc32c"])
def test_checksums_off_save_makes_the_same_calls(checksum):
    backend = _Calls()
    mgr, store = _manager(backend=backend, checksum=checksum)
    mgr.save(_state(), step=1)
    windows = -(-len(mgr.stripes_of(1)) // mgr.codec.max_batch_stripes)
    per_window = ["encode_many_lazy"] + ["checksum_many"] * bool(checksum)
    assert backend.calls == per_window * windows
    pairs = [(m.stripe_id, b) for m in mgr.stripes_of(1)
             for b in range(mgr.code.n)]
    assert all((c is None) == (checksum is None)
               for c in store.checksums(pairs))


def test_the_checksum_setting_is_checked():
    with pytest.raises(ValueError):
        _manager(checksum="md5")
    with pytest.raises(ValueError):       # not whole 512-byte chunks
        CheckpointManager(BlockStore(Topology(4, 6)), make_unilrc(1, 4),
                          block_size=3000, checksum="crc32c")
