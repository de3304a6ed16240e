"""Program spans and host<->device byte counters (`repro.obs`): a tiny
checkpoint save and degraded restore, profiled on the CPU, leave every
span in the trace, and the counters read exactly the bytes worked out
from the shapes; so do a checksummed save and a restore that finds a
corrupt block."""
import glob
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro import obs
from repro.ckpt import BlockStore, CheckpointManager
from repro.core.codes import make_unilrc
from repro.topo import Topology

BLOCK = 1 << 16
SOURCES = 6         # a UniLRC(1, 6) block's local group holds 6 others

SPANS = {"repro.ckpt.save", "repro.ckpt.restore", "repro.ckpt.serialize",
         "repro.ckpt.assemble", "repro.stripe.write_stream",
         "repro.stripe.read", "repro.engine.flush",
         "repro.engine.encode_dispatch", "repro.engine.encode_wait",
         "repro.engine.land", "repro.engine.recover",
         "repro.backend.encode_many_lazy", "repro.backend.recover_many",
         "repro.store.get_many", "repro.store.put_many"}


def _state():
    return {"w": jnp.arange(600_000, dtype=jnp.float32) * 0.5,
            "b": jnp.ones((300, 1000), jnp.bfloat16),
            "step": jnp.int32(7)}


@pytest.fixture(scope="module")
def profiled(tmp_path_factory):
    """A 30-of-42 save of two stripes at 64 KiB blocks, by a manager that
    takes the CPU for an accelerator, so by the device path, then a
    restore with the node holding stripe 0's block 0 down, inside one
    trace; after it, the same state saved by a manager that keeps CPU
    states on the host path; the transfer counters' deltas over each; the
    managers and the first one's store."""
    code = make_unilrc(1, 6)
    store = BlockStore(Topology(6, 8))
    mgr = CheckpointManager(store, code, block_size=BLOCK,
                            host_platforms=frozenset())
    host_mgr = CheckpointManager(BlockStore(Topology(6, 8)), code,
                                 block_size=BLOCK)
    state = _state()
    jax.block_until_ready(state)
    log_dir = str(tmp_path_factory.mktemp("trace"))
    with jax.profiler.trace(log_dir):
        x0 = obs.snapshot()
        mgr.save(state, step=3)
        x1 = obs.snapshot()
        store.fail_node(store.node_of(mgr.stripes_of(3)[0].stripe_id, 0))
        back, report = mgr.restore(3)
        x2 = obs.snapshot()
    host_mgr.save(state, step=4)
    x3 = obs.snapshot()
    path, = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    deltas = [{d: b[d] - a[d] for d in a}
              for a, b in ((x0, x1), (x1, x2), (x2, x3))]
    return dict(data=ProfileData.from_file(path), deltas=deltas,
                mgr=mgr, host_mgr=host_mgr, store=store, code=code,
                state=state, back=back, report=report)


def test_every_span_is_in_the_trace(profiled):
    names = {ev.name for plane in profiled["data"].planes
             for line in plane.lines for ev in line.events
             if ev.name.startswith("repro.")}
    assert SPANS <= names, SPANS - names


def test_a_span_carries_its_attributes(profiled):
    stats = [dict(ev.stats) for plane in profiled["data"].planes
             for line in plane.lines for ev in line.events
             if ev.name in ("repro.ckpt.save", "repro.ckpt.restore")]
    assert [s["step"] for s in stats] == [3, 3]


def test_the_restore_was_degraded_and_exact(profiled):
    assert profiled["report"].degraded
    for a, b in zip(jax.tree_util.tree_leaves(profiled["state"]),
                    jax.tree_util.tree_leaves(profiled["back"])):
        assert np.array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("path", ["device", "host"])
def test_save_transfers_are_the_bytes_of_the_shapes(profiled, path):
    """A state on the device is striped and encoded there: only the
    codewords come home. By the host path the leaves come to the host,
    the stripes go to the device and the codewords come back."""
    code = profiled["code"]
    leaves = sum(x.nbytes for x in jax.tree_util.tree_leaves(
        profiled["state"]))
    mgr, step, delta = {"device": ("mgr", 3, 0),
                        "host": ("host_mgr", 4, 2)}[path]
    stripes = len(profiled[mgr].stripes_of(step))
    assert stripes == -(-leaves // (code.k * BLOCK)) == 2
    expect = {"to_device": 0, "from_device": stripes * code.n * BLOCK}
    if path == "host":
        expect = {"to_device": stripes * code.k * BLOCK,
                  "from_device": leaves + stripes * code.n * BLOCK}
    assert profiled["deltas"][delta] == expect


def test_restore_transfers_are_the_bytes_of_the_shapes(profiled):
    """Each lost data block: its group's other blocks to the device, the
    block back."""
    store, code = profiled["store"], profiled["code"]
    lost = sum(not store.available(m.stripe_id, b)
               for m in profiled["mgr"].stripes_of(3)
               for b in range(code.k))
    assert lost == profiled["report"].degraded_blocks > 0
    assert profiled["deltas"][1] == {"to_device": lost * SOURCES * BLOCK,
                                     "from_device": lost * BLOCK}


def test_counters_count_only_host_device_copies():
    before = obs.snapshot()
    on_device = obs.to_device(np.zeros((3, 5), np.uint16))
    assert obs.to_device(on_device) is on_device
    back = obs.to_host(on_device)
    obs.to_host(back)
    after = obs.snapshot()
    assert after["to_device"] - before["to_device"] == 30
    assert after["from_device"] - before["from_device"] == 30


def test_counters_lose_no_update_across_threads():
    """Threads copying at once, switching every microsecond: the totals
    are the sum of every copy."""
    block = np.zeros(64, np.uint8)
    before = obs.snapshot()["to_device"]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        workers = [threading.Thread(
            target=lambda: [obs.to_device(block) for _ in range(200)])
            for _ in range(16)]
        for w in workers:
            w.start()
        for w in workers:
            w.join(timeout=60)
        assert not any(w.is_alive() for w in workers)
    finally:
        sys.setswitchinterval(interval)
    assert obs.snapshot()["to_device"] - before == 16 * 200 * 64


@pytest.fixture(scope="module")
def checked(tmp_path_factory):
    """The same save by a manager that keeps CRC32C per 512 bytes, by the
    device path and by the host path, then a restore with one data block
    corrupt, inside one trace; the checksum counters' deltas over each."""
    code = make_unilrc(1, 6)
    mgrs = {"device": CheckpointManager(
        BlockStore(Topology(6, 8)), code, block_size=BLOCK,
        checksum="crc32c", host_platforms=frozenset()),
        "host": CheckpointManager(BlockStore(Topology(6, 8)), code,
                                  block_size=BLOCK, checksum="crc32c")}
    state = _state()
    jax.block_until_ready(state)
    mgr = mgrs["device"]
    log_dir = str(tmp_path_factory.mktemp("trace"))
    with jax.profiler.trace(log_dir):
        c0 = obs.checksum_snapshot()
        mgr.save(state, step=3)
        c1 = obs.checksum_snapshot()
        mgrs["host"].save(state, step=3)
        c2 = obs.checksum_snapshot()
        sid = mgr.stripes_of(3)[0].stripe_id
        mgr.store.corrupt(sid, 2, 0, BLOCK, 0x81)
        back, report = mgr.restore(3)
        c3 = obs.checksum_snapshot()
    path, = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    deltas = [{d: b[d] - a[d] for d in a}
              for a, b in ((c0, c1), (c1, c2), (c2, c3))]
    return dict(data=ProfileData.from_file(path), deltas=deltas, mgr=mgr,
                code=code, state=state, back=back, report=report, sid=sid)


def _events(data, name):
    return [dict(ev.stats) for plane in data.planes for line in plane.lines
            for ev in line.events if ev.name == name]


@pytest.mark.parametrize("path", ["device", "host"])
def test_a_save_makes_a_crc_per_chunk(checked, path):
    """Every block of every stripe gets its CRCs, on either path."""
    code = checked["code"]
    stripes = len(checked["mgr"].stripes_of(3))
    delta = checked["deltas"][{"device": 0, "host": 1}[path]]
    assert delta == {"chunks_made": stripes * code.n * BLOCK // 512,
                     "bytes_verified": 0, "corrupt_chunks": 0,
                     "corrupt_blocks": 0}


def test_a_restore_checks_each_block_it_reads_once(checked):
    """The data blocks of every stripe, and the corrupt block's group's
    two parities, which the fold reads besides its data: each checked
    once; the corrupt block's every chunk fails."""
    code, mgr = checked["code"], checked["mgr"]
    stripes = len(mgr.stripes_of(3))
    assert checked["report"].corrupt_blocks == ((checked["sid"], 2),)
    for a, b in zip(jax.tree_util.tree_leaves(checked["state"]),
                    jax.tree_util.tree_leaves(checked["back"])):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert checked["deltas"][2] == {
        "chunks_made": 0,
        "bytes_verified": (stripes * code.k + 2) * BLOCK,
        "corrupt_chunks": BLOCK // 512, "corrupt_blocks": 1}


def test_the_checksum_spans_carry_their_counts(checked):
    code = checked["code"]
    stripes = len(checked["mgr"].stripes_of(3))
    made = _events(checked["data"], "repro.engine.checksum")
    assert sum(s["blocks"] for s in made) == 2 * stripes * code.n
    assert sum(s["chunks"] for s in made) == 2 * stripes * code.n * 128
    verify = _events(checked["data"], "repro.engine.verify")
    assert sum(s["blocks"] for s in verify) == stripes * code.k + 2
    assert sum(s["chunks"] for s in verify) == (stripes * code.k + 2) * 128
    assert sum(s["corrupt"] for s in verify) == 1
