"""Program spans and host<->device byte counters (`repro.obs`): a tiny
checkpoint save and degraded restore, profiled on the CPU, leave every
span in the trace, and the counters read exactly the bytes worked out
from the shapes."""
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
    """A 30-of-42 save of two stripes at 64 KiB blocks, then a restore
    with the node holding stripe 0's block 0 down, inside one trace;
    the transfer counters' deltas over each; the manager and its store."""
    code = make_unilrc(1, 6)
    store = BlockStore(Topology(6, 8))
    mgr = CheckpointManager(store, code, block_size=BLOCK)
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
    path, = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    deltas = [{d: b[d] - a[d] for d in a} for a, b in ((x0, x1), (x1, x2))]
    return dict(data=ProfileData.from_file(path), deltas=deltas,
                mgr=mgr, store=store, code=code, state=state, back=back,
                report=report)


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


def test_save_transfers_are_the_bytes_of_the_shapes(profiled):
    code = profiled["code"]
    leaves = sum(x.nbytes for x in jax.tree_util.tree_leaves(
        profiled["state"]))
    stripes = len(profiled["mgr"].stripes_of(3))
    assert stripes == -(-leaves // (code.k * BLOCK)) == 2
    assert profiled["deltas"][0] == {
        "to_device": stripes * code.k * BLOCK,
        "from_device": leaves + stripes * code.n * BLOCK}


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
