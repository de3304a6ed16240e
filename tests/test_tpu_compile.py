"""Compile the coding kernels for a TPU v5e that is described, not attached.

The TPU compiler is installed with JAX, and it compiles for a chip from a
topology description alone. These tests compile the kernels of the store's
main path at real widths: 1 MiB blocks, the lane tiles the autotune
planner picks, the paper's 180-of-210 and 30-of-42 UniLRC codes. A compile
refused here (an unsupported cast, a block shape off the (8, 128) tiling,
too much VMEM) would be refused on the chip. Nothing runs, so these say
nothing about results or times; the interpret-mode tests check the bytes.

The topology is described inside a module fixture, never on import: only
one process may load the TPU library, and every pytest-xdist worker
imports every test module.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.codec import decode_plan
from repro.core.codes import make_unilrc
from repro.kernels import autotune, ops
from repro.kernels.gf_bitmatmul import gf_bitmatmul, gf_bitmatmul_batched

BLOCK = 1 << 20                          # 1 MiB blocks
V5E_HBM = 16 << 30                       # bytes of HBM on one v5e chip
CODES = {"180-of-210": (2, 10), "30-of-42": (1, 6)}


@pytest.fixture(scope="module")
def one_chip():
    mp = pytest.MonkeyPatch()
    mp.setenv("TPU_LOG_DIR", "disabled")   # else the compiler logs to /tmp
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                # any cause skips
        mp.undo()
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    mp.undo()


def _compile(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("batched", [False, True], ids=["single", "batched"])
@pytest.mark.parametrize("scheme", sorted(CODES))
def test_gf_bitmatmul_compiles(one_chip, scheme, batched):
    code = make_unilrc(*CODES[scheme])
    k, m = code.k, code.n - code.k
    bb = autotune.plan_matmul_tiles(k, m, BLOCK).block_b
    kernel = gf_bitmatmul_batched if batched else gf_bitmatmul
    data = (3, k, BLOCK) if batched else (k, BLOCK)
    compiled = _compile(
        lambda a, x: kernel(a, x, block_b=bb, interpret=False),
        _spec((8 * m, 8 * k), jnp.uint8, one_chip),
        _spec(data, jnp.uint8, one_chip))
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("scheme", sorted(CODES))
def test_two_erasure_decode_compiles(one_chip, scheme):
    """The multi-erasure restore path: a (2, k) decode matrix over a
    batch of stripes, through the same wrapper the engine calls."""
    code = make_unilrc(*CODES[scheme])
    plan = decode_plan(code, (0, 1))
    compiled = _compile(
        lambda x: ops.apply_matrix_many(plan.M, x, interpret=False),
        _spec((3, len(plan.sources), BLOCK), jnp.uint8, one_chip))
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("batched", [False, True], ids=["single", "batched"])
@pytest.mark.parametrize("scheme", sorted(CODES))
def test_xor_fold_compiles(one_chip, scheme, batched):
    """Single-failure repair: the XOR fold of one local group's bytes,
    s = 20 sources for 180-of-210 and s = 6 for 30-of-42, through the
    wrappers the engine calls."""
    code = make_unilrc(*CODES[scheme])
    s = len(code.groups[0]) - 1
    fold = ops.xor_fold_many if batched else ops.xor_fold
    shape = (3, s, BLOCK) if batched else (s, BLOCK)
    compiled = _compile(lambda x: fold(x, interpret=False),
                        _spec(shape, jnp.uint8, one_chip))
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("scheme", sorted(CODES))
def test_two_encode_windows_fit_in_hbm(one_chip, scheme):
    """The streamed write keeps two encode windows live; at the default
    window for 1 MiB blocks both must fit one chip's HBM by the
    compiler's own memory analysis."""
    code = make_unilrc(*CODES[scheme])
    window = autotune.plan_stream_windows(code.k, code.n, BLOCK)
    compiled = _compile(
        lambda x: ops.encode_many(code, x, interpret=False),
        _spec((window, code.k, BLOCK), jnp.uint8, one_chip))
    mem = compiled.memory_analysis()
    per_window = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                  + mem.temp_size_in_bytes)
    assert per_window >= window * code.n * BLOCK       # the codewords
    # half the HBM at most: the rest holds the state being saved
    assert 2 * per_window < V5E_HBM // 2


def test_a_device_save_of_more_than_half_the_hbm_fits(one_chip):
    """A save's device path for a 9.7 GB state, more than half of a v5e's
    HBM: Phi-4-mini's embedding (bf16, and an f32 moment of 2.46 GB, one
    leaf past 2^31 bytes) and 6 layers of bf16 weights with both of
    Adam's f32 moments. Each encode window is laid out from the leaves
    on its own, so the save needs the state plus O(window): two windows
    of stripes and of codewords, and one flatten's temporaries. Compiled
    for the window with the most row blocks and for one inside the
    large leaf."""
    from repro.ckpt import serialize
    d, ff, q, kv, vocab = 3072, 8192, 3072, 1024, 200_064
    mats = [(d, q), (d, kv), (d, kv), (q, d), (d, ff), (d, ff), (ff, d)]
    state = {"embed": _spec((vocab, d), jnp.bfloat16, one_chip),
             "embed_mu": _spec((vocab, d), jnp.float32, one_chip)}
    for layer in range(6):
        for i, shape in enumerate(mats):
            state[f"{layer}.{i}"] = _spec(shape, jnp.bfloat16, one_chip)
            state[f"{layer}.{i}.mu"] = _spec(shape, jnp.float32, one_chip)
            state[f"{layer}.{i}.nu"] = _spec(shape, jnp.float32, one_chip)
    code = make_unilrc(*CODES["180-of-210"])
    window = autotune.plan_stream_windows(code.k, code.n, BLOCK)
    stripes, manifest, _ = serialize.DeviceStripes.of(state, code.k, BLOCK)
    total = manifest.total_bytes
    assert total > V5E_HBM // 2
    assert max(e[4] for e in manifest.entries) > serialize.FLAT_LIMIT
    plans = {w0: stripes.plan(w0, window)
             for w0 in range(0, stripes.stripes - window + 1, window)}
    busiest = max(plans, key=lambda w0: sum(len(blocks) for _pos, blocks
                                            in plans[w0][1]))
    embed_mu = next(w0 for w0, (leaves, _) in plans.items()
                    if len(leaves) == 1)
    flatten = 0
    for w0 in (busiest, embed_mu):
        leaves, plan = plans[w0]
        mem = serialize.flatten_window.lower(
            leaves, plan=plan, stripes=window, k=code.k,
            block_size=BLOCK).compile().memory_analysis()
        assert mem.output_size_in_bytes >= window * code.k * BLOCK
        flatten = max(flatten, mem.output_size_in_bytes
                      + mem.temp_size_in_bytes)
    encode = _compile(lambda x: ops.encode_many(code, x, interpret=False),
                      _spec((window, code.k, BLOCK), jnp.uint8, one_chip)
                      ).memory_analysis()
    per_window = (encode.argument_size_in_bytes
                  + encode.output_size_in_bytes + encode.temp_size_in_bytes)
    assert total + 2 * (flatten + per_window) < V5E_HBM


@pytest.mark.parametrize("blocks", [(180,), (3, 210)],
                         ids=["verify", "save_window"])
def test_the_crc_compiles(one_chip, blocks):
    """CRC32C per 512 bytes of 1 MiB blocks: a stripe's data blocks on a
    verified read, and a 180-of-210 save window's codewords. The chunk
    transpose, the kernel over one column per chunk and the CRC words are
    separate programs, as `ops.checksum_many` dispatches them; a stripe's
    blocks are laid out in one program each."""
    n = blocks[-1]
    lanes = n * BLOCK // 512
    cols = ops._chunk_columns.lower(
        _spec((n, BLOCK), jnp.uint8, one_chip), nbytes=512).compile()
    assert cols.memory_analysis().output_size_in_bytes == n * BLOCK
    lanes *= blocks[0] if len(blocks) > 1 else 1
    kernel = _compile(
        lambda a, x: ops._bitmatmul_many(a, x, block_b=None,
                                         interpret=False),
        _spec((32, 8 * 512), jnp.uint8, one_chip),
        _spec((1, 512, lanes), jnp.uint8, one_chip))
    assert "tpu_custom_call" in kernel.as_text()
    ops._crc_words.lower(_spec((1, 4, lanes), jnp.uint8, one_chip),
                         _spec((), jnp.uint32, one_chip),
                         shape=(*blocks, BLOCK // 512)).compile()
