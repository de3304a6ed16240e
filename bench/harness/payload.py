"""What the cells store, made on the device from the seed, and the exact
comparisons of what comes back with it.

A train state is one chip's shard of a dense decoder: per layer the
attention and MLP weights (the config's `weight_dtype`), two norm scales
and, per optimizer moment, the same leaves again in `moment_dtype`, plus
an int32 step. All of it comes from one jitted call on the seed.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
import numpy as np

_UINT = {2: jnp.uint16, 4: jnp.uint32}


def layer_shapes(cfg: dict) -> dict[str, tuple[int, ...]]:
    d, ff, hd = cfg["hidden_size"], cfg["intermediate_size"], cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    return {"wq": (d, q), "wk": (d, kv), "wv": (d, kv), "wo": (q, d),
            "w_gate": (d, ff), "w_up": (d, ff), "w_down": (ff, d)}


def state_maker(cfg: dict):
    """A jitted `key -> train state` for the config."""
    shapes = layer_shapes(cfg)
    d = cfg["hidden_size"]
    wdt, mdt = jnp.dtype(cfg["weight_dtype"]), jnp.dtype(cfg["moment_dtype"])

    def layer(key, wdtype, ndtype):
        keys = jax.random.split(key, len(shapes) + 2)
        out = {name: 0.02 * jax.random.normal(k, s, wdtype)
               for k, (name, s) in zip(keys, shapes.items())}
        out["attn_norm"] = 0.02 * jax.random.normal(keys[-2], (d,), ndtype)
        out["mlp_norm"] = 0.02 * jax.random.normal(keys[-1], (d,), ndtype)
        return out

    @jax.jit
    def make(key):
        keys = jax.random.split(key, 2 + cfg["optimizer_moments"])
        layers = cfg["num_hidden_layers"]
        state = {"params": [layer(k, wdt, jnp.float32)
                            for k in jax.random.split(keys[0], layers)],
                 "step": jax.random.randint(keys[1], (), 0, 1 << 30)}
        for i in range(cfg["optimizer_moments"]):
            state[f"moment{i}"] = [layer(k, mdt, mdt) for k in
                                   jax.random.split(keys[2 + i], layers)]
        return state
    return make


def _as_uint(x):
    return jax.lax.bitcast_convert_type(x, _UINT[x.dtype.itemsize])


@jax.jit
def perturb(state, c):
    """The state with the lowest byte of every element XORed with the
    uint8 `c`: the next save's state, as a training step changes the low
    bits of every value. A bfloat16's low byte holds its exponent's lowest
    bit, so an exponent moves by one at the most: values near the state's
    0.02 stay normal, and none becomes subnormal (which the TPU flushes in
    a bfloat16 result), infinite or NaN."""
    def leaf(x):
        u = _as_uint(x)
        return jax.lax.bitcast_convert_type(u ^ c.astype(u.dtype), x.dtype)
    return jax.tree_util.tree_map(leaf, state)


def low_bytes(tree, size: int) -> np.ndarray:
    """1 where a byte of `flat_bytes(tree, size)` is the lowest byte of an
    element (little-endian), the bytes `perturb` changes; else 0."""
    leaves = jax.tree_util.tree_leaves(tree)
    out = np.zeros(size, np.uint8)
    off = 0
    for x in leaves:
        out[off:off + x.nbytes:x.dtype.itemsize] = 1
        off += x.nbytes
    return out


@jax.jit
def mismatched_words(expect, got):
    """Per leaf, how many elements differ bit for bit."""
    return [jnp.sum(_as_uint(a) != _as_uint(b), dtype=jnp.int32)
            for a, b in zip(jax.tree_util.tree_leaves(expect),
                            jax.tree_util.tree_leaves(got))]


def flat_bytes(tree, size: int) -> np.ndarray:
    """The leaves, in flatten order, as one host byte array zero-padded to
    `size`: the layout the store is expected to hold a saved state in."""
    leaves = jax.tree_util.tree_leaves(tree)
    out = np.zeros(size, np.uint8)
    off = 0
    for x in leaves:
        b = np.asarray(x).reshape(-1).view(np.uint8)
        out[off:off + b.size] = b
        off += b.size
    return out
