"""Pallas TPU kernel: flash attention forward (online softmax in VMEM).

Why this kernel exists (roofline-driven, EXPERIMENTS.md §Perf it. 6): the
pure-jnp blockwise attention keeps the numerics right but XLA materialises
every (qc × kc) score block to HBM between the QK^T dot and the PV dot —
measured ~1.4 TB/device of f32 block traffic on llama3-3b train_4k. On
TPU the fix is structural: keep the block, the running max m, and the
running sum l resident in VMEM across the KV sweep. That is exactly a
Pallas grid with a sequential final axis and VMEM scratch.

Grid: (B · Hq, n_q, n_kv) — the last axis is sequential on TPU, so the
(m, l, acc) scratch carries across KV steps of one (head, q-block)
program. GQA is handled by the K/V index maps (q head h reads kv head
h // G). Causal/windowed masking via broadcasted iota; fully-masked
(q-block, kv-block) pairs are skipped with pl.when — the grid-level
analogue of the `bounded` schedule.

Block sizes: q/kv blocks default 512×128-aligned; dk, dv assumed lane
aligned (128 here: pad heads upstream if not — the model layer guarantees
it). VMEM budget per program at defaults (bf16 io):
  q 512·128·2 = 128 KiB, k/v 2·512·128·2 = 256 KiB,
  p 512·512·4 = 1 MiB, acc 512·128·4 = 256 KiB, m/l 2·512·4·128 = 512 KiB
  ≈ 2.2 MiB — far under the ~16 MiB/core budget, leaving room for
  double-buffered HBM→VMEM prefetch of the next K/V block.

The backward pass reuses the jnp blockwise implementation (custom VJP in
models/layers.py); a bwd kernel is the natural next step but fwd is where
serving lives. Validated against kernels/ref.py in interpret mode across
shapes/dtypes/masks (tests/test_kernels.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 512
NEG_INF = -1e30


def _flash_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr,
                      acc_scr, *,
                      scale: float, causal: bool, window: int,
                      block_q: int, block_k: int, nk: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * block_q
    k_start = ki * block_k

    # skip blocks fully outside the causal triangle / window
    live = True
    if causal:
        live = k_start <= q_start + block_q - 1
    if window:
        live = jnp.logical_and(
            live, k_start + block_k - 1 > q_start - window) \
            if causal else (k_start + block_k - 1 > q_start - window)

    @pl.when(live)
    def _step():
        q = q_ref[0].astype(jnp.float32)                  # (bq, dk)
        k = k_ref[0].astype(jnp.float32)                  # (bk, dk)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (bq, bk)

        if causal or window:
            qpos = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            kpos = k_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            mask = jnp.ones((block_q, block_k), jnp.bool_)
            if causal:
                mask &= qpos >= kpos
            if window:
                mask &= qpos - kpos < window
            s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[:, 0]                              # (bq,)
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        p = jnp.exp(s - m_new[:, None])
        p = jnp.where(s <= NEG_INF / 2, 0.0, p)
        corr = jnp.exp(m_prev - m_new)
        corr = jnp.where(m_prev <= NEG_INF / 2, 0.0, corr)
        l_scr[:, 0] = l_scr[:, 0] * corr + p.sum(axis=-1)
        m_scr[:, 0] = m_new
        v = v_ref[0].astype(jnp.float32)                  # (bk, dv)
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * corr[:, None] + pv

    @pl.when(ki == nk - 1)
    def _finish():
        l = l_scr[:, 0]
        o_ref[0] = (acc_scr[...] /
                    jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)
        m = m_scr[:, 0]
        lse_ref[0] = jnp.where(l > 0, m + jnp.log(jnp.maximum(l, 1e-30)),
                               -jnp.inf)


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "block_q", "block_k",
                              "interpret"))
def flash_attention_fwd(q, k, v, *, causal: bool = True, window: int = 0,
                        block_q: int = DEFAULT_BLOCK_Q,
                        block_k: int = DEFAULT_BLOCK_K,
                        interpret: bool = True):
    """q: (B, Hq, Sq, dk); k/v: (B, Hkv, Skv, dk/dv) -> (B, Hq, Sq, dv).

    Sq must divide by block_q, Skv by block_k (callers pad); Hq % Hkv == 0.
    """
    B, Hq, Sq, dk = q.shape
    Hkv, Skv, dv = k.shape[1], k.shape[2], v.shape[-1]
    G = Hq // Hkv
    bq = min(block_q, Sq)
    bk = min(block_k, Skv)
    assert Sq % bq == 0 and Skv % bk == 0, (Sq, bq, Skv, bk)
    nq, nk = Sq // bq, Skv // bk
    scale = dk ** -0.5

    qf = q.reshape(B * Hq, Sq, dk)
    kf = k.reshape(B * Hkv, Skv, dk)
    vf = v.reshape(B * Hkv, Skv, dv)

    kernel = functools.partial(
        _flash_fwd_kernel, scale=scale, causal=causal, window=window,
        block_q=bq, block_k=bk, nk=nk)

    scratch = [pltpu.VMEM((bq, 128), jnp.float32),
               pltpu.VMEM((bq, 128), jnp.float32),
               pltpu.VMEM((bq, dv), jnp.float32)]

    out, lse = pl.pallas_call(
        kernel,
        grid=(B * Hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, dk), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, bk, dk),
                         lambda b, qi, ki, G=G: (b // G, ki, 0)),
            pl.BlockSpec((1, bk, dv),
                         lambda b, qi, ki, G=G: (b // G, ki, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, dv), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, bq), lambda b, qi, ki: (b, qi)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * Hq, Sq, dv), q.dtype),
            jax.ShapeDtypeStruct((B * Hq, Sq), jnp.float32),
        ],
        scratch_shapes=scratch,
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(B, Hq, Sq, dv), lse.reshape(B, Hq, Sq)
