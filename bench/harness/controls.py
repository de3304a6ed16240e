"""Controls: backends put in the program's place that break the guarantee
a cell's check holds it to, so the check is shown to fail.

  bf16_accumulate  the plain reference's bit-plane GF(2^8) product with its
                   MXU sums rounded to bfloat16, the precision below the
                   kernel's float32 accumulator: sums of up to 8k = 1440
                   bits lose their parity above 256. Encode and
                   multi-erasure decode (the 180-of-210 cells). The sums
                   are made exactly in float32 and then rounded by
                   `lax.reduce_precision`: a bfloat16 result type alone is
                   not enough, since XLA may keep the excess precision.
  drop_source      every XOR fold leaves out its last source. XOR has no
                   precision to lower, so this control breaks bit-exact
                   repair instead (the cells whose decode is the fold).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import reference

_product = functools.partial(jax.jit, static_argnames="mantissa_bits")(
    reference.bitplane_product)


def gf_product(A: np.ndarray, data: np.ndarray, mantissa_bits: int = 7
               ) -> np.ndarray:
    """A (m, k) @ data (k, B) over GF(2^8), bit-plane, its sums rounded to
    `mantissa_bits` (7: bfloat16; 23: float32, exact)."""
    a_bits = jnp.asarray(reference.bit_matrix(A))
    B, step = data.shape[1], reference.CHUNK
    out = [np.asarray(_product(a_bits, jnp.asarray(data[:, c:c + step]),
                               mantissa_bits)) for c in range(0, B, step)]
    return np.concatenate(out, axis=1)


def _backends():
    from repro.io.backend import KernelBackend

    class Bf16Accumulate(KernelBackend):
        name = "bf16_accumulate"

        def encode_many(self, code, data):
            return np.stack([np.concatenate([d, gf_product(code.A, d)])
                             for d in data])

        def encode_many_lazy(self, code, data):
            return self.encode_many(code, data)

        def apply_decode_many(self, plan, stacked):
            src = np.stack([stacked[s] for s in plan.sources], axis=1)
            rec = np.stack([gf_product(plan.M, x) for x in src])
            return {e: rec[:, i] for i, e in enumerate(plan.erased)}

    class DropSource(KernelBackend):
        name = "drop_source"

        def recover_many(self, plan, stacked):
            if not plan.xor_only:
                return super().recover_many(plan, stacked)
            return np.bitwise_xor.reduce(
                [stacked[s] for s in plan.sources[:-1]])

        def xor_fold_many(self, stacked):
            return np.bitwise_xor.reduce(stacked[:, :-1], axis=1)

    return {"bf16_accumulate": Bf16Accumulate, "drop_source": DropSource}


def control(name: str):
    """A fresh control backend by name."""
    return _backends()[name]()
