"""Execution backends for the coding byte path.

One interface, two implementations:

  * `KernelBackend` — the JAX/Pallas kernels (kernels/ops.py): MXU
    bit-plane GF matmul for encode/decode, VPU XOR fold for XOR-only
    recovery plans. Every call is ONE kernel launch (the stripe-batched
    wrappers), counted in `ops.KERNEL_LAUNCHES`.
  * `NumpyBackend` — the host-side GF oracle (core.gf / plan.apply).
    Byte-identical outputs, zero kernel launches; what `use_kernels=False`
    used to select via if/else scattered through `ckpt/stripe.py`.

The `CodingEngine` (engine.py) is backend-agnostic: it groups op
descriptors into batches and hands each batch to exactly one backend
call, so "which device executes the bytes" is a constructor argument,
not a branch on every code path. All inputs/outputs are host numpy
uint8 arrays; the kernel backend owns the device round-trip.
"""
from __future__ import annotations

import abc

import numpy as np

from repro import obs
from repro.core.codec import DecodePlan, RecoveryPlan
from repro.core.codes import Code
from repro.core.gf import gf_matmul


class Backend(abc.ABC):
    """Executes batched coding math on (S, ...) uint8 stripe batches."""

    name: str = "abstract"
    uses_kernels: bool = False

    @abc.abstractmethod
    def encode_many(self, code: Code, data: np.ndarray) -> np.ndarray:
        """(S, k, B) data -> (S, n, B) codewords."""

    def encode_many_lazy(self, code: Code, data: np.ndarray):
        """Dispatch an encode WITHOUT forcing the result to host memory.

        Returns an opaque array-like the caller forces with
        `np.asarray(...)` when it actually needs the bytes. The kernel
        backend overrides this to return the un-forced jax array — its
        async dispatch is what lets the streaming checkpoint writer
        launch window w+1's encode while window w's codewords land in
        the store. The default (host backends) is simply eager: the
        result already IS host memory."""
        return self.encode_many(code, data)

    @abc.abstractmethod
    def recover_many(self, plan: RecoveryPlan,
                     stacked: dict[int, np.ndarray]) -> np.ndarray:
        """One single-failure plan over S stripes: {src: (S, B)} -> (S, B)."""

    @abc.abstractmethod
    def apply_decode_many(self, plan: DecodePlan,
                          stacked: dict[int, np.ndarray]
                          ) -> dict[int, np.ndarray]:
        """One multi-erasure plan over S stripes:
        {src: (S, B)} -> {erased: (S, B)}."""

    @abc.abstractmethod
    def delta_terms(self, M: np.ndarray, deltas: np.ndarray) -> np.ndarray:
        """GF(2^8) matmul M (m, u) @ deltas (u, B) -> (m, B): the parity
        delta terms of a batch of partial updates (one column per update,
        one row per touched parity term)."""

    @abc.abstractmethod
    def xor_fold_many(self, stacked: np.ndarray) -> np.ndarray:
        """(S, s, B) uint8 -> (S, B) XOR fold along axis 1 — the gateway
        pre-fold primitive (each remote cluster folds its XOR-linear
        contribution before it ships) and the final combine of folded
        partials at the reader."""


class KernelBackend(Backend):
    """JAX/Pallas execution: one kernel launch per batched call.

    Each method copies its host inputs to the device once, and its forced
    results back, through `obs.to_device` / `obs.to_host`, which count the
    bytes; each runs in a `repro.backend.<method>` span."""

    name = "kernels"
    uses_kernels = True

    def encode_many(self, code, data):
        S, k, B = data.shape
        with obs.span("backend.encode_many", to_device=data.nbytes,
                      from_device=S * code.n * B):
            return obs.to_host(self.encode_many_lazy(code, data))

    def encode_many_lazy(self, code, data):
        from repro.kernels import ops
        with obs.span("backend.encode_many_lazy", to_device=data.nbytes,
                      from_device=0):
            # un-forced jax array
            return ops.encode_many(code, obs.to_device(data))

    def recover_many(self, plan, stacked):
        from repro.kernels import ops
        S, B = stacked[plan.sources[0]].shape
        with obs.span("backend.recover_many",
                      to_device=len(plan.sources) * S * B,
                      from_device=S * B):
            return obs.to_host(ops.recover_many(
                plan, _on_device(stacked, plan.sources)))

    def apply_decode_many(self, plan, stacked):
        from repro.kernels import ops
        if not plan.erased:
            return {}
        S, B = stacked[plan.sources[0]].shape
        with obs.span("backend.apply_decode_many",
                      to_device=len(plan.sources) * S * B,
                      from_device=len(plan.erased) * S * B):
            rec = ops.apply_decode_many(plan,
                                        _on_device(stacked, plan.sources))
            return {e: obs.to_host(v) for e, v in rec.items()}

    def delta_terms(self, M, deltas):
        from repro.kernels import ops
        with obs.span("backend.delta_terms", to_device=deltas.nbytes,
                      from_device=M.shape[0] * deltas.shape[1]):
            return obs.to_host(ops.apply_matrix(M, obs.to_device(deltas)))

    def xor_fold_many(self, stacked):
        from repro.kernels import ops
        S, _s, B = stacked.shape
        with obs.span("backend.xor_fold_many", to_device=stacked.nbytes,
                      from_device=S * B):
            return obs.to_host(ops.xor_fold_many(obs.to_device(stacked)))


def _on_device(stacked: dict[int, np.ndarray], sources) -> dict:
    """{source: (S, B)} of the plan's sources, copied to the device."""
    return {s: obs.to_device(stacked[s]) for s in sources}


class NumpyBackend(Backend):
    """Host GF oracle: byte-identical to the kernels, zero launches."""

    name = "numpy"
    uses_kernels = False

    def encode_many(self, code, data):
        S, k, bs = data.shape
        flat = np.ascontiguousarray(data.transpose(1, 0, 2)).reshape(k, -1)
        cw = code.encode(flat)                              # (n, S*bs)
        return cw.reshape(code.n, S, bs).transpose(1, 0, 2)

    def recover_many(self, plan, stacked):
        return plan.apply(stacked)          # broadcasts over (S, B)

    def apply_decode_many(self, plan, stacked):
        return plan.apply(stacked)

    def delta_terms(self, M, deltas):
        return gf_matmul(np.ascontiguousarray(M, dtype=np.uint8),
                         np.ascontiguousarray(deltas, dtype=np.uint8))

    def xor_fold_many(self, stacked):
        out = np.zeros((stacked.shape[0], stacked.shape[2]), dtype=np.uint8)
        for i in range(stacked.shape[1]):
            out ^= stacked[:, i]
        return out


#: Registry behind the string spelling of `backend=`. Constructors are
#: stateless, so a fresh instance per resolve is fine.
BACKENDS: dict[str, type[Backend]] = {
    KernelBackend.name: KernelBackend,
    NumpyBackend.name: NumpyBackend,
}


def resolve_backend(backend: Backend | str | None = None, *,
                    use_kernels: bool | None = None) -> Backend:
    """The one place a backend spec becomes a `Backend`.

    `backend` is the primary API: a `Backend` instance, a registry name
    ("kernels" / "numpy"), or None for the default (kernels). The
    legacy `use_kernels` bool is a deprecation-warned shim — public
    constructors (`StripeCodec`, `CheckpointManager`) route it here so
    the warning and the mapping live in exactly one place.
    """
    if use_kernels is not None:
        import warnings
        warnings.warn(
            "use_kernels= is deprecated; pass backend='kernels' or "
            "backend='numpy' (or a Backend instance) instead",
            DeprecationWarning, stacklevel=3)
        if backend is not None:
            raise TypeError("pass backend= or use_kernels=, not both")
        return KernelBackend() if use_kernels else NumpyBackend()
    if backend is None:
        return KernelBackend()
    if isinstance(backend, Backend):
        return backend
    if isinstance(backend, str):
        try:
            return BACKENDS[backend]()
        except KeyError:
            raise ValueError(
                f"unknown backend {backend!r}; expected one of "
                f"{sorted(BACKENDS)}") from None
    raise TypeError(f"backend must be a Backend, str, or None, "
                    f"got {type(backend).__name__}")
