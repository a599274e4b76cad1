"""Top-k sparsifying compressors — FedAdam-SSM and its mask baselines.

``SharedTopKCompressor`` realizes the paper's contribution: ONE boolean
mask (Eq. 28: ``Top_k(|dW|)`` for rule ``ssm_w``; ``ssm_m``/``ssm_v``/
``fairness_top`` are the Section VII mask-rule baselines) applied to all
three deltas, so a single index set describes the support of W, M and V
— the alignment that makes the Section IV bit count
``N * min(3kq + d, k(3q + log2 d))`` instead of three index sets.

``IndependentTopKCompressor`` is FedAdam-Top: three separate Top_k masks,
three index sets, ``3N * min(kq + d, k(q + log2 d))`` bits.

Both optionally carry a beyond-paper error-feedback residual on dW: the
round's masked-away remainder is added back into the next round's input
(``init_state`` returns the zero residual; stateless when EF is off).

Hot path: with threshold masks (``exact_topk=False``) and the kernel
backend active (``sparsify_backend`` / REPRO_SPARSIFY_BACKEND, auto on
TPU), ``compress`` runs the PACKED Pallas pipeline: every pytree leaf
rides one tile-aligned buffer and the whole cohort costs exactly two
launches — a segmented tau histogram, then fused refine/tau-pick/mask
apply + ``value_dtype`` wire cast + EF residual
(``core/sparsify.tree_shared_compress_packed`` for the shared mask,
``tree_independent_compress_packed`` for FedAdam-Top's three masks) —
instead of 4 launches per leaf.  Backend rules, layout and launch
accounting: docs/kernels.md.

See ``docs/compressors.md`` for the protocol and bit formulas.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import comm, masks, wire
from repro.core import sparsify as S
from repro.core.compressors.base import (
    Compressor, Deltas, Packed, register, tree_add, tree_size, tree_sub,
)


def _cast_values(value_dtype, tree):
    """Beyond-paper low-precision value transport (cast + cast back)."""
    if value_dtype is None:
        return tree
    dt = jnp.dtype(value_dtype)
    return jax.tree.map(lambda x: x.astype(dt).astype(x.dtype), tree)


@dataclasses.dataclass(frozen=True)
class _TopKBase(Compressor):
    alpha: float = 0.05
    mask_scope: str = "per_tensor"        # per_tensor | global
    exact_topk: bool = True
    error_feedback: bool = False
    value_dtype: Optional[str] = None
    q_bits: int = 32
    # auto | kernel | reference — resolved by core/sparsify.resolve_backend
    # (TPU -> Pallas kernels, else jnp reference; env-overridable).  Only
    # the threshold (exact_topk=False) masks have a kernel realization.
    sparsify_backend: str = "auto"

    def init_state(self, params):
        if not self.error_feedback:
            return None
        return {"err": jax.tree.map(jnp.zeros_like, params)}

    def _masks(self, dW, dM, dV):
        raise NotImplementedError

    def _kernel_path(self) -> bool:
        return (not self.exact_topk) and \
            S.use_kernel_path(self.sparsify_backend)

    def _fused_compress(self, dW, dM, dV, with_residual):
        """Kernel-path fused compress.  Returns ``(sW, sM, sV,
        err_tree | None, mask)`` — ``mask`` is one shared tree
        (SharedTopK) or a ``(mW, mM, mV)`` tuple (IndependentTopK) —
        or None when the compressor has no fused realization for these
        inputs (e.g. mixed dtypes defeat the packed layout)."""
        return None

    def _wire_ok(self) -> bool:
        # wire value streams ship as f32 — exact only at q = 32
        return self.q_bits == wire.VALUE_BITS

    def _mask_capacity(self, sizes) -> tuple:
        return wire.mask_leaf_capacities(sizes, self.alpha,
                                         self.mask_scope, self.exact_topk)

    def _pack_wire(self, sW, sM, sV, sizes):
        """``(payload, counts)`` of the carriers (``core/wire.py``)."""
        raise NotImplementedError

    def compress(self, deltas: Deltas, state):
        dW, dM, dV = deltas
        if state is not None:
            dW = tree_add(dW, state["err"])
        fused = self._fused_compress(dW, dM, dV, state is not None) \
            if self._kernel_path() else None
        if fused is not None:
            # ONE streaming pipeline: mask apply on all three deltas, the
            # value_dtype wire cast and the EF residual — two packed
            # launches for the whole cohort instead of 4 per leaf
            # (docs/kernels.md).  Independent compressors return a
            # (mW, mM, mV) tuple; shared compressors one mask for all.
            sW, sM, sV, err, m = fused
            if isinstance(m, tuple):
                mW, mM, mV = m
            else:
                mW = mM = mV = m
            new_state = {"err": err} if state is not None else None
        else:
            mW, mM, mV = self._masks(dW, dM, dV)
            sW = _cast_values(self.value_dtype, S.tree_sparsify(dW, mW))
            sM = _cast_values(self.value_dtype, S.tree_sparsify(dM, mM))
            sV = _cast_values(self.value_dtype, S.tree_sparsify(dV, mV))
            new_state = {"err": tree_sub(dW, sW)} \
                if state is not None else None
        diag = {
            "err_w": S.tree_sparsity_error(dW, mW),
            "err_m": S.tree_sparsity_error(dM, mM),
            "err_v": S.tree_sparsity_error(dV, mV),
            "norm_dw": S.tree_norm(dW),
            "norm_dm": S.tree_norm(dM),
            "norm_dv": S.tree_norm(dV),
        }
        payload, counts = self._counted_pack_wire(Deltas(sW, sM, sV))
        packed = Packed(sW, sM, sV, diag, payload, counts)
        return packed, new_state, self.bits_per_client(tree_size(deltas.W))

    def _counted_pack_wire(self, carriers: Deltas):
        """``(payload, counts)``, or ``(None, None)`` off the wire."""
        if not self._wire_ok():
            return None, None
        sizes = tuple(x.size for x in jax.tree.leaves(carriers.W))
        return self._pack_wire(carriers.W, carriers.M, carriers.V, sizes)

    def pack_wire(self, carriers: Deltas):
        # idempotent: the sparse carriers' union support IS the mask, so
        # re-encoding a decoded triple reproduces the payload bitwise
        # (what lets the async driver re-materialize landed bytes)
        return self._counted_pack_wire(carriers)[0]


@dataclasses.dataclass(frozen=True)
class SharedTopKCompressor(_TopKBase):
    """One shared mask for all three tensors (FedAdam-SSM family)."""

    name: str = "fedadam_ssm"
    rule: str = "ssm_w"                   # ssm_w | ssm_m | ssm_v | fairness_top

    transport = "shared_sparse"
    wire_layout = "mask_shared"

    def _masks(self, dW, dM, dV):
        m = masks.shared_mask(self.rule, dW, dM, dV, self.alpha,
                              self.mask_scope, self.exact_topk,
                              backend=self.sparsify_backend)
        return m, m, m

    def _fused_compress(self, dW, dM, dV, with_residual):
        score = masks.shared_score_tree(self.rule, dW, dM, dV)
        sW, sM, sV, err, m = S.tree_shared_compress_fused(
            score, dW, dM, dV, self.alpha, self.mask_scope,
            value_dtype=self.value_dtype, with_residual=with_residual)
        return sW, sM, sV, err, m

    def _pack_wire(self, sW, sM, sV, sizes):
        return wire.pack_shared_mask(sW, sM, sV, self._mask_capacity(sizes))

    def unpack_wire(self, payload, like) -> Deltas:
        return Deltas(*wire.unpack_shared_mask(payload, like))

    def bits_per_client(self, d: int) -> int:
        return comm.bits_fedadam_ssm(d, S.k_for(d, self.alpha), 1,
                                     self.q_bits)

    def wire_bits_per_client(self, sizes):
        if not self._wire_ok():
            return None
        return wire.mask_wire_bits(sizes, self.alpha, self.mask_scope,
                                   self.exact_topk, shared=True)


@dataclasses.dataclass(frozen=True)
class IndependentTopKCompressor(_TopKBase):
    """Three independent Top_k masks (FedAdam-Top)."""

    name: str = "fedadam_top"

    transport = "independent_sparse"
    wire_layout = "mask_independent"

    def _masks(self, dW, dM, dV):
        # three distinct masks — no shared-mask fusion, but the mask
        # construction itself still dispatches to the threshold kernel
        return masks.independent_masks(dW, dM, dV, self.alpha,
                                       self.mask_scope, self.exact_topk,
                                       backend=self.sparsify_backend)

    def _fused_compress(self, dW, dM, dV, with_residual):
        # three independent selections still collapse to TWO launches:
        # all leaves of dW ++ dM ++ dV share one packed buffer whose
        # segments each pick their own tau (core/sparsify)
        if not S._uniform_dtype(dW, dM, dV):
            return None
        return S.tree_independent_compress_packed(
            dW, dM, dV, self.alpha, self.mask_scope,
            value_dtype=self.value_dtype, with_residual=with_residual)

    def _pack_wire(self, sW, sM, sV, sizes):
        return wire.pack_independent_mask(sW, sM, sV,
                                          self._mask_capacity(sizes))

    def unpack_wire(self, payload, like) -> Deltas:
        return Deltas(*wire.unpack_independent_mask(payload, like))

    def bits_per_client(self, d: int) -> int:
        return comm.bits_fedadam_top(d, S.k_for(d, self.alpha), 1,
                                     self.q_bits)

    def wire_bits_per_client(self, sizes):
        if not self._wire_ok():
            return None
        return wire.mask_wire_bits(sizes, self.alpha, self.mask_scope,
                                   self.exact_topk, shared=False)


def _shared_factory(rule):
    def factory(fed) -> SharedTopKCompressor:
        return SharedTopKCompressor(
            name=fed.algorithm, rule=rule, alpha=fed.alpha,
            mask_scope=fed.mask_scope, exact_topk=fed.exact_topk,
            error_feedback=fed.error_feedback, value_dtype=fed.value_dtype,
            q_bits=fed.q_bits, sparsify_backend=fed.sparsify_backend)
    return factory


register("fedadam_ssm")(_shared_factory("ssm_w"))
register("ssm_m")(_shared_factory("ssm_m"))
register("ssm_v")(_shared_factory("ssm_v"))
register("fairness_top")(_shared_factory("fairness_top"))


@register("fedadam_top")
def _fedadam_top(fed) -> IndependentTopKCompressor:
    return IndependentTopKCompressor(
        name="fedadam_top", alpha=fed.alpha, mask_scope=fed.mask_scope,
        exact_topk=fed.exact_topk, error_feedback=fed.error_feedback,
        value_dtype=fed.value_dtype, q_bits=fed.q_bits,
        sparsify_backend=fed.sparsify_backend)
