"""Shared-sparse-mask (SSM) rules — Section V of the paper.

Given the three local update pytrees (dW, dM, dV) produce ONE boolean mask
pytree applied to all three:

* ``ssm_w``      — mask = Top_k(|dW|).  The paper's OPTIMAL rule (Eq. 28):
                   by Proposition 1, Gamma > Theta > Lambda, and empirically
                   |dW| >> |dM| >> |dV| (Fig. 1), so minimizing the dominant
                   Gamma-term of the Theorem-1 divergence bound reduces to
                   keeping the largest entries of dW.
* ``ssm_m``      — mask from |dM| (baseline FedAdam-SSM_M).
* ``ssm_v``      — mask from |dV| (baseline FedAdam-SSM_V).
* ``fairness_top`` — mask from the *union* of the three tensors
                   (Fairness-Top [40]): each tensor is magnitude-normalized
                   so all three compete fairly, then one top-k over the
                   elementwise max of the normalized scores.
* ``top``        — NOT a shared mask: three independent Top_k masks
                   (FedAdam-Top, Section IV).  Returned as a 3-tuple.

Consumed by the top-k compressors (core/compressors/topk.py,
docs/compressors.md); the rule string is a compressor-construction
parameter, never dispatched on inside the FL round.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import sparsify as S
from repro.core import stages

_F32 = jnp.float32

SHARED_RULES = ("ssm_w", "ssm_m", "ssm_v", "fairness_top")


def shared_score_tree(rule: str, dW, dM, dV):
    """Score tensors whose |.| the shared mask thresholds — the input to
    both the mask construction here and the fused kernel compress path
    (core/sparsify.tree_shared_compress_fused).  Returns ``None`` for
    ``ssm_w``: the score IS dW, and the fused kernel then derives the
    mask from the dW stream it already reads instead of streaming a
    separate score tensor."""
    if rule == "ssm_w":
        return None
    if rule == "ssm_m":
        return dM
    if rule == "ssm_v":
        return dV
    if rule == "fairness_top":
        def union(w, m, v):
            def norm(x):
                n = jnp.sqrt(jnp.sum(x.astype(_F32) ** 2)) + 1e-30
                return jnp.abs(x.astype(_F32)) / n
            return jnp.maximum(norm(w), jnp.maximum(norm(m), norm(v)))
        return jax.tree.map(union, dW, dM, dV)
    raise ValueError(f"unknown shared mask rule {rule!r}")


@stages.scoped(stages.SELECT)
def shared_mask(rule: str, dW, dM, dV, alpha: float,
                scope: str = "per_tensor", exact: bool = True,
                backend=None):
    score = shared_score_tree(rule, dW, dM, dV)
    score = jax.tree.map(jnp.abs, dW if score is None else score)
    return S.tree_topk_masks(score, alpha, scope=scope, exact=exact,
                             backend=backend)


@stages.scoped(stages.SELECT)
def independent_masks(dW, dM, dV, alpha: float, scope: str = "per_tensor",
                      exact: bool = True, backend=None):
    """FedAdam-Top: three separate Top_k masks."""
    mk = lambda t: S.tree_topk_masks(
        jax.tree.map(jnp.abs, t), alpha, scope=scope, exact=exact,
        backend=backend)
    return mk(dW), mk(dM), mk(dV)
