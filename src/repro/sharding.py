"""Logical-axis -> mesh-axis rules and deployment plans.

Meshes (launch/mesh.py):
  single-pod: (16, 16)      axes ("data", "model")
  multi-pod : (2, 16, 16)   axes ("pod", "data", "model")

Parameter rule-sets
-------------------
``tp``   : megatron-style tensor parallel — heads/mlp/experts/vocab over
           "model"; everything else replicated.  Used when one client's
           (or the serving) weights fit a 16-chip model group.
``fsdp`` : tp + the d_model ("embed") dimension sharded over the data(+pod)
           axes — fully-sharded storage with GSPMD inserting per-layer
           all-gathers.  Used for archs whose FedAdam state (6-7x weights)
           exceeds a 16-chip group: kimi-k2, jamba-1.5-large,
           mistral-large, gemma3-27b.

Client mappings (docs/ARCHITECTURE.md §3-§4):
``spatial`` : FL clients = mesh data(+pod) slices; per-client divergent
              replicas carried as a leading vmapped client axis.
``virtual`` : FL clients time-multiplexed by lax.scan; full mesh per client.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
from jax import lax
from jax.sharding import PartitionSpec

from repro.configs.base import ArchConfig


def client_axes(multi_pod: bool) -> Tuple[str, ...]:
    return ("pod", "data") if multi_pod else ("data",)


def fsdp_axes(multi_pod: bool) -> Tuple[str, ...]:
    return ("data", "pod") if multi_pod else ("data",)


def param_rules(kind: str, multi_pod: bool) -> dict:
    rules = {
        "vocab": "model",
        "heads": "model",
        "kv_heads": "model",
        "mlp": "model",
        "experts": "model",
        "ssm_heads": "model",
        "ssm_inner": "model",
        "embed": None,
        "kv_lora": None,
        "head_dim": None,
        "conv": None,
        "layers": None,
    }
    if kind == "fsdp":
        rules["embed"] = fsdp_axes(multi_pod)
    elif kind != "tp":
        raise ValueError(kind)
    return rules


def cache_rules(shape_kind: str, multi_pod: bool,
                cache_seq_shard=None) -> dict:
    """Logical rules for decode caches / activations-by-name.

    cache_seq_shard: optional mesh axis (or tuple) for the cache sequence
    dim — the split-KV decode optimization (kv_heads often cannot shard on
    a 16-way model axis: GQA kv=2..8, so the cache is otherwise replicated
    across "model" and dominates decode memory).
    """
    rules = {
        "batch": client_axes(multi_pod),
        "kv_heads": "model",
        "ssm_heads": "model",
        "ssm_inner": "model",
        "kv_lora": None,
        "kv_seq": None,
        "enc_seq": None,
        "head_dim": None,
        "ssm_state": None,
        "conv": None,
        "layers": None,
        "embed": None,
    }
    if shape_kind == "long":
        # batch=1: shard the cache sequence axis instead (split-KV decode)
        rules["batch"] = None
        rules["kv_seq"] = "data"
    if cache_seq_shard is not None:
        rules["kv_seq"] = cache_seq_shard
    return rules


# ---------------------------------------------------------------------------
# Deployment plans per architecture
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class DeployPlan:
    clients: str = "spatial"        # spatial | virtual
    train_params: str = "tp"        # tp | fsdp
    serve_params: str = "tp"        # tp | fsdp  (fsdp = "2D" for serving)
    n_virtual: int = 2              # virtual-client count in dry-run
    why: str = ""


_BIG = DeployPlan(
    clients="virtual", train_params="fsdp", serve_params="fsdp",
    why="FedAdam state (~7x weights) exceeds a 16-chip TP group; params "
        "fully sharded over (data[,pod],model), clients time-multiplexed")

_MID = DeployPlan(
    clients="virtual", train_params="fsdp", serve_params="tp",
    why="training state needs FSDP; serving weights fit a TP group")

PLANS = {
    "kimi-k2-1t-a32b": dataclasses.replace(
        _BIG, why=_BIG.why + "; 1T params — serving also needs 2D"),
    "jamba-1-5-large-398b": _BIG,
    "mistral-large-123b": _MID,
    "gemma3-27b": _MID,
    "deepseek-v2-lite-16b": DeployPlan(
        clients="spatial", train_params="tp", serve_params="tp",
        why="16B: per-client TP state ~14GB — spatial clients on the data "
            "axis exercise the full on-mesh sparse uplink"),
}

_DEFAULT = DeployPlan(why="small arch: spatial clients, TP within client")


def plan_for(arch: str) -> DeployPlan:
    return PLANS.get(arch, _DEFAULT)


# ---------------------------------------------------------------------------
# Activation sharding hint (used sparingly inside model code)
# ---------------------------------------------------------------------------


def hint(x, *axes):
    """``with_sharding_constraint(x, PartitionSpec(*axes))`` over the
    ambient mesh (``jax.set_mesh``), leaving out the axes that are manual
    here (inside a shard_map region).  Identity when no mesh is set or
    every mesh axis is manual; an axis the mesh lacks raises."""
    mesh = jax.sharding.get_abstract_mesh()
    manual = set(mesh.manual_axes)
    if mesh.empty or manual == set(mesh.axis_names):
        return x
    spec = PartitionSpec(*(None if a in manual else a for a in axes))
    return lax.with_sharding_constraint(x, spec)
