"""The round's stage scopes and the wire's mask counters.

* Every ``fl.*`` stage name (core/stages.py) reaches the compiled
  round's ``op_name`` metadata, on the scan driver and on the vmap
  sparse-gather wire transport.
* ``mask_selected`` / ``mask_shipped`` / ``mask_capacity`` count the
  uncapped union support, the shipped bitmap and the value slots; they
  stay out of the payload, and schemes without a mask payload (and the
  mesh step, which ships none of its own) report zeros.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import FedConfig, compressors, fed_init, make_fl_round
from repro.core import sparsify as S
from repro.core import stages, wire
from repro.core.compressors import Deltas
from repro.kernels.wirepack.ref import unpack_mask_bits_ref
from repro.optim import AdamHyper

_BF16 = jnp.bfloat16
C = 3


def _toy(dtype=jnp.float32):
    key = jax.random.PRNGKey(0)
    params = {"w": (jax.random.normal(key, (64, 40)) * 0.1).astype(dtype),
              "b": jnp.zeros((40,), dtype)}
    xs = jax.random.normal(jax.random.PRNGKey(1), (C, 16, 64))
    ys = jax.random.normal(jax.random.PRNGKey(2), (C, 16, 40))

    def loss_fn(p, batch):
        x, y = batch
        out = x @ p["w"].astype(jnp.float32) + p["b"].astype(jnp.float32)
        return jnp.mean((out - y) ** 2)

    return params, (xs, ys), loss_fn


def _fed(algo, **kw):
    return FedConfig(algorithm=algo, alpha=0.1, local_epochs=2,
                     n_clients=C, adam=AdamHyper(lr=0.05), **kw)


def _round(fed, dtype=jnp.float32):
    params, batches, loss_fn = _toy(dtype)
    rf = jax.jit(make_fl_round(fed, loss_fn))
    state = fed_init(fed, params)
    return rf, state, batches


_DRIVERS = {
    "ssm-scan": dict(algo="fedadam_ssm", client_mode="scan"),
    "top-vmap-wire": dict(algo="fedadam_top", client_mode="vmap",
                          aggregate="sparse_gather"),
}


@pytest.fixture(scope="module")
def op_names():
    """The compiled round's ``op_name`` strings, per driver."""
    out = {}
    for key, kw in _DRIVERS.items():
        kw = dict(kw)
        fed = _fed(kw.pop("algo"), **kw)
        rf, state, batches = _round(fed)
        text = rf.lower(state, batches).compile().as_text()
        out[key] = re.findall(r'op_name="([^"]*)"', text)
    return out


@pytest.mark.parametrize("stage", stages.ALL)
@pytest.mark.parametrize("driver", sorted(_DRIVERS))
def test_every_stage_reaches_the_compiled_op_names(op_names, driver,
                                                   stage):
    pat = re.compile(r"(?<![\w.])" + re.escape(stage) + r"\b")
    assert any(pat.search(n) for n in op_names[driver]), stage


def test_stage_names_are_distinct_fl_names():
    assert len(set(stages.ALL)) == 8
    assert all(re.fullmatch(r"fl\.[a-z_]+", s) for s in stages.ALL)


# ---------------------------------------------------------------------------
# Counters
# ---------------------------------------------------------------------------


def _tied_carriers(tied: bool):
    """bf16 carriers: leaf ``a`` is 4096 entries at one magnitude when
    ``tied`` (a threshold mask keeps every one, past the leaf's
    capacity), else an exact top-k; leaf ``b`` an exact top-k, whose
    support M widens when ``tied`` (the union then outgrows W's)."""
    alpha = 0.05
    x = jax.random.normal(jax.random.PRNGKey(3), (4096,))
    y = jax.random.normal(jax.random.PRNGKey(4), (3000,))
    exact = lambda v: v * S.topk_mask_exact(v, S.k_for(v.size, alpha))
    a = jnp.full((4096,), 0.5) if tied else exact(x)
    sW = {"a": a.astype(_BF16), "b": exact(y).astype(_BF16)}
    mb = exact(jnp.roll(y, 7)) if tied else 2.0 * exact(y)
    sM = {"a": (2.0 * a).astype(_BF16), "b": mb.astype(_BF16)}
    sV = jax.tree.map(lambda t: (3.0 * t).astype(_BF16), sW)
    sizes = tuple(v.size for v in jax.tree.leaves(sW))
    caps = wire.mask_leaf_capacities(sizes, alpha, exact_topk=False)
    return (sW, sM, sV), sizes, caps, alpha


def _union_count(trees, shared: bool) -> int:
    nz = [[np.asarray(x) != 0 for x in jax.tree.leaves(t)] for t in trees]
    if shared:
        return sum(int(np.count_nonzero(w | m | v)) for w, m, v in zip(*nz))
    return sum(int(np.count_nonzero(x)) for leaves in nz for x in leaves)


def _bitmap_popcount(payload) -> int:
    return sum(int(np.asarray(unpack_mask_bits_ref(w)).sum())
               for w in payload.words)


@pytest.mark.parametrize("tied", [True, False])
@pytest.mark.parametrize("layout", ["shared", "independent"])
def test_mask_counters_count_the_support_and_the_bitmap(layout, tied):
    trees, sizes, caps, alpha = _tied_carriers(tied)
    shared = layout == "shared"
    pack = wire.pack_shared_mask if shared else wire.pack_independent_mask
    payload, counts = pack(*trees, caps)
    assert set(counts) == set(wire.COUNT_KEYS)
    assert all(v.dtype == jnp.int32 for v in counts.values())
    sel, shipped, cap = (int(counts[k]) for k in wire.COUNT_KEYS)
    assert sel == _union_count(trees, shared)
    assert shipped == _bitmap_popcount(payload)
    assert cap == (1 if shared else 3) * sum(caps)
    assert shipped <= cap
    if tied:
        assert sel > shipped                 # the cap dropped the ties
        assert sel > sum(caps)
    else:
        assert sel == shipped
    # the counters never enter the payload: its bytes are the formula's
    assert 8 * wire.payload_nbytes(payload) == wire.mask_wire_bits(
        sizes, alpha, exact_topk=False, shared=shared)


@pytest.mark.parametrize("algo", ["fedadam_ssm", "fedadam_top"])
def test_compress_carries_the_counters_of_its_payload(algo):
    """A bf16 threshold compress over-selects on ties; its counters are
    those of the carriers it hands over and of the payload it built."""
    fed = FedConfig(algorithm=algo, alpha=0.05, exact_topk=False,
                    sparsify_backend="reference")
    comp = compressors.make_compressor(fed)
    key = jax.random.PRNGKey(5)
    d = lambda i: {"w": (jnp.round(jax.random.normal(
        jax.random.fold_in(key, i), (96, 80)) * 4) / 64).astype(_BF16)}
    packed, _, _ = comp.compress(Deltas(d(0), d(1), d(2)), None)
    counts = packed.counts
    shared = algo == "fedadam_ssm"
    assert int(counts["mask_selected"]) == _union_count(
        (packed.W, packed.M, packed.V), shared)
    assert int(counts["mask_shipped"]) == _bitmap_popcount(packed.wire)
    assert int(counts["mask_shipped"]) <= int(counts["mask_capacity"])
    assert int(counts["mask_selected"]) > int(counts["mask_shipped"])


def test_round_reports_counters_per_client():
    fed = _fed("fedadam_ssm", exact_topk=False)
    rf, state, batches = _round(fed, _BF16)
    _, mets = rf(state, batches)
    for k in wire.COUNT_KEYS:
        assert mets[k].shape == (C,) and mets[k].dtype == jnp.int32
    assert bool(jnp.all(mets["mask_shipped"] <= mets["mask_capacity"]))
    assert bool(jnp.all(mets["mask_shipped"] > 0))
    assert bool(jnp.all(mets["mask_selected"] >= mets["mask_shipped"]))
    _, again = rf(state, batches)
    for k in wire.COUNT_KEYS:
        assert bool(jnp.all(again[k] == mets[k]))


@pytest.mark.parametrize("algo", ["fedadam", "fedsgd", "onebit_adam",
                                  "efficient_adam"])
def test_schemes_without_a_mask_payload_report_zeros(algo):
    rf, state, batches = _round(_fed(algo))
    _, mets = rf(state, batches)
    for k in wire.COUNT_KEYS:
        assert mets[k].shape == (C,)
        assert not bool(jnp.any(mets[k])), k
    assert wire.mask_shares({k: jnp.sum(mets[k])
                             for k in wire.COUNT_KEYS}) is None


def test_mesh_step_reports_zeros():
    """The shard_map driver's step ships no wire payload of its own (its
    transport is the per-shard bitmap): zeros, under the same keys."""
    from repro.launch.mesh import make_mesh

    params, batches, loss_fn = _toy()
    batches = jax.tree.map(lambda x: x[:1], batches)
    fed = FedConfig(algorithm="fedadam_ssm", alpha=0.1, local_epochs=2,
                    n_clients=1, adam=AdamHyper(lr=0.05),
                    client_mode="vmap", client_axes=("data",))
    rf = jax.jit(make_fl_round(fed, loss_fn))
    with jax.set_mesh(make_mesh((1,), ("data",))):
        _, mets = rf(fed_init(fed, params), batches)
    for k in wire.COUNT_KEYS:
        assert mets[k].shape == (1,) and int(mets[k][0]) == 0


def test_mask_shares():
    assert wire.mask_shares(dict(mask_selected=120, mask_shipped=90,
                                 mask_capacity=100)) == (90.0, 25.0)
    assert wire.mask_shares(dict(mask_selected=0, mask_shipped=0,
                                 mask_capacity=100)) == (0.0, 0.0)
    assert wire.mask_shares(dict.fromkeys(wire.COUNT_KEYS, 0)) is None


def test_async_steps_sum_the_counters_of_their_landed_updates():
    """Zero churn, K = cohort: each server step's counters are the sync
    round's, summed over the clients."""
    from repro.core import AsyncConfig, make_async_round

    fed = _fed("fedadam_ssm", exact_topk=False)
    rf, state, batches = _round(fed, _BF16)
    _, mets = rf(state, batches)
    params, _, loss_fn = _toy(_BF16)
    run = make_async_round(fed, loss_fn, AsyncConfig(buffer_size=C))
    _, amets = run(fed_init(fed, params), batches, rounds=1)
    assert amets["counts_per_step"] == [
        {k: int(jnp.sum(mets[k])) for k in wire.COUNT_KEYS}]
