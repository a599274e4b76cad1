"""Scan <-> shard_map driver equivalence for STATEFUL compressors.

The shard_map spatial driver threads per-client compressor state (EF
residuals under ``client_state["comp"]``, plus the ``local_adam``
persistent moments) through the MANUAL region — these tests pin it to
the ``client_mode="scan"`` reference: 3 rounds from identical seeds must
produce the same global state and the same per-client state every round.

* shared / independent top-k with error feedback: BIT-identical — the
  per-client compute is elementwise + mask selection, and the mesh
  driver's dense aggregation replays scan's exact accumulation order
  (``aggregate.ordered_weighted_sum``).
* 1-bit Adam / Efficient-Adam: identical to ~2 ulp (f32).  Their block
  L1 / min-max scales are reductions, and XLA fuses those differently
  inside the scan body vs the shard_map body, so bitwise equality is not
  guaranteed by construction; the state threading itself is exact (the
  round-0 client state matches bitwise before any reduction feeds back).

Runs in a SUBPROCESS with 8 forced host devices (this process must keep
the 1-device backend for the smoke tests), like test_mesh_integration.
"""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

_REPO = Path(__file__).resolve().parents[1]

#: algorithm -> (FedConfig kwargs, must be bit-identical)
STATEFUL = {
    "fedadam_ssm": (dict(error_feedback=True, alpha=0.25), True),
    "fedadam_top": (dict(error_feedback=True, alpha=0.25), True),
    "onebit_adam": (dict(), False),
    "efficient_adam": (dict(), False),
}

_SUB = textwrap.dedent("""
    import json, os
    import jax, jax.numpy as jnp
    from repro.launch.mesh import make_mesh
    from repro.core import FedConfig, fed_init, make_fl_round
    from repro.core import comm
    from repro.core import sparsify as S
    from repro.optim import AdamHyper

    key = jax.random.PRNGKey(0)
    params = {"w": jax.random.normal(key, (8, 4)) * 0.1,
              "b": jnp.zeros((4,))}
    d = sum(x.size for x in jax.tree.leaves(params))
    C = 8
    xs = jax.random.normal(jax.random.PRNGKey(1), (C, 16, 8))
    w_true = jax.random.normal(jax.random.PRNGKey(2), (8, 4))
    ys = jnp.einsum("cbi,ij->cbj", xs, w_true)
    batches = (xs, ys)

    def loss_fn(p, b):
        x, y = b
        return jnp.mean((x @ p["w"] + p["b"] - y) ** 2)

    mesh = make_mesh((8,), ("data",))
    ALGOS = json.loads(os.environ["EQUIV_ALGOS"])

    def run(mode, algo, kw, rounds=3):
        fed = FedConfig(algorithm=algo, local_epochs=2, n_clients=C,
                        adam=AdamHyper(lr=0.05), client_mode=mode,
                        client_axes=(("data",) if mode == "vmap"
                                     else None), **kw)
        rf = jax.jit(make_fl_round(fed, loss_fn))
        st = fed_init(fed, params)
        assert st.client_state is not None, algo + " is not stateful"
        hist, bits = [], None
        if mode == "vmap":
            with jax.set_mesh(mesh):
                for _ in range(rounds):
                    st, mets = rf(st, batches)
                    hist.append(st)
                bits = float(mets["uplink_bits"])
        else:
            for _ in range(rounds):
                st, mets = rf(st, batches)
                hist.append(st)
        return hist, bits

    def maxdiff(ta, tb):
        la, lb = jax.tree.leaves(ta), jax.tree.leaves(tb)
        assert len(la) == len(lb)
        md, eq = 0.0, True
        for x, y in zip(la, lb):
            assert x.shape == y.shape and x.dtype == y.dtype
            md = max(md, float(jnp.max(jnp.abs(
                x.astype(jnp.float32) - y.astype(jnp.float32)))))
            eq = eq and bool((x == y).all())
        return md, eq

    out = {}
    for algo, kw in ALGOS.items():
        hs, _ = run("scan", algo, dict(kw))
        hm, bits = run("vmap", algo, dict(kw))
        rounds = []
        for a, b in zip(hs, hm):
            gmd, geq = maxdiff((a.W, a.M, a.V), (b.W, b.M, b.V))
            cmd, ceq = maxdiff(a.client_state, b.client_state)
            rounds.append(dict(global_maxdiff=gmd, global_eq=geq,
                               cs_maxdiff=cmd, cs_eq=ceq))
        k = S.k_for(d, kw.get("alpha", 0.05))
        sizes = tuple(x.size for x in jax.tree.leaves(params))
        expect_bits = float(C * comm.bits_for(
            algo, d, k, 1, 32, sizes=sizes,
            alpha=kw.get("alpha", 0.05)))
        out[algo] = dict(rounds=rounds, uplink_bits=bits,
                         expect_bits=expect_bits)
    print("RESULT", json.dumps(out))
""")


@pytest.fixture(scope="module")
def equiv():
    """One subprocess runs every stateful algorithm (scan + mesh, 3
    rounds each); the parameterized tests below assert per algorithm."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = str(_REPO / "src")
    env["EQUIV_ALGOS"] = json.dumps({k: v[0] for k, v in STATEFUL.items()})
    out = subprocess.run([sys.executable, "-c", _SUB], env=env,
                         capture_output=True, text=True, timeout=560)
    assert out.returncode == 0, out.stderr[-4000:]
    line = [ln for ln in out.stdout.splitlines()
            if ln.startswith("RESULT ")][-1]
    return json.loads(line[len("RESULT "):])


@pytest.mark.slow
@pytest.mark.parametrize("algo", sorted(STATEFUL))
def test_scan_shardmap_equivalence(equiv, algo):
    bitwise = STATEFUL[algo][1]
    rounds = equiv[algo]["rounds"]
    assert len(rounds) == 3
    for r, rec in enumerate(rounds):
        if bitwise:
            assert rec["global_eq"], \
                f"{algo} round {r}: global state differs " \
                f"(max {rec['global_maxdiff']})"
            assert rec["cs_eq"], \
                f"{algo} round {r}: per-client state differs " \
                f"(max {rec['cs_maxdiff']})"
        else:
            assert rec["global_maxdiff"] <= 2e-6, (algo, r, rec)
            assert rec["cs_maxdiff"] <= 2e-6, (algo, r, rec)
    # round 0 client state is pre-aggregation-feedback: must match
    # bitwise for EVERY compressor — state threading itself is exact
    assert rounds[0]["cs_eq"], f"{algo}: round-0 client state not bitwise"


@pytest.mark.slow
@pytest.mark.parametrize("algo", sorted(STATEFUL))
def test_mesh_uplink_bits_match_comm(equiv, algo):
    """bits reported by a mesh-driver round == comm.py wire-exact count
    (``comm.bits_for(..., sizes=...)`` == 8 * WirePayload.nbytes)."""
    assert equiv[algo]["uplink_bits"] == equiv[algo]["expect_bits"], algo
