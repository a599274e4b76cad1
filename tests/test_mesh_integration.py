"""Mesh integration tests: run in a SUBPROCESS with 8 forced host devices
(this process must keep the 1-device backend for the smoke tests).

Covers: sharded FL train step executes and matches the unsharded result;
serve step executes sharded; the shard_map sparse transport engages the
expected collectives.
"""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import pytest

_REPO = Path(__file__).resolve().parents[1]

# The sharded step builders use jax.shard_map under a jax.set_mesh
# context, on meshes with Auto axes (launch/mesh.make_mesh).


def _run_sub(code: str) -> dict:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = str(_REPO / "src")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=560)
    assert out.returncode == 0, out.stderr[-4000:]
    line = [ln for ln in out.stdout.splitlines()
            if ln.startswith("RESULT ")][-1]
    return json.loads(line[len("RESULT "):])


@pytest.mark.slow
def test_sharded_train_step_matches_single_device():
    code = textwrap.dedent("""
        import json, jax, jax.numpy as jnp
        from repro.configs import get_config, reduce_for_smoke
        from repro.launch import steps as ST
        from repro.launch.mesh import make_test_mesh
        from repro.models import init_params
        from repro.core import fed_init

        cfg = reduce_for_smoke(get_config("starcoder2-3b"))
        ST.SHAPES["train_4k"] = ST.ShapeSpec("train_4k", 64, 4, "train")
        mesh = make_test_mesh()
        bundle = ST.build_step(cfg, mesh, "train_4k", local_epochs=2)
        params = init_params(cfg, jax.random.PRNGKey(0))
        fed = bundle.static["fed"]
        state = fed_init(fed, params)
        batch = {"tokens": jax.random.randint(
            jax.random.PRNGKey(1),
            bundle.args_sds[1]["tokens"].shape, 0, cfg.vocab_size)}
        with jax.set_mesh(mesh):
            jfn = jax.jit(bundle.fn, in_shardings=bundle.in_shardings,
                          out_shardings=bundle.out_shardings)
            st2, mets = jfn(state, batch)
        loss = float(jnp.mean(mets["loss"]))
        wsum = float(sum(jnp.sum(jnp.abs(x.astype(jnp.float32)))
                     for x in jax.tree.leaves(st2.W)))
        print("RESULT", json.dumps({"loss": loss, "wsum": wsum}))
    """)
    res = _run_sub(code)
    assert res["loss"] > 0 and res["wsum"] > 0
    import math
    assert math.isfinite(res["loss"]) and math.isfinite(res["wsum"])


@pytest.mark.slow
def test_sharded_serve_step_runs():
    code = textwrap.dedent("""
        import json, functools, jax, jax.numpy as jnp
        from repro.configs import get_config, reduce_for_smoke
        from repro.launch import steps as ST
        from repro.launch.mesh import make_test_mesh
        from repro.models import init_params, materialize, cache_meta

        cfg = reduce_for_smoke(get_config("mamba2-1-3b"))
        ST.SHAPES["decode_32k"] = ST.ShapeSpec("decode_32k", 128, 4, "decode")
        mesh = make_test_mesh()
        bundle = ST.build_step(cfg, mesh, "decode_32k")
        params = init_params(cfg, jax.random.PRNGKey(0))
        caches = materialize(cache_meta(cfg, 4, 128), jax.random.PRNGKey(1))
        tok = jnp.zeros((4,), jnp.int32)
        with jax.set_mesh(mesh):
            jfn = jax.jit(bundle.fn, in_shardings=bundle.in_shardings,
                          out_shardings=bundle.out_shardings)
            logits, caches = jfn(params, caches, jnp.int32(0), tok)
            logits, _ = jfn(params, caches, jnp.int32(1), tok)
        ok = bool(jnp.isfinite(logits).all())
        print("RESULT", json.dumps({"ok": ok,
                                    "shape": list(logits.shape)}))
    """)
    res = _run_sub(code)
    assert res["ok"] and res["shape"][0] == 4


@pytest.mark.slow
def test_sharded_train_step_threads_ef_state():
    """Stateful (error-feedback) compressor through the full launch path:
    per-client EF residuals enter the shard_map MANUAL region sharded
    over the client mesh axes, are updated by the round, and come back
    client-stacked — nonzero after a round that dropped anything."""
    code = textwrap.dedent("""
        import json, jax, jax.numpy as jnp
        from repro.configs import get_config, reduce_for_smoke
        from repro.launch import steps as ST
        from repro.launch.mesh import make_test_mesh
        from repro.models import init_params
        from repro.core import fed_init

        cfg = reduce_for_smoke(get_config("starcoder2-3b"))
        ST.SHAPES["train_4k"] = ST.ShapeSpec("train_4k", 64, 4, "train")
        mesh = make_test_mesh()
        bundle = ST.build_step(cfg, mesh, "train_4k", local_epochs=2,
                               aggregate="sparse_gather",
                               error_feedback=True, alpha=0.05)
        fed = bundle.static["fed"]
        assert fed.error_feedback and fed.aggregate == "sparse_gather"
        params = init_params(cfg, jax.random.PRNGKey(0))
        state = fed_init(fed, params)
        assert state.client_state is not None, "EF state missing at init"
        batch = {"tokens": jax.random.randint(
            jax.random.PRNGKey(1),
            bundle.args_sds[1]["tokens"].shape, 0, cfg.vocab_size)}
        with jax.set_mesh(mesh):
            jfn = jax.jit(bundle.fn, in_shardings=bundle.in_shardings,
                          out_shardings=bundle.out_shardings)
            st2, mets = jfn(state, batch)
            st3, _ = jfn(st2, batch)
        err1 = st2.client_state["comp"]["err"]
        err2 = st3.client_state["comp"]["err"]
        n_c = fed.n_clients
        lead_ok = all(x.shape[0] == n_c for x in jax.tree.leaves(err1))
        norm1 = float(sum(jnp.sum(jnp.abs(x.astype(jnp.float32)))
                          for x in jax.tree.leaves(err1)))
        norm2 = float(sum(jnp.sum(jnp.abs(x.astype(jnp.float32)))
                          for x in jax.tree.leaves(err2)))
        carried = any(bool(jnp.any(a != b)) for a, b in
                      zip(jax.tree.leaves(err1), jax.tree.leaves(err2)))
        loss = float(jnp.mean(mets["loss"]))
        print("RESULT", json.dumps({
            "loss": loss, "lead_ok": lead_ok, "carried": carried,
            "err_norm1": norm1, "err_norm2": norm2}))
    """)
    res = _run_sub(code)
    import math
    assert math.isfinite(res["loss"]) and res["loss"] > 0
    assert res["lead_ok"], "EF state lost its client axis"
    # a sparse round drops mass, so the residual must be populated and
    # must evolve round-over-round (it is carried, not re-zeroed)
    assert res["err_norm1"] > 0 and math.isfinite(res["err_norm1"])
    assert res["err_norm2"] > 0 and math.isfinite(res["err_norm2"])
    assert res["carried"]


@pytest.mark.slow
def test_sparse_transport_collectives_present():
    """The shard_map sparse aggregation lowers to all-gathers whose total
    bytes are far below the dense all-reduce of the model."""
    code = textwrap.dedent("""
        import json, jax, jax.numpy as jnp
        from repro.configs import get_config, reduce_for_smoke
        from repro.launch import steps as ST
        from repro.launch.mesh import make_test_mesh
        from repro import roofline as RL

        cfg = reduce_for_smoke(get_config("starcoder2-3b"))
        ST.SHAPES["train_4k"] = ST.ShapeSpec("train_4k", 64, 4, "train")
        mesh = make_test_mesh()
        out = {}
        for algo, agg in [("fedadam_ssm", "sparse_gather"),
                          ("fedadam", "dense")]:
            bundle = ST.build_step(cfg, mesh, "train_4k",
                                   algorithm=algo, aggregate=agg,
                                   local_epochs=1, alpha=0.05)
            with jax.set_mesh(mesh):
                jfn = jax.jit(bundle.fn, in_shardings=bundle.in_shardings,
                              out_shardings=bundle.out_shardings)
                compiled = jfn.lower(*bundle.args_sds).compile()
            coll = RL.collective_bytes(compiled.as_text(),
                                       bundle.static["loop_trips"])
            out[algo] = coll["total"]
        print("RESULT", json.dumps(out))
    """)
    res = _run_sub(code)
    assert res["fedadam_ssm"] > 0
    assert res["fedadam_ssm"] < 0.6 * res["fedadam"], res
