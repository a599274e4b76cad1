"""Entry points and mesh plumbing: the in-process trainer, the compile
cache location, the chip smoke's refusal to run off the TPU, the manual
axes of the per-client shard_map region, and sharding hints that raise
instead of dropping a bad constraint."""
import contextlib
import io
import math
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import AbstractMesh

from repro.core.fed import client_region_axes
from repro.launch import cache, train
from repro.launch.mesh import make_mesh
from repro.sharding import hint

_REPO = Path(__file__).resolve().parents[1]


def test_train_returns_per_round_metrics():
    run = train.train(train.parse_args([
        "--arch", "starcoder2-3b", "--smoke", "--rounds", "2",
        "--clients", "2", "--local-epochs", "1", "--seq", "32",
        "--threshold-topk"]))
    assert len(run.rounds) == 2
    for r in run.rounds:
        assert math.isfinite(r["loss"]) and r["uplink_bits"] > 0
        assert r["seconds"] > 0
        assert 0 < r["value_fill_share"] <= 100
        assert 0 <= r["mask_dropped_share"] < 100
    assert run.rounds[0]["uplink_bits"] == run.rounds[1]["uplink_bits"]
    assert run.compile_seconds > 0
    assert "HloModule" in run.compiled.as_text()
    assert int(run.state.round) == 2


@pytest.mark.parametrize("env", [None, "elsewhere"])
def test_compile_cache_dir(monkeypatch, tmp_path, env):
    """``JAX_COMPILATION_CACHE_DIR`` wins and the code sets nothing;
    otherwise the cache sits at a fixed path inside the checkout."""
    was = jax.config.jax_compilation_cache_dir
    if env is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = str(_REPO / ".jax_cache")
    else:
        want = str(tmp_path / env)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", want)
    try:
        assert cache.enable_compile_cache() == want
        expect = want if env is None else was
        assert jax.config.jax_compilation_cache_dir == expect
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_chip_smoke_fails_without_a_tpu():
    sys.path.insert(0, str(_REPO))
    try:
        import chip_smoke
    finally:
        sys.path.remove(str(_REPO))
    out, err = io.StringIO(), io.StringIO()
    with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
        rc = chip_smoke.main([])
    assert rc != 0
    assert '"ok"' not in out.getvalue()
    assert "no TPU" in err.getvalue()


@pytest.mark.parametrize("sizes, want", [
    ((4, 1), {"data", "model"}),
    ((4, 2), {"data"}),
    ((1, 2), {"data"}),
])
def test_client_region_axes(sizes, want):
    """Size-1 axes join the manual region; a real model axis stays with
    the automatic partitioner."""
    mesh = AbstractMesh(sizes, ("data", "model"))
    assert client_region_axes(("data",), mesh) == frozenset(want)


def test_hint_is_identity_without_a_mesh():
    x = jnp.ones((4,))
    assert hint(x, "data") is x


def test_hint_raises_on_an_axis_the_mesh_lacks():
    x = jnp.ones((4,))
    with jax.set_mesh(make_mesh((1,), ("data",))):
        assert jax.jit(lambda a: hint(a, "data"))(x).shape == (4,)
        with pytest.raises(ValueError, match="nope"):
            jax.jit(lambda a: hint(a, "nope"))(x)
