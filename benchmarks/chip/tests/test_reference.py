"""The reference against the program at a small size on the CPU: the
same weights from the same seed, the same losses in float32, and gaps
in bfloat16 that stay far under those of the planted faults."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import check
import program
import reference as R
import traffic
from conftest import TINY, TINY_MIX

SEED = 2**31 + 77


def readings(c, mix, seed):
    prog = program.Program(c, mix, seed, check=False)
    batches = [jax.tree.map(jnp.asarray, traffic.client_batches(
        mix, c["vocab_size"], c["hidden_size"], c["encoder_frames"], seed,
        r)) for r in range(mix["check_rounds"])]
    prog.compile(batches[0])
    losses, m1 = [], None
    for b in batches:
        mets = prog.run_round(b)
        losses.append([float(x) for x in mets["loss"]])
        if m1 is None:
            m1 = prog.m_norms()
    return prog, batches, {"losses": losses, "m1_norms": m1,
                           "dw_norms": prog.change_norms()}


def test_reference_makes_the_programs_weights():
    c = dict(TINY)
    prog = program.Program(c, TINY_MIX, SEED, check=False)
    mine = R.init_params(c, jax.random.PRNGKey(SEED))
    a, ta = jax.tree_util.tree_flatten(prog.state.W)
    b, tb = jax.tree_util.tree_flatten(mine)
    assert ta == tb
    for x, y in zip(a, b):
        assert x.dtype == y.dtype and bool(jnp.array_equal(x, y))


@pytest.mark.parametrize("dtype,loss0", [("float32", 1e-5),
                                         ("bfloat16", 2e-3)])
def test_reference_agrees_with_the_program(dtype, loss0):
    c = dict(TINY, dtype=dtype)
    _, batches, got = readings(c, TINY_MIX, SEED)
    ref = R.run(c, TINY_MIX, SEED, batches, TINY_MIX["check_rounds"])
    nums = check.numbers(got, ref)
    assert nums["loss0_gap"] < loss0
    # the program's threshold mask keeps up to 6% + 8 more entries
    assert nums["grad_gap"] < 0.15 and nums["update_gap"] < 0.15


def test_threshold_mask_keeps_ties_up_to_the_capacity():
    a = jnp.array([3.0, 1.0, 2.0, 2.0, 2.0, 0.0])
    assert float(R.kth_largest(a, 3)) == 2.0
    one = jnp.ones_like(a)
    assert R.threshold_mask(a, one, one, 3).tolist() == [
        True, False, True, True, True, False]
    # k = 0 nonzero at the threshold: zeros with a moving moment count
    w = jnp.zeros(2000).at[1500].set(1.0)
    m = jnp.ones(2000)
    keep = R.threshold_mask(w, m, m, 100)
    assert int(keep.sum()) == R.capacity(2000, 100) == 114
    assert bool(keep[0]) and not bool(keep[1500])
    x = jax.random.normal(jax.random.PRNGKey(0), (1000,))
    keep = R.threshold_mask(x, x, x, 50)
    assert int(keep.sum()) == 50
    assert float(jnp.abs(x)[keep].min()) >= float(jnp.abs(x)[~keep].max())
