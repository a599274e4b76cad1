"""A run of the harness, without its look for a chip, with the timed path
broken underneath: ``correct`` comes out false for each fault a training
round can have on one chip, and true for the sound program.

The limits here are this small size's own, set from the sound run's
readings (grad and update gaps under 0.07, first-round loss gap under
2e-4 in bfloat16) with room above them."""
import jax
import pytest

import run as bench
from conftest import TINY, TINY_MIX

LIMITS = {"loss0_gap": 2e-3, "grad_gap": 0.15, "update_gap": 0.15}


def cell():
    return dict(name="tiny", chips=1, c=dict(TINY), mix=dict(TINY_MIX),
                limits=dict(LIMITS), end_to_end=["round_s"], per_layer=[])


def unchanged(make):
    def build(fed, loss, *a, **k):
        inner = make(fed, loss, *a, **k)

        def round_fn(state, batches, *args, **kw):
            _, mets = inner(state, batches, *args, **kw)
            return state, mets
        return round_fn
    return build


def half_batch(make):
    def build(fed, loss, *a, **k):
        inner = make(fed, loss, *a, **k)

        def round_fn(state, batches, *args, **kw):
            half = jax.tree.map(lambda x: x[:, :x.shape[1] // 2], batches)
            return inner(state, half, *args, **kw)
        return round_fn
    return build


@pytest.mark.parametrize("fault", [None, unchanged, half_batch])
def test_correct_only_for_the_sound_round(monkeypatch, fault):
    from repro.core import fed
    if fault is not None:
        monkeypatch.setattr(fed, "make_fl_round", fault(fed.make_fl_round))
    res = bench.run(cell(), 2**31 + 5, 0.2, False, require_chip=False)
    assert res["correct"] is (fault is None), res["checks"]
    assert list(res)[-1] == "checks"


def test_control_fails():
    """The control: the reference in float8 wherever the configuration
    holds bfloat16, put in the program's place, is not correct."""
    import jax.numpy as jnp
    import check
    import reference
    import traffic
    c, mix, seed = dict(TINY), dict(TINY_MIX), 2**31 + 5
    batches = [jax.tree.map(jnp.asarray, traffic.client_batches(
        mix, c["vocab_size"], c["hidden_size"], c["encoder_frames"], seed,
        r)) for r in range(mix["check_rounds"])]
    n = mix["check_rounds"]
    ref = reference.run(c, mix, seed, batches, n)
    control = reference.run(c, mix, seed, batches, n, mode="fp8")
    ok, checks = check.judge(check.numbers(control, ref), LIMITS)
    assert not ok, checks
    assert checks["loss0_gap"]["value"] > 3 * LIMITS["loss0_gap"]
