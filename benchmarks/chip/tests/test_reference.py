"""The reference against the program at a small size on the CPU: the
same weights from the same seed, the same losses in float32, and gaps
in bfloat16 that stay far under those of the planted faults.  The
reference's round, run as pieces, against the same round in one
program: the same weights and moments bit for bit, the same losses to
1e-6."""

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
# decoder only, the head tied, 2 query heads per KV head (starcoder2-3b's
# kind of model)
TINY_GQA = dict(TINY, name="tiny-gqa", encoder_layers=0, encoder_frames=0,
                tie_word_embeddings=True)
F32 = jnp.float32


def one_program_round(c, mix, mode="f32", fault=None):
    """The reference's round as one jitted program: the clients in a
    ``lax.scan`` and every leaf at once (the oracle of ``R.Round``)."""

    def client_update(W, M, V, tokens, embeds):
        if fault == "half_batch" and tokens.shape[0] > 1:
            half = tokens.shape[0] // 2
            tokens = tokens[:half]
            embeds = None if embeds is None else embeds[:half]
        elif fault == "half_batch":
            tokens = tokens[:, :tokens.shape[1] // 2]
        lr = mix["lr"]
        w, m, v = W, M, V
        losses = []
        for _ in range(mix["local_epochs"]):
            l, g = jax.value_and_grad(R.loss)(R._up(w), tokens, embeds, c,
                                              mode)
            losses.append(l)
            mf = jax.tree.map(lambda a, b: R.BETA1 * a.astype(F32)
                              + (1 - R.BETA1) * b, m, g)
            vf = jax.tree.map(lambda a, b: R.BETA2 * a.astype(F32)
                              + (1 - R.BETA2) * b * b, v, g)
            w = R._cast(jax.tree.map(
                lambda a, mm, vv: a.astype(F32)
                - lr * mm / jnp.sqrt(vv + R.EPS), w, mf, vf), w, mode)
            m, v = R._cast(mf, m, mode), R._cast(vf, v, mode)
        delta = lambda a, b: R._cast(jax.tree.map(
            lambda x, y: x.astype(F32) - y.astype(F32), a, b), b, mode)
        dW, dM, dV = delta(w, W), delta(m, M), delta(v, V)
        mask = jax.tree.map(
            lambda w, m_, v_: R.threshold_mask(
                w, m_, v_, max(1, int(round(mix["alpha"] * w.size)))),
            dW, dM, dV)
        keep = lambda t: jax.tree.map(
            lambda x, mk: jnp.where(mk, x.astype(F32), 0.0), t, mask)
        return keep(dW), keep(dM), keep(dV), jnp.mean(jnp.stack(losses))

    def one_round(state, batch):
        def body(acc, xs):
            sW, sM, sV, l = client_update(*state, *xs)
            add = lambda a, s: jax.tree.map(jnp.add, a, s)
            return (add(acc[0], sW), add(acc[1], sM), add(acc[2], sV)), l

        zero = jax.tree.map(lambda x: jnp.zeros(x.shape, F32), state.W)
        (aW, aM, aV), losses = jax.lax.scan(
            body, (zero, zero, zero), (batch["tokens"], batch.get("embeds")))
        if fault == "unchanged":
            return state, losses
        n = batch["tokens"].shape[0]
        apply = lambda T, A: R._cast(jax.tree.map(
            lambda t, a: t.astype(F32) + a / n, T, A), T, mode)
        return R.State(apply(state.W, aW), apply(state.M, aM),
                       apply(state.V, aV)), losses

    return jax.jit(one_round)


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


def device_batches(c, mix, seed):
    return [jax.tree.map(jnp.asarray, traffic.client_batches(
        mix, c["vocab_size"], c["hidden_size"], c["encoder_frames"], seed,
        r)) for r in range(mix["check_rounds"])]


@pytest.mark.parametrize("c,mode,fault", [
    (TINY, "f32", None), (TINY_GQA, "f32", None), (TINY, "fp8", None),
    (TINY_GQA, "fp8", None), (TINY, "f32", "half_batch"),
    (TINY_GQA, "f32", "half_batch"), (TINY, "f32", "unchanged")])
def test_pieces_read_what_one_program_reads(monkeypatch, c, mode, fault):
    """The same weights and moments bit for bit; the losses within 1e-6
    (the compiler sums a loss inside the clients' loop in another order
    than in a program of its own: one float32 ulp here)."""
    mix = dict(TINY_MIX, batch=1) if fault == "half_batch" and \
        c is TINY_GQA else TINY_MIX
    batches = device_batches(c, mix, SEED)
    n = mix["check_rounds"]
    got = R.run(c, mix, SEED, batches, n, mode, fault)
    monkeypatch.setattr(R, "Round", one_program_round)
    want = R.run(c, mix, SEED, batches, n, mode, fault)
    assert got["m1_norms"] == want["m1_norms"]
    assert got["dw_norms"] == want["dw_norms"]
    np.testing.assert_allclose(got["losses"], want["losses"], rtol=1e-6,
                               atol=0)


@pytest.mark.parametrize("c,mode,fault", [
    (TINY, "f32", None), (TINY_GQA, "fp8", None),
    (TINY_GQA, "f32", "unchanged")])
def test_pieces_list_every_compiled_call(monkeypatch, c, mode, fault):
    """``R.pieces`` names each distinct compiled call a run makes, with
    its argument shapes: what ``rehearse.py --reference`` compiles."""
    batches = device_batches(c, TINY_MIX, SEED)
    sig = lambda args: tuple(
        (tuple(x.shape), str(x.dtype)) for x in jax.tree.leaves(args))
    listed = {(name, sig(args)) for name, _, args in
              R.pieces(c, TINY_MIX, batches[0], mode, fault)}
    called = set()

    class Recording:
        """``jax`` as the reference sees it, its ``jit`` recording calls."""

        def __getattr__(self, name):
            return getattr(jax, name)

        @staticmethod
        def jit(fn, *a, **k):
            inner = jax.jit(fn, *a, **k)
            name = getattr(fn, "__name__", None) or fn.func.__name__

            def call(*args):
                called.add((name, sig(args)))
                return inner(*args)
            return call
    monkeypatch.setattr(R, "jax", Recording())
    R.run(c, TINY_MIX, SEED, batches, 2, mode, fault)
    called = {("init" if n == "<lambda>" else n.lstrip("_"), s)
              for n, s in called}
    assert called == listed
