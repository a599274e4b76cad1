"""Plain float32 reference of one FedAdam-SSM round, for the comparison
that decides ``correct``.  It imports nothing of the program.

Model: the decoder stack the program runs for whisper-base and
starcoder2-3b (configuration files under ``configs/``, and
``tests/data/`` for a configuration with no cell yet): RMSNorm, rotary
self-attention with grouped KV heads, cross-attention to an encoder of
the same layers (whisper), a GELU MLP, an untied head over the padded
vocabulary, next-token cross-entropy.  Each departure from the published
model is listed in its configuration file.

Round (arXiv:2405.17932, Algorithms 1 and 2): every client starts from
the global (W, M, V), runs L Adam steps without bias correction (eps
inside the square root), forms (dW, dM, dV), keeps in all three the
entries that the configuration's shared threshold mask selects, and the
server adds the mean of the kept deltas to (W, M, V).  The threshold
mask of a tensor of n entries keeps every entry whose |dW| is at least
the k-th largest, k = round(alpha n), ties included; the payload holds
at most k + min(6% k + 8, n - k) of them, the first in flat order
(docs/wire.md).  In bfloat16 many deltas tie, and where fewer than k
are nonzero the threshold is zero.

Arithmetic is float32 with every matmul at HIGHEST precision.  The
state is stored in the configuration's dtypes (bfloat16 weights and
moments, float32 norm scales), as the configuration states, so the
reference rounds its state where the configuration's storage does
(through :func:`_narrow`, which the TPU compiler cannot skip).
Weights are made here from the seed with the program's published
initialisation recipe (normal 0.02, or 1/sqrt(fan_in) for projections,
one key per leaf in flattened order).

The round runs as small compiled pieces (:class:`Round`), so that a
configuration of 535M parameters fits one 16 GB chip: the device holds
the global state, one client's, one float32 gradient and one leaf's
float32 temporaries at a time, and each client's kept deltas wait on the
host in their stored dtype until the last client is done.  The pieces
compute what one program of the whole round computes, in the same order.

``mode="fp8"`` is the control: the same reference in float8_e4m3fn
(per-tensor scale) wherever the configuration holds bfloat16: the
stored weights, moments and deltas, matmul operands and outputs, norm
outputs, the MLP's hidden activation, the residual stream and the
embeddings; attention's scores and softmax stay float32, as the
configuration keeps them.  ``fault`` plants a fault where the program would make it:
``"unchanged"`` returns the state it was given, ``"half_batch"`` trains
each client on the first half of its batch only (of its one sequence's
tokens, where the batch holds one).
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

F32 = jnp.float32
HIGHEST = lax.Precision.HIGHEST
FP8_MAX = 448.0                     # float8_e4m3fn's largest finite value
BETA1, BETA2, EPS = 0.9, 0.999, 1e-6


def padded_vocab(vocab: int) -> int:
    return -(-vocab // 256) * 256


class Leaf:
    """One parameter: shape, initialisation and storage dtype."""

    def __init__(self, shape, init="normal", fan_in=None, dtype="bfloat16"):
        self.shape, self.init, self.fan_in = tuple(shape), init, fan_in
        self.dtype = dtype


def _stack(tree, n):
    return jax.tree.map(
        lambda l: Leaf((n,) + l.shape, l.init, l.fan_in, l.dtype), tree)


def param_spec(c: dict) -> dict:
    """The parameter tree, in the program's flattened order."""
    d, dt = c["hidden_size"], c["dtype"]
    H, K, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                c["head_dim"])
    norm = lambda: {"scale": Leaf((d,), "ones", dtype="float32")}

    def attn(kv_heads):
        return {"wq": Leaf((d, H, hd), "scaled", d, dt),
                "wk": Leaf((d, kv_heads, hd), "scaled", d, dt),
                "wv": Leaf((d, kv_heads, hd), "scaled", d, dt),
                "wo": Leaf((H, hd, d), "scaled", H * hd, dt)}

    def mlp(f):
        return {"w_up": Leaf((d, f), "scaled", d, dt),
                "w_down": Leaf((f, d), "scaled", f, dt)}

    block = {"norm_mixer": norm(), "mixer": attn(K), "norm_ffn": norm(),
             "ffn": mlp(c["intermediate_size"])}
    if c["encoder_layers"]:
        block["cross"] = attn(K)
        block["norm_cross"] = norm()
    vp = padded_vocab(c["vocab_size"])
    tree = {"embed": Leaf((vp, d), "normal", dtype=dt),
            "blocks": (_stack(_stack(block, 1), c["num_hidden_layers"]),),
            "final_norm": norm()}
    if not c["tie_word_embeddings"]:
        tree["lm_head"] = Leaf((d, vp), "scaled", d, dt)
    if c["encoder_layers"]:
        enc = {"norm_mixer": norm(), "mixer": attn(K), "norm_ffn": norm(),
               "ffn": mlp(4 * d)}
        tree["encoder"] = {"blocks": _stack(enc, c["encoder_layers"]),
                           "final_norm": norm()}
    return tree


def init_params(c: dict, key):
    """Weights from ``key``, in their storage dtypes."""
    leaves, treedef = jax.tree_util.tree_flatten(param_spec(c))
    keys = jax.random.split(key, len(leaves))

    def one(l, k):
        if l.init == "ones":
            return jnp.ones(l.shape, l.dtype)
        std = 0.02 if l.init == "normal" else 1.0 / math.sqrt(l.fan_in)
        return (jax.random.normal(k, l.shape, F32) * std).astype(l.dtype)

    return jax.tree_util.tree_unflatten(
        treedef, [one(l, k) for l, k in zip(leaves, keys)])


# ---------------------------------------------------------------------------
# Model
# ---------------------------------------------------------------------------


def _narrow(x, dtype):
    """``x`` stored in ``dtype``, rounded there.  The TPU compiler drops a
    round trip through a narrower type whose two casts share a fusion
    (excess precision), so the rounded value is fenced off: the barrier
    makes it a value of its own, stored in ``dtype``."""
    return lax.optimization_barrier(x.astype(dtype))


def _q8(x):
    """x rounded to float8_e4m3fn under a per-tensor scale; the gradient
    passes straight through the rounding."""
    scale = jnp.max(jnp.abs(x)) / FP8_MAX + 1e-30
    q = _narrow(x / scale, jnp.float8_e4m3fn).astype(F32) * scale
    return x + lax.stop_gradient(q - x)


def _act(x, mode):
    """An activation where the configuration holds one in its dtype: in
    the control, rounded to float8."""
    return _q8(x) if mode == "fp8" else x


def _mm(eq, a, b, mode):
    if mode == "fp8":
        a, b = _q8(a), _q8(b)
    return _act(jnp.einsum(eq, a, b, precision=HIGHEST), mode)


def _rmsnorm(p, x, eps, mode="f32"):
    return _act(x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
                * p["scale"], mode)


def _rope(x, theta):
    """x: (b, s, heads, hd); positions 0..s-1."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(x.shape[1], dtype=F32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(p, x, src, c, mode, *, causal, rope, rows=512):
    """Softmax attention, computed in blocks of ``rows`` query rows."""
    b, s, _ = x.shape
    K, hd = c["num_key_value_heads"], c["head_dim"]
    g = c["num_attention_heads"] // K
    q = _mm("bsd,dhk->bshk", x, p["wq"], mode)
    k = _mm("bsd,dhk->bshk", src, p["wk"], mode)
    v = _mm("bsd,dhk->bshk", src, p["wv"], mode)
    if rope:
        q, k = _rope(q, c["rope_theta"]), _rope(k, c["rope_theta"])
    q = q.reshape(b, s, K, g, hd) / math.sqrt(hd)
    rows = rows if s % rows == 0 else s

    @jax.checkpoint
    def block(i):
        qb = lax.dynamic_slice_in_dim(q, i * rows, rows, 1)
        sc = jnp.einsum("bqkgh,bckh->bkgqc", qb, k, precision=HIGHEST)
        if causal:
            qpos = i * rows + jnp.arange(rows)
            mask = jnp.arange(k.shape[1])[None, :] <= qpos[:, None]
            sc = jnp.where(mask, sc, -jnp.inf)
        pr = jax.nn.softmax(sc, -1)
        return jnp.einsum("bkgqc,bckh->bqkgh", pr, v, precision=HIGHEST)

    out = lax.map(block, jnp.arange(s // rows))      # (nb, b, rows, K, g, hd)
    out = _act(jnp.moveaxis(out, 0, 1).reshape(b, s, -1), mode)
    return _mm("bsk,kd->bsd", out, p["wo"].reshape(-1, x.shape[-1]), mode)


def _mlp(p, x, mode):
    h = _act(jax.nn.gelu(_mm("bsd,df->bsf", x, p["w_up"], mode)), mode)
    return _mm("bsf,fd->bsd", h, p["w_down"], mode)


def _encoder(p, frames, c, mode):
    eps = c["norm_eps"]

    def layer(x, lp):
        h = _rmsnorm(lp["norm_mixer"], x, eps, mode)
        x = _act(x + _attention(lp["mixer"], h, h, c, mode, causal=False,
                                rope=True), mode)
        return _act(x + _mlp(lp["ffn"], _rmsnorm(lp["norm_ffn"], x, eps,
                                                 mode), mode), mode), None

    x, _ = lax.scan(jax.checkpoint(layer), _act(frames, mode), p["blocks"])
    return _rmsnorm(p["final_norm"], x, eps, mode)


def loss(params, tokens, embeds, c, mode="f32"):
    """Mean next-token cross-entropy of float32 ``params``."""
    eps = c["norm_eps"]
    x = _act(jnp.take(params["embed"], tokens, axis=0), mode)
    enc = None if embeds is None else _encoder(params["encoder"], embeds, c,
                                               mode)

    def layer(x, lp):
        lp = jax.tree.map(lambda a: a[0], lp)          # group of one layer
        h = _rmsnorm(lp["norm_mixer"], x, eps, mode)
        x = _act(x + _attention(lp["mixer"], h, h, c, mode, causal=True,
                                rope=True), mode)
        if enc is not None:
            h = _rmsnorm(lp["norm_cross"], x, eps, mode)
            x = _act(x + _attention(lp["cross"], h, enc, c, mode,
                                    causal=False, rope=False), mode)
        return _act(x + _mlp(lp["ffn"], _rmsnorm(lp["norm_ffn"], x, eps,
                                                 mode), mode), mode), None

    x, _ = lax.scan(jax.checkpoint(layer), x, params["blocks"][0])
    x = _rmsnorm(params["final_norm"], x, eps, mode)
    head = params["embed"].T if c["tie_word_embeddings"] \
        else params["lm_head"]
    return _cross_entropy(x[:, :-1], head, tokens[:, 1:], mode)


def _cross_entropy(x, head, tgt, mode, rows=512):
    """Mean cross-entropy of the logits ``x @ head``, computed in blocks
    of ``rows`` positions so that the logits of one block live at once."""
    b, s, d = x.shape
    pad = -s % rows if s > rows else 0
    n = (s + pad) // min(rows, s + pad)
    xs = jnp.pad(x, ((0, 0), (0, pad), (0, 0))).reshape(b, n, -1, d)
    ts = jnp.pad(tgt, ((0, 0), (0, pad))).reshape(b, n, -1)
    valid = (jnp.arange(s + pad) < s).reshape(n, -1)

    @jax.checkpoint
    def block(args):
        xb, tb, vb = args
        logits = _mm("bsd,dv->bsv", xb, head, mode)
        picked = jnp.take_along_axis(logits, tb[..., None], -1)[..., 0]
        return jnp.sum(jnp.where(vb, jax.nn.logsumexp(logits, -1) - picked,
                                 0.0))

    total = jnp.sum(lax.map(block, (jnp.moveaxis(xs, 1, 0),
                                    jnp.moveaxis(ts, 1, 0), valid)))
    return total / (b * s)


# ---------------------------------------------------------------------------
# The round
# ---------------------------------------------------------------------------


class State(NamedTuple):
    W: dict
    M: dict
    V: dict


def kth_largest(a, k: int):
    """The k-th largest value of ``a >= 0`` (any shape), exactly: a
    bisection on the float bits, which order like the values."""
    bits = lax.bitcast_convert_type(a.reshape(-1), jnp.int32)

    def body(_, lohi):
        lo, hi = lohi
        mid = lo + (hi - lo) // 2
        ok = jnp.sum(bits >= mid) >= k
        return jnp.where(ok, mid, lo), jnp.where(ok, hi, mid)

    lo, _ = lax.fori_loop(0, 32, body, (jnp.int32(0), jnp.max(bits) + 1))
    return lax.bitcast_convert_type(lo, F32)


def capacity(n: int, k: int) -> int:
    """Entries a leaf's payload holds: k plus the contracted over-selection
    of the threshold mask, min(6% k + 8, n - k) (docs/wire.md)."""
    return min(n, k + min(int(0.06 * k) + 8, n - k))


def threshold_mask(dW, dM, dV, k: int):
    """The configuration's shared threshold mask on one leaf: every entry
    whose |dW| is at least the k-th largest (ties kept), of which the
    payload carries the first ``capacity`` in flat order that have a
    nonzero delta in any of the three."""
    a = jnp.abs(dW.astype(F32))
    sel = (a >= kth_largest(a, k)) & ((dW != 0) | (dM != 0) | (dV != 0))
    flat = sel.reshape(-1)
    keep = flat & (jnp.cumsum(flat.astype(jnp.int32))
                   <= capacity(a.size, k))
    return keep.reshape(a.shape)


def _cast(tree32, like, mode="f32"):
    """Store float32 values in the dtypes of ``like``; in the control,
    what the configuration stores in bfloat16 is first rounded to
    float8."""
    def one(x, l):
        if mode == "fp8" and l.dtype == jnp.bfloat16:
            x = _q8(x)
        return _narrow(x, l.dtype)
    return jax.tree.map(one, tree32, like)


def _up(tree):
    return jax.tree.map(lambda x: x.astype(F32), tree)


def _grad(w, tokens, embeds, i, c, mode, fault):
    """Loss and float32 gradient of client ``i``'s batch at the weights
    ``w`` (stored dtypes, taken to float32)."""
    tokens = lax.dynamic_index_in_dim(tokens, i, keepdims=False)
    if embeds is not None:
        embeds = lax.dynamic_index_in_dim(embeds, i, keepdims=False)
    if fault == "half_batch" and tokens.shape[0] > 1:
        half = tokens.shape[0] // 2
        tokens = tokens[:half]
        embeds = None if embeds is None else embeds[:half]
    elif fault == "half_batch":                 # one row: half its tokens
        tokens = tokens[:, :tokens.shape[1] // 2]
    return jax.value_and_grad(loss)(_up(w), tokens, embeds, c, mode)


def _adam(w, m, v, g, lr, mode):
    """One leaf's Adam step without bias correction (eps inside the
    square root), its new values stored in the leaf's dtypes."""
    mf = BETA1 * m.astype(F32) + (1 - BETA1) * g
    vf = BETA2 * v.astype(F32) + (1 - BETA2) * g * g
    w = _cast(w.astype(F32) - lr * mf / jnp.sqrt(vf + EPS), w, mode)
    return w, _cast(mf, m, mode), _cast(vf, v, mode)


def _kept(w, W, m, M, v, V, alpha, mode):
    """One leaf's (dW, dM, dV), stored in the leaf's dtype, with the
    entries the shared threshold mask does not keep set to zero."""
    delta = lambda a, b: _cast(a.astype(F32) - b.astype(F32), b, mode)
    dW, dM, dV = delta(w, W), delta(m, M), delta(v, V)
    mask = threshold_mask(dW, dM, dV, max(1, int(round(alpha * dW.size))))
    return tuple(jnp.where(mask, d, jnp.zeros((), d.dtype))
                 for d in (dW, dM, dV))


def _apply(T, kept, mode):
    """One leaf of the new state: ``T + (0 + s_1 + ... + s_n) / n`` in
    float32, the clients' kept deltas ``kept`` (n, ...) added in order,
    stored in ``T``'s dtype."""
    def add(acc, s):
        return acc + s.astype(F32), None

    acc, _ = lax.scan(add, jnp.zeros(T.shape, F32), kept)
    return _cast(T.astype(F32) + acc / kept.shape[0], T, mode)


def _mean(losses):
    return jnp.mean(jnp.stack(losses))


class Round:
    """``Round(c, mix, mode, fault)(state, batch) -> (state, [loss of
    each client])``: the clients run in sequence and their kept deltas are
    summed in float32.

    The round runs as a sequence of small compiled pieces, so that the
    device holds the global state, one client's (w, m, v), its float32
    gradient and the float32 temporaries of one leaf at a time: the
    gradient of one local step (``grad``); each leaf's Adam step
    (``adam``); each leaf's deltas, shared mask and kept deltas
    (``kept``), which go to the host in the leaf's dtype (exact: they
    are stored there); and, after the last client, each leaf's sum over
    the clients and its update (``apply``).  The arithmetic and its
    order are those of a round in one program: the same expressions per
    leaf, the float32 sum ``0 + s_1 + s_2 + ...`` in client order."""

    def __init__(self, c: dict, mix: dict, mode: str = "f32",
                 fault: Optional[str] = None):
        self.mix, self.fault = mix, fault
        self.fns = {
            "grad": jax.jit(functools.partial(_grad, c=c, mode=mode,
                                              fault=fault)),
            "adam": jax.jit(functools.partial(_adam, lr=mix["lr"],
                                              mode=mode)),
            "kept": jax.jit(functools.partial(_kept, alpha=mix["alpha"],
                                              mode=mode)),
            "apply": jax.jit(functools.partial(_apply, mode=mode)),
            "mean": jax.jit(_mean),
        }

    def __call__(self, state: State, batch: dict):
        f = self.fns
        tokens, embeds = batch["tokens"], batch.get("embeds")
        n = tokens.shape[0]
        glob = [jax.tree_util.tree_leaves(t) for t in state]
        treedef = jax.tree_util.tree_structure(state.W)
        host = [[[], [], []] for _ in glob[0]]     # per leaf, tree: clients
        losses = []
        for i in range(n):
            w, m, v = (list(t) for t in glob)
            step_losses = []
            for _ in range(self.mix["local_epochs"]):
                l, g = f["grad"](jax.tree_util.tree_unflatten(treedef, w),
                                 tokens, embeds, jnp.int32(i))
                step_losses.append(l)
                g = jax.tree_util.tree_leaves(g)
                for j in range(len(w)):
                    w[j], m[j], v[j] = f["adam"](w[j], m[j], v[j], g[j])
                    g[j] = None
            losses.append(f["mean"](step_losses))
            if self.fault == "unchanged":
                continue
            for j in range(len(w)):
                kept = f["kept"](w[j], glob[0][j], m[j], glob[1][j], v[j],
                                 glob[2][j])
                for t, x in enumerate(jax.device_get(kept)):
                    host[j][t].append(x)
                w[j] = m[j] = v[j] = None
        if self.fault == "unchanged":
            return state, losses
        new = [[] for _ in glob]
        for j in range(len(host)):
            for t in range(3):
                new[t].append(f["apply"](glob[t][j], np.stack(host[j][t])))
                host[j][t] = None
        return State(*(jax.tree_util.tree_unflatten(treedef, leaves)
                       for leaves in new)), losses

    def pieces(self, state, batch) -> list:
        """``[(name, jitted function, arguments)]``: each distinct
        compiled call that a round on ``state`` and ``batch`` (arrays or
        ``jax.ShapeDtypeStruct`` s) makes, with arguments of its shapes."""
        f, sds = self.fns, jax.ShapeDtypeStruct
        n = batch["tokens"].shape[0]
        out = [("grad", f["grad"], (state.W, batch["tokens"],
                                    batch.get("embeds"),
                                    sds((), jnp.int32))),
               ("mean", f["mean"], ([sds((), F32)]
                                    * self.mix["local_epochs"],))]
        seen = set()
        for x in jax.tree_util.tree_leaves(state.W):
            like = sds(x.shape, x.dtype)
            if (x.shape, x.dtype) in seen:
                continue
            seen.add((x.shape, x.dtype))
            out.append(("adam", f["adam"],
                        (like, like, like, sds(x.shape, F32))))
            if self.fault != "unchanged":
                out += [("kept", f["kept"], (like,) * 6),
                        ("apply", f["apply"],
                         (like, sds((n,) + x.shape, x.dtype)))]
        return out


def leaf_norms(tree):
    """Float32 L2 norm of every leaf, in flattened order."""
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(F32))))
                      for x in jax.tree.leaves(tree)])


def change_norms(W, W0):
    return leaf_norms(jax.tree.map(
        lambda a, b: a.astype(F32) - b.astype(F32), W, W0))


def _to_mode(W, mode):
    """The control's weights: rounded to float8 where the configuration
    holds bfloat16 (a program of its own, so that the rounding of the
    initialisation to bfloat16 stays)."""
    return _cast(_up(W), W, mode)


def _zeros(W):
    return jax.tree.map(jnp.zeros_like, W)


def run(c: dict, mix: dict, seed: int, batches, rounds: int,
        mode: str = "f32", fault: Optional[str] = None) -> dict:
    """The reference's readings over the first ``rounds`` rounds: the
    per-client losses of each round, the norm of every leaf of M after
    the first round and of W's change after the last."""
    W0 = jax.jit(lambda k: init_params(c, k))(jax.random.PRNGKey(seed))
    if mode == "fp8":
        W0 = jax.jit(functools.partial(_to_mode, mode=mode))(W0)
    zeros = jax.jit(_zeros)(W0)
    state = State(W0, zeros, zeros)
    W0 = jax.device_get(W0)             # on the host until the end
    step = Round(c, mix, mode, fault)
    losses, m1 = [], None
    for r in range(rounds):
        state, l = step(state, batches[r])
        losses.append(jax.device_get(l))
        if r == 0:
            m1 = jax.device_get(jax.jit(leaf_norms)(state.M))
    dw = jax.device_get(jax.jit(change_norms)(state.W, W0))
    return {"losses": [list(map(float, l)) for l in losses],
            "m1_norms": [float(x) for x in m1],
            "dw_norms": [float(x) for x in dw]}


def pieces(c: dict, mix: dict, batch: dict, mode: str = "f32",
           fault: Optional[str] = None) -> list:
    """``[(name, jitted function, arguments)]``: each distinct compiled
    call of :func:`run` on batches shaped like ``batch`` (arrays or
    ``jax.ShapeDtypeStruct`` s), with ``jax.ShapeDtypeStruct`` arguments;
    what compiling each for the chip plans is what the reference needs
    there (``rehearse.py --reference``)."""
    sds = jax.ShapeDtypeStruct
    init = jax.jit(lambda k: init_params(c, k))
    key = jax.random.PRNGKey(0)
    W = jax.eval_shape(init, key)
    state = State(W, W, W)
    out = [("init", init, (sds(key.shape, key.dtype),))]
    if mode == "fp8":
        out.append(("to_mode", jax.jit(functools.partial(_to_mode,
                                                         mode=mode)), (W,)))
    return (out + [("zeros", jax.jit(_zeros), (W,))]
            + Round(c, mix, mode, fault).pieces(state, batch)
            + [("leaf_norms", jax.jit(leaf_norms), (W,)),
               ("change_norms", jax.jit(change_norms), (W, W))])
