"""Model layers: norms, RoPE, attention (GQA / MLA / windowed), MLP, MoE,
Mamba-2 (SSD) — pure JAX, shardable, scan-friendly.

Conventions
-----------
* every layer has ``<name>_params(cfg-ish) -> pytree[P]`` and a forward fn
  taking the materialized pytree;
* activations are (batch, seq, d_model) in the model dtype; softmax /
  normalization statistics accumulate in float32;
* training attention uses an online-softmax scan over KV chunks so the
  lowered HLO never materializes a (seq x seq) score tensor;
* decode functions process exactly one new token against a cache.
"""
from __future__ import annotations

import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import AttentionSpec, LayerSpec, MoESpec, SSMSpec
from repro.models.params import P
from repro.sharding import hint

NEG_INF = -1e9          # finite mask value (see online-softmax notes)
_F32 = jnp.float32


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def rmsnorm_params(d: int):
    return {"scale": P((d,), ("embed",), init="ones", dtype="float32")}


def rmsnorm(p, x, eps: float = 1e-6):
    xf = x.astype(_F32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * lax.rsqrt(var + eps) * p["scale"].astype(_F32)
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope(x, positions, theta: float):
    """x: (..., seq, heads..., head_dim); positions: (..., seq) int32."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=_F32) / half)
    ang = positions.astype(_F32)[..., None] * freqs          # (..., seq, half)
    # insert singleton dims for the head axes between seq and head_dim
    extra = x.ndim - positions.ndim - 1
    for _ in range(extra):
        ang = ang[..., None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention — online-softmax over KV chunks (training / prefill)
# ---------------------------------------------------------------------------


def _chunk_mask(qpos, kpos, *, causal: bool, window: Optional[int],
                kv_valid_len=None):
    """qpos: (sq,), kpos: (L,) -> bool (sq, L)."""
    m = jnp.ones((qpos.shape[0], kpos.shape[0]), bool)
    if causal:
        m &= kpos[None, :] <= qpos[:, None]
    if window is not None:
        m &= kpos[None, :] > qpos[:, None] - window
    if kv_valid_len is not None:
        m &= kpos[None, :] < kv_valid_len
    return m


def chunked_attention(q, k, v, *, causal=True, window=None, q_offset=0,
                      kv_valid_len=None, chunk=1024):
    """Online-softmax attention.

    q: (b, sq, nkv, g, hd) — GQA groups g = heads/kv_heads folded explicitly.
    k, v: (b, skv, nkv, hd).
    Returns (b, sq, nkv, g, hd) in q.dtype.
    """
    b, sq, nkv, g, hd = q.shape
    skv = k.shape[1]
    if skv % chunk:
        chunk = skv                                   # single-shot fallback
    nchunks = skv // chunk
    scale = 1.0 / math.sqrt(hd)
    qf = q.astype(_F32) * scale
    qpos = q_offset + jnp.arange(sq)

    ks = k.reshape(b, nchunks, chunk, nkv, hd)
    vs = v.reshape(b, nchunks, chunk, nkv, hd)

    @jax.checkpoint
    def body(carry, inp):
        # rematerialized in backward: the (b, nkv, g, sq, chunk) score
        # tensor is the single largest training activation — recomputing it
        # costs one extra QK^T einsum per chunk and saves its storage.
        m, l, acc = carry                              # m,l: (b,nkv,g,sq)
        kc, vc, j = inp                                # kc: (b,chunk,nkv,hd)
        s = jnp.einsum("bqkgh,bckh->bkgqc", qf, kc.astype(_F32))
        kpos = j * chunk + jnp.arange(chunk)
        mask = _chunk_mask(qpos, kpos, causal=causal, window=window,
                           kv_valid_len=kv_valid_len)
        s = jnp.where(mask[None, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + p.sum(-1)
        upd = jnp.einsum("bkgqc,bckh->bkgqh", p, vc.astype(_F32))
        acc = acc * alpha[..., None] + upd
        return (m_new, l_new, acc), None

    m0 = jnp.full((b, nkv, g, sq), -1e30, _F32)
    l0 = jnp.zeros((b, nkv, g, sq), _F32)
    a0 = jnp.zeros((b, nkv, g, sq, hd), _F32)
    (m, l, acc), _ = lax.scan(
        body, (m0, l0, a0),
        (jnp.moveaxis(ks, 1, 0), jnp.moveaxis(vs, 1, 0), jnp.arange(nchunks)))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return jnp.moveaxis(out, 3, 1).astype(q.dtype)      # (b, sq, nkv, g, hd)


def decode_attention(q, k_cache, v_cache, *, pos, window=None,
                     ring: bool = False):
    """One-token attention against a cache.

    q: (b, nkv, g, hd); caches: (b, S, nkv, hd); pos: scalar int32 — index of
    the *current* token (already written into the cache).
    ring=True: cache is a ring buffer of size S=window written at t % S.
    """
    b, S, nkv, hd = k_cache.shape
    scale = 1.0 / math.sqrt(hd)
    s = jnp.einsum("bkgh,bskh->bkgs", q.astype(_F32) * scale,
                   k_cache.astype(_F32))
    slots = jnp.arange(S)
    if ring:
        # slot s holds global position pos - ((pos - s) mod S); valid iff >= 0
        gpos = pos - jnp.mod(pos - slots, S)
        valid = gpos >= 0
    else:
        valid = slots <= pos
        if window is not None:
            valid &= slots > pos - window
    s = jnp.where(valid[None, None, None], s, NEG_INF)
    s = s - s.max(-1, keepdims=True)
    p = jnp.exp(s)
    p = p / jnp.maximum(p.sum(-1, keepdims=True), 1e-30)
    out = jnp.einsum("bkgs,bskh->bkgh", p, v_cache.astype(_F32))
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# GQA attention layer
# ---------------------------------------------------------------------------


def attention_params(d: int, a: AttentionSpec, cross: bool = False):
    if a.is_mla:
        return mla_params(d, a)
    p = {
        "wq": P((d, a.num_heads, a.head_dim), ("embed", "heads", "head_dim"),
                init="scaled", fan_in=d),
        "wk": P((d, a.num_kv_heads, a.head_dim), ("embed", "kv_heads", "head_dim"),
                init="scaled", fan_in=d),
        "wv": P((d, a.num_kv_heads, a.head_dim), ("embed", "kv_heads", "head_dim"),
                init="scaled", fan_in=d),
        "wo": P((a.num_heads, a.head_dim, d), ("heads", "head_dim", "embed"),
                init="scaled", fan_in=a.num_heads * a.head_dim),
    }
    return p


def attention_fwd(p, a: AttentionSpec, x, *, positions, window_override=None,
                  kv=None, kv_valid_len=None, chunk=1024):
    """Training/prefill forward.  x: (b, s, d).  kv: optional (b, skv, d)
    source for cross-attention (encoder states); causal only for self-attn.
    Returns (out, (k, v)) — k/v returned for cache priming."""
    if a.is_mla:
        return mla_fwd(p, a, x, positions=positions, chunk=chunk)
    b, s, _ = x.shape
    src = x if kv is None else kv
    cross = kv is not None
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", src, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", src, p["wv"])
    if not cross:
        q = rope(q, positions, a.rope_theta)
        k = rope(k, positions, a.rope_theta)
    g = a.num_heads // a.num_kv_heads
    qg = q.reshape(b, s, a.num_kv_heads, g, a.head_dim)
    window = a.window if window_override is None else window_override
    out = chunked_attention(qg, k, v, causal=not cross, window=window,
                            kv_valid_len=kv_valid_len, chunk=chunk)
    out = out.reshape(b, s, a.num_heads * a.head_dim)
    wo = p["wo"].reshape(a.num_heads * a.head_dim, -1)
    return jnp.einsum("bsk,kd->bsd", out, wo), (k, v)


def attention_decode(p, a: AttentionSpec, x, cache, *, pos,
                     window_override=None, ring=False):
    """x: (b, 1, d); cache: dict(k,v) (b, S, nkv, hd).  Writes the current
    token into the cache (at pos, or pos % S for ring) then attends."""
    if a.is_mla:
        return mla_decode(p, a, x, cache, pos=pos)
    b = x.shape[0]
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])[:, 0]      # (b, H, hd)
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])[:, 0]
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])[:, 0]
    posv = jnp.full((b, 1), pos, jnp.int32)
    q = rope(q[:, None], posv, a.rope_theta)[:, 0]
    k = rope(k[:, None], posv, a.rope_theta)[:, 0]
    S = cache["k"].shape[1]
    slot = jnp.mod(pos, S) if ring else pos
    k_cache = lax.dynamic_update_slice_in_dim(cache["k"], k[:, None], slot, 1)
    v_cache = lax.dynamic_update_slice_in_dim(cache["v"], v[:, None], slot, 1)
    g = a.num_heads // a.num_kv_heads
    qg = q.reshape(b, a.num_kv_heads, g, a.head_dim)
    window = a.window if window_override is None else window_override
    out = decode_attention(qg, k_cache, v_cache, pos=pos,
                           window=None if ring else window, ring=ring)
    out = out.reshape(b, 1, a.num_heads * a.head_dim)
    wo = p["wo"].reshape(a.num_heads * a.head_dim, -1)
    y = jnp.einsum("bsk,kd->bsd", out, wo)
    return y, {"k": k_cache, "v": v_cache}


def attention_cache(a: AttentionSpec, batch: int, cache_len: int, dtype):
    if a.is_mla:
        return {"ckv": P((batch, cache_len, a.kv_lora_rank),
                         ("batch", "kv_seq", "kv_lora"), init="zeros",
                         dtype=dtype)}
    shape = (batch, cache_len, a.num_kv_heads, a.head_dim)
    axes = ("batch", "kv_seq", "kv_heads", "head_dim")
    return {"k": P(shape, axes, init="zeros", dtype=dtype),
            "v": P(shape, axes, init="zeros", dtype=dtype)}


# ---------------------------------------------------------------------------
# MLA (DeepSeek multi-head latent attention)
# ---------------------------------------------------------------------------


def mla_params(d: int, a: AttentionSpec):
    r = a.kv_lora_rank
    p = {
        "wq": P((d, a.num_heads, a.head_dim), ("embed", "heads", "head_dim"),
                init="scaled", fan_in=d),
        "w_dkv": P((d, r), ("embed", "kv_lora"), init="scaled", fan_in=d),
        "w_uk": P((r, a.num_heads, a.head_dim), ("kv_lora", "heads", "head_dim"),
                  init="scaled", fan_in=r),
        "w_uv": P((r, a.num_heads, a.head_dim), ("kv_lora", "heads", "head_dim"),
                  init="scaled", fan_in=r),
        "wo": P((a.num_heads, a.head_dim, d), ("heads", "head_dim", "embed"),
                init="scaled", fan_in=a.num_heads * a.head_dim),
    }
    return p


def mla_fwd(p, a: AttentionSpec, x, *, positions, chunk=1024):
    """Training: expand the latent to full K/V (naive form).

    NoPE convention (no rotary on the MLA path) so the training math is
    *identical* to the absorbed decode path — the released DeepSeek models
    use a decoupled rope/nope head split instead; see mla_decode notes."""
    b, s, _ = x.shape
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    ckv = jnp.einsum("bsd,dr->bsr", x, p["w_dkv"])         # (b, s, r)
    k = jnp.einsum("bsr,rhk->bshk", ckv, p["w_uk"])
    v = jnp.einsum("bsr,rhk->bshk", ckv, p["w_uv"])
    qg = q.reshape(b, s, a.num_heads, 1, a.head_dim)        # g=1 per head
    out = chunked_attention(qg, k, v, causal=True, chunk=chunk)
    out = out.reshape(b, s, a.num_heads * a.head_dim)
    wo = p["wo"].reshape(a.num_heads * a.head_dim, -1)
    return jnp.einsum("bsk,kd->bsd", out, wo), (ckv,)


def mla_decode(p, a: AttentionSpec, x, cache, *, pos):
    """Decode with the *absorbed* form: scores and context live in the
    latent space, so the cache stores only c_kv (b, S, r).

    NOTE on RoPE: the released DeepSeek models use a decoupled rope/nope
    head split so that rotation commutes with absorption.  We adopt the
    simpler NoPE-in-latent convention for the absorbed path (rope applied
    to q only contributes a head-invariant rotation that we drop), which
    keeps the cache fully compressed; the training path applies full rope.
    Documented in docs/ARCHITECTURE.md §5 as a family-faithful
    simplification.
    """
    b = x.shape[0]
    r = a.kv_lora_rank
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])[:, 0]       # (b, H, hd)
    ckv = jnp.einsum("bsd,dr->bsr", x, p["w_dkv"])          # (b, 1, r)
    cache_ckv = lax.dynamic_update_slice_in_dim(cache["ckv"], ckv, pos, 1)
    # absorb: q_lat[h] = w_uk[.,h,:]^T q[h]  -> (b, H, r)
    q_lat = jnp.einsum("bhk,rhk->bhr", q.astype(_F32),
                       p["w_uk"].astype(_F32))
    scale = 1.0 / math.sqrt(a.head_dim)
    s = jnp.einsum("bhr,bsr->bhs", q_lat * scale, cache_ckv.astype(_F32))
    valid = jnp.arange(cache_ckv.shape[1]) <= pos
    s = jnp.where(valid[None, None], s, NEG_INF)
    s = s - s.max(-1, keepdims=True)
    pr = jnp.exp(s)
    pr = pr / jnp.maximum(pr.sum(-1, keepdims=True), 1e-30)
    ctx_lat = jnp.einsum("bhs,bsr->bhr", pr, cache_ckv.astype(_F32))
    out = jnp.einsum("bhr,rhk->bhk", ctx_lat, p["w_uv"].astype(_F32))
    out = out.reshape(b, 1, a.num_heads * a.head_dim).astype(x.dtype)
    wo = p["wo"].reshape(a.num_heads * a.head_dim, -1)
    return jnp.einsum("bsk,kd->bsd", out, wo), {"ckv": cache_ckv}


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------


def mlp_params(d: int, d_ff: int, gated: bool = True):
    if gated:
        return {
            "w_gate": P((d, d_ff), ("embed", "mlp"), init="scaled", fan_in=d),
            "w_up": P((d, d_ff), ("embed", "mlp"), init="scaled", fan_in=d),
            "w_down": P((d_ff, d), ("mlp", "embed"), init="scaled", fan_in=d_ff),
        }
    return {
        "w_up": P((d, d_ff), ("embed", "mlp"), init="scaled", fan_in=d),
        "w_down": P((d_ff, d), ("mlp", "embed"), init="scaled", fan_in=d_ff),
    }


def mlp_fwd(p, x):
    h = jnp.einsum("bsd,df->bsf", x, p["w_up"])
    if "w_gate" in p:
        gate = jnp.einsum("bsd,df->bsf", x, p["w_gate"])
        h = jax.nn.silu(gate) * h
    else:
        h = jax.nn.gelu(h)
    return jnp.einsum("bsf,fd->bsd", h, p["w_down"])


# ---------------------------------------------------------------------------
# MoE — token-choice top-k with capacity, sort-free cumsum dispatch
# ---------------------------------------------------------------------------


def moe_params(d: int, m: MoESpec):
    p = {
        "router": P((d, m.num_experts), ("embed", "experts"),
                    init="scaled", fan_in=d, dtype="float32"),
        "w_gate": P((m.num_experts, d, m.d_ff), ("experts", "embed", "mlp"),
                    init="scaled", fan_in=d),
        "w_up": P((m.num_experts, d, m.d_ff), ("experts", "embed", "mlp"),
                  init="scaled", fan_in=d),
        "w_down": P((m.num_experts, m.d_ff, d), ("experts", "mlp", "embed"),
                    init="scaled", fan_in=m.d_ff),
    }
    if m.num_shared_experts:
        p["shared"] = mlp_params(d, m.num_shared_experts * m.shared_d_ff)
    return p


def moe_capacity(m: MoESpec, tokens: int) -> int:
    c = int(math.ceil(tokens * m.top_k / m.num_experts * m.capacity_factor))
    return max(8, -(-c // 8) * 8)                          # round up to 8


def moe_fwd(p, m: MoESpec, x):
    """x: (b, s, d) -> (y, aux) with load-balance aux loss.

    Dispatch is PER BATCH ROW (capacity C per sequence): the batch dim is
    the data-sharded axis, so routing never crosses it — each data shard
    dispatches its own rows into an expert buffer whose E dim is sharded
    over "model" (expert parallelism); the only cross-model comm is the
    per-token combine all-reduce, same as any TP layer.  Position-in-expert
    via per-row cumsum over a (s*k, E) one-hot — sort-free.
    """
    b, s, d = x.shape
    E = m.num_experts
    k = m.top_k
    logits = jnp.einsum("bsd,de->bse", x.astype(_F32),
                        p["router"].astype(_F32))
    probs = jax.nn.softmax(logits, -1)                      # (b, s, E)
    gates, eidx = lax.top_k(probs, k)                       # (b, s, k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)

    C = moe_capacity(m, s)
    e_flat = eidx.reshape(b, s * k)                         # (b, sk)
    onehot = jax.nn.one_hot(e_flat, E, dtype=jnp.int32)     # (b, sk, E)
    pos = jnp.cumsum(onehot, 1) - onehot
    pos_flat = jnp.take_along_axis(pos, e_flat[..., None], 2)[..., 0]
    keep = pos_flat < C                                     # (b, sk)
    dst = jnp.where(keep, e_flat * C + pos_flat, E * C)     # OOB drop slot
    src = jnp.repeat(jnp.arange(s), k)                      # (sk,) token idx
    bi = jnp.broadcast_to(jnp.arange(b)[:, None], (b, s * k))
    # GATHER-based dispatch: scatter only int32 token ids (tiny), then
    # gather token activations slot-wise.  (A values-scatter materializes
    # a (b, s*k, d) updates tensor that GSPMD replicates across the mesh —
    # observed as multi-TB all-gathers in the dry-run.)
    slot_tok = jnp.zeros((b, E * C + 1), jnp.int32) \
        .at[bi, dst].set(jnp.broadcast_to(src + 1, (b, s * k)),
                         mode="drop")[:, :-1]               # (b, EC); 0=empty
    slot_valid = slot_tok > 0
    buf = jnp.take_along_axis(
        x, jnp.maximum(slot_tok - 1, 0)[..., None], axis=1)  # (b, EC, d)
    buf = jnp.where(slot_valid[..., None], buf, 0).reshape(b, E, C, d)
    buf = hint(buf, "data", "model", None, None)
    # expert FFN (gated); E sharded over "model" = expert parallelism
    h = jnp.einsum("becd,edf->becf", buf, p["w_up"])
    g = jnp.einsum("becd,edf->becf", buf, p["w_gate"])
    y = jnp.einsum("becf,efd->becd", jax.nn.silu(g) * h, p["w_down"])
    y = hint(y, "data", "model", None, None)
    # combine: one (b, s, d) gather per routing slot j < k from the flat
    # (b, E*C, d) buffer.  (Measured alternatives, see EXPERIMENTS.md §Perf:
    # a (b,s*k,d) values-scatter and an explicit (e,c)-indexed gather both
    # lower to multi-TB replication collectives under GSPMD; this flat
    # take_along_axis form is the best of the three at every scale tried.)
    y_flat = y.reshape(b, E * C, d)
    out = jnp.zeros((b, s, d), _F32)
    for j in range(k):
        dst_j = dst[:, j::k]                                # (b, s)
        keep_j = keep[:, j::k]
        gath = jnp.take_along_axis(
            y_flat, jnp.minimum(dst_j, E * C - 1)[..., None], axis=1)
        gath = jnp.where(keep_j[..., None], gath.astype(_F32), 0.0)
        out = out + gath * gates[:, :, j][..., None]
    out = out.astype(x.dtype)
    if "shared" in p:
        out = out + mlp_fwd(p["shared"], x)
    # load-balance aux (Switch-style)
    frac_tokens = jnp.mean(jax.nn.one_hot(eidx, E, dtype=_F32),
                           axis=(0, 1, 2))
    frac_probs = jnp.mean(probs, axis=(0, 1))
    aux = E * jnp.sum(frac_tokens * frac_probs)
    return out, aux


# ---------------------------------------------------------------------------
# Mamba-2 (SSD)
# ---------------------------------------------------------------------------


def ssm_params(d: int, s: SSMSpec):
    d_inner = s.expand * d
    h = s.num_heads(d)
    conv_ch = d_inner + 2 * s.d_state
    return {
        "in_proj": P((d, 2 * d_inner + 2 * s.d_state + h),
                     ("embed", "ssm_inner"), init="scaled", fan_in=d),
        "conv_w": P((s.d_conv, conv_ch), ("conv", "ssm_inner"),
                    init="scaled", fan_in=s.d_conv),
        "conv_b": P((conv_ch,), ("ssm_inner",), init="zeros"),
        "a_log": P((h,), ("ssm_heads",), init="ones", dtype="float32"),
        "dt_bias": P((h,), ("ssm_heads",), init="zeros", dtype="float32"),
        "d_skip": P((h,), ("ssm_heads",), init="ones", dtype="float32"),
        "norm": rmsnorm_params(d_inner)["scale"],
        "out_proj": P((d_inner, d), ("ssm_inner", "embed"),
                      init="scaled", fan_in=d_inner),
    }


def _segsum(x):
    """x: (..., T) -> (..., T, T) with out[i,j] = sum_{k=j+1..i} x_k (i>=j),
    -inf above the diagonal."""
    T = x.shape[-1]
    xx = jnp.broadcast_to(x[..., None], x.shape + (T,))     # [..., i, j]=x_i
    lower = jnp.tril(jnp.ones((T, T), bool), -1)
    xx = jnp.where(lower, xx, 0.0)
    seg = jnp.cumsum(xx, -2)
    keep = jnp.tril(jnp.ones((T, T), bool))
    return jnp.where(keep, seg, -jnp.inf)


def ssd_chunked(xh, dt, A, B, C, chunk: int):
    """SSD (state-space duality) chunked scan.

    xh: (b, s, h, p); dt: (b, s, h) f32 (post-softplus); A: (h,) f32 <0;
    B, C: (b, s, n) f32 (ngroups=1).  Returns (y, final_state) with
    y: (b, s, h, p), final_state: (b, h, p, n) f32.
    """
    b, s, h, pdim = xh.shape
    n = B.shape[-1]
    if s % chunk:
        chunk = s
    nc = s // chunk
    r = lambda t, tail: t.reshape(b, nc, chunk, *tail)
    xc = r(xh.astype(_F32), (h, pdim))
    dtc = r(dt, (h,))
    Bc = r(B.astype(_F32), (n,))
    Cc = r(C.astype(_F32), (n,))
    dA = dtc * A                                           # (b,nc,l,h)
    dA_cum = jnp.cumsum(dA, axis=2)
    xdt = xc * dtc[..., None]                              # dt-weighted input

    # intra-chunk (diagonal blocks)
    L = jnp.exp(_segsum(jnp.moveaxis(dA, -1, -2)))         # (b,nc,h,l,l)
    scores = jnp.einsum("bcln,bcmn->bclm", Cc, Bc)         # (b,nc,l,l)
    y_diag = jnp.einsum("bclm,bchlm,bcmhp->bclhp", scores, L, xdt)

    # states carried out of each chunk
    decay_states = jnp.exp(dA_cum[:, :, -1:, :] - dA_cum)  # (b,nc,l,h)
    states = jnp.einsum("bcln,bclh,bclhp->bchpn", Bc, decay_states, xdt)

    # inter-chunk recurrence
    chunk_decay = jnp.exp(dA_cum[:, :, -1, :])             # (b,nc,h)

    def scan_body(h_prev, inp):
        st, dec = inp                                      # (b,h,p,n),(b,h)
        h_new = h_prev * dec[..., None, None] + st
        return h_new, h_prev

    h0 = jnp.zeros((b, h, pdim, n), _F32)
    final_state, prev_states = lax.scan(
        scan_body, h0,
        (jnp.moveaxis(states, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)))
    prev_states = jnp.moveaxis(prev_states, 0, 1)          # (b,nc,h,p,n)

    state_decay_out = jnp.exp(dA_cum)                      # (b,nc,l,h)
    y_off = jnp.einsum("bcln,bchpn,bclh->bclhp", Cc, prev_states,
                       state_decay_out)
    y = (y_diag + y_off).reshape(b, s, h, pdim)
    return y, final_state


def ssm_fwd(p, spec: SSMSpec, x, *, norm_eps=1e-6):
    """Mamba-2 block forward (training).  x: (b, s, d) -> (y, final_states)."""
    b, s, d = x.shape
    d_inner = spec.expand * d
    n = spec.d_state
    h = spec.num_heads(d)
    zxbcdt = jnp.einsum("bsd,de->bse", x, p["in_proj"])
    z, xin, Braw, Craw, dtraw = jnp.split(
        zxbcdt, [d_inner, 2 * d_inner, 2 * d_inner + n, 2 * d_inner + 2 * n], -1)
    # causal depthwise conv over (x, B, C)
    xbc_raw = jnp.concatenate([xin, Braw, Craw], -1)       # (b, s, ch)
    xbc = causal_conv(xbc_raw, p["conv_w"], p["conv_b"])
    xbc = jax.nn.silu(xbc)
    xin, Braw, Craw = jnp.split(xbc, [d_inner, d_inner + n], -1)
    A = -jnp.exp(p["a_log"].astype(_F32))                  # (h,)
    dt = jax.nn.softplus(dtraw.astype(_F32) + p["dt_bias"].astype(_F32))
    xh = xin.reshape(b, s, h, spec.head_dim)
    y, final_state = ssd_chunked(xh, dt, A, Braw, Craw, spec.chunk_size)
    y = y + xh.astype(_F32) * p["d_skip"].astype(_F32)[:, None]
    y = y.reshape(b, s, d_inner).astype(x.dtype)
    y = y * jax.nn.silu(z)
    y = rmsnorm({"scale": p["norm"]}, y, norm_eps)
    out = jnp.einsum("bse,ed->bsd", y, p["out_proj"])
    # decode-continuation cache: final SSM state + conv tail (last w-1 raw
    # conv inputs), matching ssm_cache layout
    conv_tail = xbc_raw[:, -(spec.d_conv - 1):, :]
    return out, {"state": final_state, "conv": conv_tail}


def causal_conv(x, w, bias):
    """Depthwise causal conv.  x: (b, s, ch); w: (width, ch)."""
    width = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    out = sum(xp[:, i:i + x.shape[1]] * w[i] for i in range(width))
    return out + bias


def ssm_decode(p, spec: SSMSpec, x, cache, *, norm_eps=1e-6):
    """One-token Mamba-2 step.  x: (b, 1, d).
    cache: {"conv": (b, width-1, ch), "state": (b, h, p, n) f32}."""
    b, _, d = x.shape
    d_inner = spec.expand * d
    n = spec.d_state
    h = spec.num_heads(d)
    zxbcdt = jnp.einsum("bsd,de->bse", x, p["in_proj"])[:, 0]
    z, xin, Braw, Craw, dtraw = jnp.split(
        zxbcdt, [d_inner, 2 * d_inner, 2 * d_inner + n, 2 * d_inner + 2 * n], -1)
    xbc = jnp.concatenate([xin, Braw, Craw], -1)           # (b, ch)
    conv_hist = cache["conv"]                              # (b, w-1, ch)
    window = jnp.concatenate([conv_hist, xbc[:, None]], 1)  # (b, w, ch)
    conv_out = jnp.einsum("bwc,wc->bc", window, p["conv_w"]) + p["conv_b"]
    conv_out = jax.nn.silu(conv_out)
    new_conv = window[:, 1:]
    xin, Braw, Craw = jnp.split(conv_out, [d_inner, d_inner + n], -1)
    A = -jnp.exp(p["a_log"].astype(_F32))
    dt = jax.nn.softplus(dtraw.astype(_F32) + p["dt_bias"].astype(_F32))  # (b,h)
    xh = xin.reshape(b, h, spec.head_dim).astype(_F32)
    Bf = Braw.astype(_F32)                                 # (b, n)
    Cf = Craw.astype(_F32)
    decay = jnp.exp(dt * A)                                # (b, h)
    state = cache["state"] * decay[..., None, None] + \
        jnp.einsum("bh,bhp,bn->bhpn", dt, xh, Bf)
    y = jnp.einsum("bn,bhpn->bhp", Cf, state)
    y = y + xh * p["d_skip"].astype(_F32)[:, None]
    y = y.reshape(b, 1, d_inner).astype(x.dtype)
    y = y * jax.nn.silu(z[:, None])
    y = rmsnorm({"scale": p["norm"]}, y, norm_eps)
    return jnp.einsum("bse,ed->bsd", y, p["out_proj"]), \
        {"conv": new_conv, "state": state}


def ssm_cache(spec: SSMSpec, d: int, batch: int, dtype):
    d_inner = spec.expand * d
    h = spec.num_heads(d)
    ch = d_inner + 2 * spec.d_state
    return {
        "conv": P((batch, spec.d_conv - 1, ch), ("batch", "conv", "ssm_inner"),
                  init="zeros", dtype=dtype),
        "state": P((batch, h, spec.head_dim, spec.d_state),
                   ("batch", "ssm_heads", "head_dim", "ssm_state"),
                   init="zeros", dtype="float32"),
    }
