"""The one traffic generator: client batches of a federated round, made
from ``--seed`` and a traffic mix's parameters (``traffic/<name>.json``).

Copied from the program's ``data/synthetic.py`` (``synthetic_tokens``,
``synthetic_frontend_embeds``) and ``launch/train.build_client_batches``
so that a change to the program cannot change the traffic.  Tokens are
Zipf-distributed over the vocabulary and each client draws them through
a permutation of its own topic (non-IID clients); frame embeddings for
a stubbed audio encoder are Gaussian.  Every seed gives the same sizes:
only the values change.
"""
from __future__ import annotations

import numpy as np


def zipf_tokens(n_seqs: int, seq_len: int, vocab: int, seed: int,
                topic: int) -> np.ndarray:
    """Zipf tokens, permuted by topic: topics shift the unigram
    distribution, so clients of different topics are non-IID."""
    rng = np.random.default_rng(seed + 7919 * topic)
    p = 1.0 / np.arange(1, vocab + 1, dtype=np.float64)
    p /= p.sum()
    perm = np.random.default_rng(topic).permutation(vocab)
    return perm[rng.choice(vocab, size=(n_seqs, seq_len), p=p)].astype(
        np.int32)


def frame_embeds(n: int, frames: int, d_model: int, seed: int) -> np.ndarray:
    """Precomputed frame embeddings for a stubbed audio frontend."""
    rng = np.random.default_rng(seed)
    return rng.normal(0, 0.02, size=(n, frames, d_model)).astype(np.float32)


def round_seed(seed: int, r: int) -> int:
    """Seed of round ``r``'s batch: distinct rows for every round."""
    return int(np.random.SeedSequence([seed, r]).generate_state(1)[0])


def client_batches(mix: dict, vocab: int, d_model: int, frames: int,
                   seed: int, r: int) -> dict:
    """One round's batch: ``tokens`` (C, B, S) int32 and, where the model
    has a stubbed encoder (``frames > 0``), ``embeds`` (C, B, F, D) f32."""
    s = round_seed(seed, r)
    C, B, S = mix["clients"], mix["batch"], mix["seq"]
    topic = (lambda c: c) if mix["non_iid"] else (lambda c: 0)
    out = {"tokens": np.stack([zipf_tokens(B, S, vocab, s, topic(c))
                               for c in range(C)])}
    if frames:
        out["embeds"] = np.stack([frame_embeds(B, frames, d_model, s + c)
                                  for c in range(C)])
    return out
