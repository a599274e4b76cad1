"""Operation and byte counts of a round, from the configuration's shapes,
and the table of device peaks (``peaks.json``, keyed by ``device_kind``).

Model FLOPs count the matmuls the model requires (2 per multiply-add):
projections, MLP, attention scores and values (causal self-attention
counts its lower triangle), and the head over every position.  A
training step is forward + backward = 3 forwards; recomputation does
not count.
"""
from __future__ import annotations

import json
from pathlib import Path

HERE = Path(__file__).resolve().parent
VALUE_BYTES = 4                     # the wire's value streams are float32


def peaks(device_kind: str) -> dict:
    table = json.loads((HERE / "peaks.json").read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device_kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def padded_vocab(vocab: int) -> int:
    return -(-vocab // 256) * 256


def forward_flops(c: dict, seq: int) -> float:
    """FLOPs of one forward pass over one sequence of ``seq`` tokens
    (plus the encoder's frames, where the model has an encoder)."""
    d, f = c["hidden_size"], c["intermediate_size"]
    q = c["num_attention_heads"] * c["head_dim"]
    kv = c["num_key_value_heads"] * c["head_dim"]
    proj = 2 * d * (2 * q + 2 * kv)              # wq, wo, wk, wv per token
    mlp = 2 * 2 * d * f
    causal = 2 * 2 * q * (seq + 1) / 2           # scores + values, mean row
    dec = seq * (proj + mlp + causal)
    total = 0.0
    frames = c["encoder_frames"]
    if c["encoder_layers"]:
        enc = frames * (proj + 2 * 2 * d * 4 * d + 2 * 2 * q * frames)
        total += c["encoder_layers"] * enc
        cross = seq * 2 * d * 2 * q + frames * 2 * d * 2 * kv \
            + seq * 2 * 2 * q * frames
        dec += cross
    total += c["num_hidden_layers"] * dec
    total += seq * 2 * d * padded_vocab(c["vocab_size"])
    return float(total)


def round_model_flops(c: dict, mix: dict) -> float:
    """Model FLOPs of local training in one round: every client's
    ``local_epochs`` steps, forward and backward, over its batch."""
    return 3.0 * forward_flops(c, mix["seq"]) * mix["batch"] \
        * mix["local_epochs"] * mix["clients"]


def leaf_sizes(c: dict) -> list:
    """``(elements, itemsize)`` of every parameter leaf."""
    d, dt = c["hidden_size"], 2 if c["dtype"] == "bfloat16" else 4
    q = c["num_attention_heads"] * c["head_dim"]
    kv = c["num_key_value_heads"] * c["head_dim"]
    vp = padded_vocab(c["vocab_size"])
    L, E = c["num_hidden_layers"], c["encoder_layers"]
    attn = [d * q, d * kv, d * kv, q * d]
    out = [(vp * d, dt), (d, 4)]
    if not c["tie_word_embeddings"]:
        out.append((d * vp, dt))
    block = attn + [d * c["intermediate_size"]] * 2
    norms = 2                                    # before mixer and MLP
    if E:
        block += attn                            # cross-attention
        norms += 1
        out += [(E * n, dt) for n in attn + [d * 4 * d] * 2] \
            + [(E * d, 4)] * 2 + [(d, 4)]
    out += [(L * d, 4)] * norms + [(L * n, dt) for n in block]
    return out


def payload_bytes(c: dict, alpha: float) -> int:
    """One client's FedAdam-SSM payload in the program's wire format
    (docs/wire.md): a bitmap over every slot, each leaf padded to 1024
    slots and the whole to 4096, and three float32 value streams of the
    threshold masks' capacity, k = round(alpha n) plus the contracted
    over-selection min(6% k + 8, n - k) per leaf."""
    sizes = [n for n, _ in leaf_sizes(c)]
    slots = sum(-(-n // 1024) * 1024 for n in sizes)
    slots = -(-slots // 4096) * 4096
    cap = 0
    for n in sizes:
        k = max(1, int(round(alpha * n)))
        cap += min(n, k + min(int(0.06 * k) + 8, n - k))
    return slots // 8 + 3 * cap * VALUE_BYTES


def codec_least_bytes(c: dict, mix: dict, payload: float) -> float:
    """Least HBM traffic of one round's uplink work: per client, dW, dM
    and dV read once at their dtypes, and the ``payload`` bytes written
    once and read once."""
    deltas = 3 * sum(n * b for n, b in leaf_sizes(c))
    return mix["clients"] * (deltas + 2 * payload)
