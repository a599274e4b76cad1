"""FLOP and byte counters against counts written out by hand, and the
payload counter against the program's measured payload."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import counters as K
from conftest import HERE, TINY


def config(name):
    """A configuration of the benchmark, or one kept as test data
    (``data/``): starcoder2-3b at 2 layers has no cell yet."""
    path = HERE / "data" / f"{name}.json"
    if not path.exists():
        path = HERE.parent / "configs" / f"{name}.json"
    return json.loads(path.read_text())


def test_starcoder2_forward_flops_per_token():
    c = config("starcoder2-3b-l2")
    seq = 4096
    d, f, q, kv, vocab = 3072, 12288, 24 * 128, 2 * 128, 49152
    per_token_layer = (2 * d * q            # wq
                       + 2 * d * kv * 2     # wk, wv
                       + 2 * q * d          # wo
                       + 2 * d * f * 2      # w_up, w_down
                       + 2 * 2 * q * (seq + 1) / 2)   # causal QK and PV
    head = 2 * d * vocab
    want = seq * (c["num_hidden_layers"] * per_token_layer + head)
    assert per_token_layer + head == 519_051_264
    assert K.forward_flops(c, seq) == want


def test_whisper_base_forward_flops():
    c = config("whisper-base")
    seq, frames, d, f, vp = 128, 1500, 512, 2048, 51968
    proj = 4 * 2 * d * d                    # q, k, v, o: 8 heads x 64 = d
    encoder = 6 * frames * (proj + 2 * 2 * d * 4 * d + 2 * 2 * d * frames)
    decoder = 6 * (seq * (proj + 2 * 2 * d * f + 2 * 2 * d * (seq + 1) / 2)
                   + seq * 2 * 2 * d * d    # cross q, o
                   + frames * 2 * 2 * d * d  # cross k, v over the frames
                   + seq * 2 * 2 * d * frames)   # cross QK and PV
    head = seq * 2 * d * vp
    assert K.forward_flops(c, seq) == encoder + decoder + head
    mix = {"seq": seq, "batch": 2, "local_epochs": 2, "clients": 4}
    assert K.round_model_flops(c, mix) == 3 * 2 * 2 * 4 * (
        encoder + decoder + head)


@pytest.mark.parametrize("name,params", [("whisper-base", 70_664_192),
                                         ("starcoder2-3b-l2", 342_899_712)])
def test_leaf_sizes_count_the_parameters(name, params):
    assert sum(n for n, _ in K.leaf_sizes(config(name))) == params


def test_payload_bytes_equal_the_programs_payload():
    from repro.core import FedConfig, make_compressor
    from repro.core.compressors import Deltas
    from repro.core.wire import payload_nbytes
    from repro.models import init_params
    import program

    cfg = program.arch_config(TINY, check=False)
    params = init_params(cfg, jax.random.PRNGKey(0))
    assert sorted((x.size, x.dtype.itemsize) for x in
                  jax.tree.leaves(params)) == sorted(K.leaf_sizes(TINY))
    keys = iter(jax.random.split(jax.random.PRNGKey(1), 3 * 64))
    rand = lambda: jax.tree.map(
        lambda x: jax.random.normal(next(keys), x.shape).astype(x.dtype),
        params)
    comp = make_compressor(FedConfig(exact_topk=False,
                                     sparsify_backend="reference"))
    packed, _, _ = comp.compress(Deltas(rand(), rand(), rand()), None)
    assert payload_nbytes(packed.wire) == K.payload_bytes(TINY, 0.05)


def test_codec_least_bytes():
    mix = {"clients": 3}
    deltas = 3 * sum(n * b for n, b in K.leaf_sizes(TINY))
    assert K.codec_least_bytes(TINY, mix, 1000.0) == 3 * (deltas + 2000.0)


def test_peak_table_is_keyed_by_device_kind():
    p = K.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert "Google Cloud" in p["source"]
    with pytest.raises(KeyError):
        K.peaks("cpu")
