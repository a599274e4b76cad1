"""Compile the main-path Pallas kernels for a described TPU v5e chip.

Nothing runs: each test lowers one kernel with ``interpret=False`` at a
real model width and asks the installed TPU compiler for the program,
which refuses what the chip would refuse (sub-tile blocks, scalar
stores to VMEM, too much fast memory).  Interpret-mode parity lives in
tests/test_kernels.py; this file guards what interpret mode cannot see.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU
library, and every test worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.fused_adam import fused_adam as fa
from repro.kernels.packed_topk import packed_topk as pk
from repro.kernels.ssm_apply import ssm_apply as sa
from repro.kernels.topk_mask import topk_mask as tm
from repro.kernels.wirepack import expand as we
from repro.kernels.wirepack import wirepack as wp

#: Leaf widths (element counts) the kernels see at published widths.
LEAVES = {
    "whisper_base_embed": 51865 * 512,
    "starcoder2_3b_embed": 49152 * 3072,
    "starcoder2_3b_mlp": 3072 * 12288,
}

#: The whole aligned buffer of a whisper-base mask payload (70.66M
#: parameters) and its value-stream capacity at alpha 0.05.
WHISPER_ROWS, WHISPER_CAP = 552096, 3745384


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler can be loaded here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip can be written to the persistent
    # cache but never read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _rows(n: int, lanes: int, quantum: int) -> int:
    """Rows of the (rows, lanes) buffer an n-element leaf pads to."""
    per = quantum * lanes
    return -(-n // per) * quantum


def _compile(fn, sharding, *shapes, **static):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    text = fn.lower(*args, interpret=False, **static).compile().as_text()
    assert "tpu_custom_call" in text
    return text


@pytest.mark.parametrize("leaf", LEAVES)
def test_packed_hist_compiles(one_chip, leaf):
    r = _rows(LEAVES[leaf], pk.LANES, pk.SUBLANES)
    _compile(pk.packed_hist_2d, one_chip,
             ((r, pk.LANES), jnp.float32),
             ((r // pk.SUBLANES,), jnp.int32),
             ((1, pk.N_BINS), jnp.float32))


@pytest.mark.parametrize("leaf", LEAVES)
def test_packed_apply_compiles(one_chip, leaf):
    r = _rows(LEAVES[leaf], pk.LANES, pk.SUBLANES)
    buf = ((r, pk.LANES), jnp.float32)
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in (
        ((1, pk.N_BINS), jnp.float32), ((r // pk.SUBLANES,), jnp.int32),
        ((1,), jnp.float32), ((1,), jnp.float32))]
    streams = tuple(jax.ShapeDtypeStruct(*buf, sharding=one_chip)
                    for _ in range(3))
    text = pk.packed_apply_2d.lower(
        *args, streams, with_residual=True, value_dtype=None,
        interpret=False).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("leaf", LEAVES)
def test_absmax_and_count_ge_compile(one_chip, leaf):
    r = _rows(LEAVES[leaf], tm.LANES, tm.SUBLANES)
    x = ((r, tm.LANES), jnp.float32)
    _compile(tm.absmax_2d, one_chip, x)
    _compile(tm.count_ge_2d, one_chip, ((tm.N_BINS,), jnp.float32), x)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("leaf", LEAVES)
def test_ssm_apply_ef_compiles(one_chip, leaf, dtype):
    r = _rows(LEAVES[leaf], sa.LANES, sa.SUBLANES)
    x = ((r, sa.LANES), dtype)
    _compile(sa.ssm_apply_ef_2d, one_chip, ((), jnp.float32), x, x, x,
             with_residual=True, value_dtype=None)


@pytest.mark.parametrize("bits", [1, 8])
@pytest.mark.parametrize("leaf", LEAVES)
def test_wirepack_compiles(one_chip, leaf, bits):
    r = _rows(LEAVES[leaf], wp.LANES, wp.CODE_SUBLANES)
    _compile(wp.pack_words_2d, one_chip, ((r, wp.LANES), jnp.int32),
             bits=bits)
    _compile(wp.unpack_words_2d, one_chip,
             ((r * bits // wp.WORD_BITS, wp.LANES), jnp.uint32), bits=bits)


@pytest.mark.parametrize("leaf", LEAVES)
def test_fused_adam_compiles(one_chip, leaf):
    r = _rows(LEAVES[leaf], fa.LANES, fa.SUBLANES)
    x = ((r, fa.LANES), jnp.float32)
    _compile(fa.fused_adam_2d, one_chip, ((4,), jnp.float32), x, x, x, x)


@pytest.mark.parametrize("n_streams", [1, 3])
@pytest.mark.parametrize("leaf", [*LEAVES, "whisper_base_buffer"])
def test_wirepack_expand_compiles(one_chip, leaf, n_streams):
    if leaf == "whisper_base_buffer":
        rows, cap = WHISPER_ROWS, WHISPER_CAP
    else:
        rows = _rows(LEAVES[leaf], wp.LANES, wp.CODE_SUBLANES)
        cap = -(-LEAVES[leaf] // 20)
    words = jax.ShapeDtypeStruct((rows // wp.WORD_BITS, wp.LANES),
                                 jnp.uint32, sharding=one_chip)
    streams = tuple(jax.ShapeDtypeStruct((cap,), jnp.float32,
                                         sharding=one_chip)
                    for _ in range(n_streams))
    text = we.expand_streams_2d.lower(
        words, streams, interpret=False).compile().as_text()
    assert "tpu_custom_call" in text
