"""``rehearse.py --reference`` on a described v5e chip (the TPU's
compiler, no chip): every piece of the reference compiles, and the
largest argument + temporary bytes are reported, for a configuration
named by its files rather than by a cell."""
import json

import pytest

from conftest import TINY, TINY_MIX


@pytest.fixture(scope="module")
def topo():
    """The described chip; compiles for it stay out of the persistent
    cache, which could not read them back without the chip."""
    import jax
    from jax.experimental import topologies
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.mark.parametrize("mode", ["f32", "fp8"])
def test_reference_pieces_compile_for_the_chip(topo, tmp_path, mode):
    import rehearse
    c = tmp_path / "tiny.json"
    c.write_text(json.dumps(dict(TINY, encoder_layers=0, encoder_frames=0,
                                 tie_word_embeddings=True)))
    mix = tmp_path / "tiny-mix.json"
    mix.write_text(json.dumps(TINY_MIX))
    ap = rehearse.argparse.ArgumentParser()
    rehearse.bench.add_cell_args(ap)
    args = ap.parse_args(["--config", str(c), "--traffic", str(mix)])
    out = rehearse.rehearse_reference(rehearse.bench.cell_from_args(ap, args),
                                      mode)
    names = {p["piece"] for p in out["pieces"]}
    assert names >= {"init", "zeros", "grad", "mean", "adam", "kept",
                     "apply", "leaf_norms", "change_norms"}
    assert ("to_mode" in names) is (mode == "fp8")
    assert out["largest"]["argument_plus_temp"] == max(
        p["argument_size_in_bytes"] + p["temp_size_in_bytes"]
        for p in out["pieces"])
