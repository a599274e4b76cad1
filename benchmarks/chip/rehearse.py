"""Compile a cell's round for a described TPU v5e chip, with no chip
attached, and print what the compiler plans for it.

    JAX_PLATFORMS=cpu python3 benchmarks/chip/rehearse.py \\
        --workload whisper-base.ssm-bisect.c4-l2 [--layers 2]
    JAX_PLATFORMS=cpu python3 benchmarks/chip/rehearse.py \\
        --config CONFIG.json --traffic MIX.json [--reference f32]

A cell is named by ``--workload``, or, before it has one, by a
configuration file and a traffic mix file.  It builds the round as
``program.Program`` does, from shapes alone, and prints
``memory_analysis()`` (argument, output, temporary and total bytes on
the chip) and the number of Pallas kernels (``tpu_custom_call``) in the
compiled round.  ``--layers`` overrides the configuration's depth: this
is how the depth of a configuration cut to one chip is chosen.
``--reference f32|fp8`` compiles the reference (or the control) instead,
which must fit the chip too: each compiled piece of it
(``reference.pieces``), with its argument and temporary bytes, and the
largest argument + temporary of them.
Nothing runs, so it gives no time.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parents[1] / "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

import run as bench  # noqa: E402


def _described(topology: str):
    """``place(tree)``: the tree's shapes on one described chip."""
    dev = topologies.get_topology_desc(platform="tpu",
                                       topology_name=topology).devices[0]
    shard = SingleDeviceSharding(dev)
    return lambda t: jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=shard), t)


def _batch(c: dict, mix: dict) -> dict:
    C, B, S = mix["clients"], mix["batch"], mix["seq"]
    batch = {"tokens": jax.ShapeDtypeStruct((C, B, S), jnp.int32)}
    if c["encoder_frames"]:
        batch["embeds"] = jax.ShapeDtypeStruct(
            (C, B, c["encoder_frames"], c["hidden_size"]), jnp.float32)
    return batch


def rehearse(cell: dict, layers=None, topology: str = "v5e:2x2") -> dict:
    import program
    from repro.core import fed as fed_mod
    from repro.models import init_params, loss_fn

    c, mix = dict(cell["c"]), cell["mix"]
    if layers is not None:
        c["num_hidden_layers"] = layers
    cfg = program.arch_config(c)
    fed = program.fed_config(mix)
    place = _described(topology)
    state = place(jax.eval_shape(lambda: fed_mod.fed_init(
        fed, init_params(cfg, jax.random.PRNGKey(0)))))
    batch = place(_batch(c, mix))

    def loss(p, b):
        return loss_fn(cfg, p, b["tokens"], frontend_embeds=b.get("embeds"),
                       remat=mix["remat"])

    # the kernels pick Mosaic over interpret mode by the default backend
    jax.default_backend = lambda: "tpu"
    t0 = time.perf_counter()
    compiled = jax.jit(fed_mod.make_fl_round(fed, loss)).lower(
        state, batch).compile()
    mem = compiled.memory_analysis()
    out = {k: int(getattr(mem, k)) for k in (
        "argument_size_in_bytes", "output_size_in_bytes",
        "temp_size_in_bytes", "alias_size_in_bytes")}
    out["total_bytes"] = (out["argument_size_in_bytes"]
                          + out["output_size_in_bytes"]
                          + out["temp_size_in_bytes"]
                          - out["alias_size_in_bytes"])
    out["tpu_custom_call"] = compiled.as_text().count("tpu_custom_call")
    out["compile_s"] = time.perf_counter() - t0
    out["layers"] = c["num_hidden_layers"]
    return out


def rehearse_reference(cell: dict, mode: str = "f32", layers=None,
                       topology: str = "v5e:2x2") -> dict:
    """The same for each compiled piece of the reference
    (``mode="fp8"``: the control), which runs on the chip after the
    window, and the largest argument + temporary bytes of them."""
    import reference

    c, mix = dict(cell["c"]), cell["mix"]
    if layers is not None:
        c["num_hidden_layers"] = layers
    place = _described(topology)
    pieces = []
    for name, fn, args in reference.pieces(c, mix, _batch(c, mix), mode):
        t0 = time.perf_counter()
        mem = fn.lower(*place(args)).compile().memory_analysis()
        arg, temp = (int(mem.argument_size_in_bytes),
                     int(mem.temp_size_in_bytes))
        shapes = [list(x.shape) for x in jax.tree.leaves(args)][:2]
        pieces.append({"piece": name, "shapes": shapes,
                       "argument_size_in_bytes": arg,
                       "temp_size_in_bytes": temp,
                       "output_size_in_bytes": int(
                           mem.output_size_in_bytes),
                       "argument_plus_temp": arg + temp,
                       "compile_s": time.perf_counter() - t0})
    largest = max(pieces, key=lambda p: p["argument_plus_temp"])
    return {"mode": mode, "layers": c["num_hidden_layers"],
            "pieces": pieces, "largest": largest}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    bench.add_cell_args(ap)
    ap.add_argument("--layers", type=int, default=None)
    ap.add_argument("--reference", choices=("f32", "fp8"), default=None,
                    help="compile the reference's pieces (fp8: the "
                         "control) in place of the program's round")
    args = ap.parse_args(argv)
    cell = bench.cell_from_args(ap, args)
    jax.config.update("jax_enable_compilation_cache", False)
    print(json.dumps(rehearse_reference(cell, args.reference, args.layers)
                     if args.reference else rehearse(cell, args.layers)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
