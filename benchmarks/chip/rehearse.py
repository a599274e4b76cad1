"""Compile a cell's round for a described TPU v5e chip, with no chip
attached, and print what the compiler plans for it.

    JAX_PLATFORMS=cpu python3 benchmarks/chip/rehearse.py \\
        --workload whisper-base.ssm-bisect.c4-l2 [--layers 2]

It builds the round as ``program.Program`` does, from shapes alone, and
prints ``memory_analysis()`` (argument, output, temporary and total bytes
on the chip) and the number of Pallas kernels (``tpu_custom_call``) in
the compiled round.  ``--layers`` overrides the configuration's depth:
this is how the depth of a configuration cut to one chip is chosen.
``--reference f32|fp8`` compiles the reference's round (or the control's)
instead, which must fit the chip too.
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


def rehearse(cell: dict, layers=None, topology: str = "v5e:2x2") -> dict:
    import program
    from repro.core import fed as fed_mod
    from repro.models import init_params, loss_fn

    c, mix = dict(cell["c"]), cell["mix"]
    if layers is not None:
        c["num_hidden_layers"] = layers
    cfg = program.arch_config(c)
    fed = program.fed_config(mix)
    dev = topologies.get_topology_desc(platform="tpu",
                                       topology_name=topology).devices[0]
    shard = SingleDeviceSharding(dev)
    place = lambda t: jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=shard), t)
    state = place(jax.eval_shape(lambda: fed_mod.fed_init(
        fed, init_params(cfg, jax.random.PRNGKey(0)))))
    C, B, S = mix["clients"], mix["batch"], mix["seq"]
    batch = {"tokens": jax.ShapeDtypeStruct((C, B, S), jnp.int32)}
    if c["encoder_frames"]:
        batch["embeds"] = jax.ShapeDtypeStruct(
            (C, B, c["encoder_frames"], c["hidden_size"]), jnp.float32)
    batch = place(batch)

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


def rehearse_reference(cell: dict, mode: str = "f32",
                       topology: str = "v5e:2x2") -> dict:
    """The same for the reference's round (``mode="fp8"``: the control),
    which runs on the chip after the window."""
    import reference

    c, mix = cell["c"], cell["mix"]
    dev = topologies.get_topology_desc(platform="tpu",
                                       topology_name=topology).devices[0]
    shard = SingleDeviceSharding(dev)
    place = lambda t: jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=shard), t)
    W = jax.eval_shape(lambda: reference.init_params(
        c, jax.random.PRNGKey(0)))
    state = place(reference.State(W, W, W))
    C, B, S = mix["clients"], mix["batch"], mix["seq"]
    batch = {"tokens": jax.ShapeDtypeStruct((C, B, S), jnp.int32)}
    if c["encoder_frames"]:
        batch["embeds"] = jax.ShapeDtypeStruct(
            (C, B, c["encoder_frames"], c["hidden_size"]), jnp.float32)
    t0 = time.perf_counter()
    compiled = reference.make_round(c, mix, mode).lower(
        state, place(batch)).compile()
    mem = compiled.memory_analysis()
    return {"mode": mode, "temp_size_in_bytes": int(mem.temp_size_in_bytes),
            "argument_size_in_bytes": int(mem.argument_size_in_bytes),
            "compile_s": time.perf_counter() - t0}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--layers", type=int, default=None)
    ap.add_argument("--reference", choices=("f32", "fp8"), default=None,
                    help="compile the reference's round (fp8: the "
                         "control) in place of the program's")
    args = ap.parse_args(argv)
    jax.config.update("jax_enable_compilation_cache", False)
    cell = bench.load_cell(args.workload)
    print(json.dumps(rehearse_reference(cell, args.reference)
                     if args.reference else rehearse(cell, args.layers)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
