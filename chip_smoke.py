"""Run the FedAdam-SSM round on TPU chips and check what comes out.

    python chip_smoke.py             # one chip: the trainer, kernel vs reference
    python chip_smoke.py --chips 4   # four chips: the mesh round vs round_scan

One chip (the default) trains whisper-base at its published widths
(d_model 512, vocab 51865, 6+6 layers, 1500 encoder frames) through
``repro.launch.train.train``: 4 clients, 2 local epochs, batch 2, 128
text tokens, 3 rounds of ``fedadam_ssm`` with threshold masks on the
Pallas kernel backend, then the same rounds on the jnp reference
backend from the same seed.  It checks that the losses are finite and
agree, that the uplink bytes are equal, and that the kernels were
compiled for the chip (``tpu_custom_call`` in the compiled round).

``--chips 4`` builds the same model through
``repro.launch.steps.build_train_step`` on a (4, 1) ("data", "model")
mesh, one spatial client per chip on the sparse bitmap transport, and
compares two rounds with ``round_scan`` over the same clients and
batches.  It prints each device's memory statistics.  This comparison
runs in float32 at the highest matmul precision: with bf16 deltas,
tied magnitudes make the threshold mask flip whole groups of entries
on a last-bit difference between the two compiled programs, which
would hide a real disagreement behind noise.

The last line of standard output is one JSON object naming the device.
Without a TPU the script fails before it prints any result.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "whisper-base"
CLIENTS, LOCAL_EPOCHS, BATCH, SEQ, ALPHA = 4, 2, 2, 128, 0.05
ROUNDS, MESH_ROUNDS, MESH_CHIPS = 3, 2, 4
#: kernel vs reference, and mesh vs scan: per-round loss difference,
#: relative
LOSS_RTOL = 1e-3
#: mesh vs scan: |W_mesh - W_scan| over |W_scan - W_0|, whole tree (L2).
#: Float noise amplified by Adam's normalization of tiny gradients stays
#: near 1e-3; a transport fault (a leaf, a client or a weight lost)
#: moves W by a sizable fraction of the update.
W_RTOL = 1e-2


class SmokeFailure(Exception):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def tpu_devices(n_chips: int):
    import jax
    devs = jax.devices()
    check(devs[0].platform == "tpu",
          f"no TPU: JAX found {devs[0].platform} devices")
    check(len(devs) >= n_chips, f"need {n_chips} chips, found {len(devs)}")
    return devs


def custom_calls(compiled) -> int:
    return compiled.as_text().count("tpu_custom_call")


def one_chip() -> None:
    import jax
    from repro.configs import get_config
    from repro.launch import train

    cfg = get_config(ARCH)
    log(f"{cfg.name}: d_model={cfg.d_model} vocab={cfg.vocab_size} "
        f"layers={cfg.pattern_repeats}+{cfg.encoder.num_layers} "
        f"frames={cfg.encoder.src_len}")
    runs = {}
    for backend in ("kernel", "reference"):
        log(f"--- sparsify_backend={backend}")
        runs[backend] = train.train(train.parse_args([
            "--arch", ARCH, "--algorithm", "fedadam_ssm",
            "--alpha", str(ALPHA), "--rounds", str(ROUNDS),
            "--local-epochs", str(LOCAL_EPOCHS), "--clients", str(CLIENTS),
            "--batch", str(BATCH), "--seq", str(SEQ),
            "--threshold-topk", "--sparsify-backend", backend]))
    k, r = runs["kernel"], runs["reference"]

    n_k, n_r = custom_calls(k.compiled), custom_calls(r.compiled)
    log(f"tpu_custom_call: kernel round {n_k}, reference round {n_r}")
    check(n_k > n_r, "the kernel round holds no more Pallas kernels than "
                     "the reference round")

    worst = 0.0
    for i, (a, b) in enumerate(zip(k.rounds, r.rounds)):
        log(f"round {i}: loss kernel={a['loss']!r} reference={b['loss']!r} "
            f"uplink_bytes kernel={a['uplink_bits'] / 8!r} "
            f"reference={b['uplink_bits'] / 8!r}")
        check(math.isfinite(a["loss"]) and math.isfinite(b["loss"]),
              f"round {i}: loss is not finite")
        check(a["uplink_bits"] == b["uplink_bits"],
              f"round {i}: uplink bytes differ")
        worst = max(worst, abs(a["loss"] - b["loss"]) / abs(b["loss"]))
    log(f"largest relative loss difference kernel vs reference: {worst!r} "
        f"(limit {LOSS_RTOL})")
    check(worst < LOSS_RTOL, "kernel and reference losses disagree")

    for name, run in runs.items():
        steady = [x["seconds"] for x in run.rounds[1:]]
        log(f"{name}: compile {run.compile_seconds!r} s, steady "
            f"{sum(steady) / len(steady)!r} s/round (rounds 1..)")
    stats = jax.devices()[0].memory_stats() or {}
    log(f"peak_bytes_in_use: {stats.get('peak_bytes_in_use')}")


def four_chips() -> None:
    """The mesh round on four chips against ``round_scan``, in float32
    at the highest matmul precision (see the module docstring)."""
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.core import fed_init, make_fl_round
    from repro.launch import steps as ST
    from repro.launch.mesh import make_mesh
    from repro.launch.train import build_client_batches
    from repro.models import init_params, loss_fn

    n_dev = MESH_CHIPS
    cfg = dataclasses.replace(get_config(ARCH), dtype="float32")
    log(f"{cfg.name}: d_model={cfg.d_model} vocab={cfg.vocab_size} "
        f"dtype={cfg.dtype}")
    mesh = make_mesh((n_dev, 1), ("data", "model"),
                     devices=jax.devices()[:n_dev])
    shape = ST.ShapeSpec("chip_smoke", SEQ, n_dev * BATCH, "train")
    bundle = ST.build_train_step(cfg, mesh, shape, algorithm="fedadam_ssm",
                                 alpha=ALPHA, local_epochs=LOCAL_EPOCHS,
                                 sparsify_backend="kernel")
    fed = bundle.static["fed"]
    check(fed.client_mode == "vmap" and fed.aggregate == "sparse_gather"
          and fed.n_clients == n_dev,
          f"not the spatial sparse-gather round: {fed}")
    params = init_params(cfg, jax.random.PRNGKey(0))
    batches = [build_client_batches(cfg, n_dev, BATCH,
                                    bundle.static["text_len"], seed=r)
               for r in range(MESH_ROUNDS)]

    with jax.default_matmul_precision("highest"), jax.set_mesh(mesh):
        t0 = time.perf_counter()
        step = jax.jit(bundle.fn, in_shardings=bundle.in_shardings,
                       out_shardings=bundle.out_shardings)
        state = fed_init(fed, params)
        step = step.lower(state, batches[0]).compile()
        log(f"mesh round: compile {time.perf_counter() - t0!r} s, "
            f"tpu_custom_call {custom_calls(step)}")
        mesh_mets = []
        for i, b in enumerate(batches):
            t0 = time.perf_counter()
            state, mets = jax.block_until_ready(step(state, b))
            mesh_mets.append(mets)
            log(f"mesh round {i}: {time.perf_counter() - t0!r} s")
    for d in jax.devices()[:n_dev]:
        s = d.memory_stats() or {}
        log(f"device {d.id}: bytes_in_use={s.get('bytes_in_use')} "
            f"peak_bytes_in_use={s.get('peak_bytes_in_use')}")
    w_devs = {s.device.id for x in jax.tree.leaves(state.W)
              for s in x.addressable_shards}
    check(len(w_devs) == n_dev, f"W lives on devices {sorted(w_devs)}")

    fed_scan = dataclasses.replace(fed, client_mode="scan", client_axes=None)

    def loss(p, b):
        return loss_fn(cfg, p, b["tokens"], frontend_embeds=b.get("embeds"),
                       remat="full")

    with jax.default_matmul_precision("highest"):
        scan_round = jax.jit(make_fl_round(fed_scan, loss))
        ref = fed_init(fed_scan, params)
        for i, b in enumerate(batches):
            ref, mets = scan_round(ref, b)
            lm = float(jnp.mean(mesh_mets[i]["loss"]))
            ls = float(jnp.mean(mets["loss"]))
            bm = float(mesh_mets[i]["uplink_bits"])
            bs = float(mets["uplink_bits"])
            log(f"round {i}: loss mesh={lm!r} scan={ls!r} "
                f"uplink_bits mesh={bm!r} scan={bs!r}")
            check(math.isfinite(lm) and math.isfinite(ls),
                  f"round {i}: loss is not finite")
            check(abs(lm - ls) <= LOSS_RTOL * abs(ls),
                  f"round {i}: mesh and scan losses disagree")
            check(bm == bs, f"round {i}: uplink bits differ")

    def norm(tree):
        return math.sqrt(sum(float(np.sum(np.square(x)))
                             for x in jax.tree.leaves(tree)))

    host = lambda t: jax.tree.map(
        lambda x: np.asarray(jax.device_get(x), np.float64), t)
    w_mesh, w_scan, w_0 = host(state.W), host(ref.W), host(params)
    diff = norm(jax.tree.map(np.subtract, w_mesh, w_scan)) \
        / norm(jax.tree.map(np.subtract, w_scan, w_0))
    log(f"|W_mesh - W_scan| / |W_scan - W_0| = {diff!r} (limit {W_RTOL})")
    check(diff < W_RTOL, "mesh and scan rounds disagree on W")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, MESH_CHIPS), default=1,
                    help="1: trainer, kernel vs reference; 4: mesh vs scan")
    args = ap.parse_args(argv)
    try:
        devs = tpu_devices(args.chips)
        from repro.launch.cache import enable_compile_cache
        log(f"compile cache: {enable_compile_cache()}")
        if args.chips == MESH_CHIPS:
            four_chips()
        else:
            one_chip()
    except SmokeFailure as e:
        print(f"[chip_smoke] FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
