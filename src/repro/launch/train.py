"""FL training driver: any zoo architecture x any FedAdam algorithm.

Runs on whatever devices JAX finds: the published widths on a TPU, or
``--smoke`` (reduced widths) on the CPU, where the Pallas kernels run in
interpret mode.  Examples:

    PYTHONPATH=src python -m repro.launch.train \
        --arch starcoder2-3b --smoke --rounds 5 --algorithm fedadam_ssm

    PYTHONPATH=src python -m repro.launch.train \
        --arch mamba2-1-3b --smoke --rounds 3 --algorithm fedadam_top

``train(parse_args([...]))`` runs the same driver in-process and returns
the per-round metrics (chip_smoke.py does this).
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Any, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import save_fed_state
from repro.configs import get_config, reduce_for_smoke
from repro.core import (
    FedConfig, fed_init, make_compressor, make_fl_round, wire)
from repro.core.compressors import available as available_algorithms
from repro.data import synthetic_tokens, synthetic_frontend_embeds
from repro.launch.cache import enable_compile_cache
from repro.models import init_params, loss_fn
from repro.optim import AdamHyper


def build_client_batches(cfg, n_clients, batch_size, seq_len, *, seed=0,
                         non_iid=True):
    toks = np.stack([
        synthetic_tokens(batch_size, seq_len, cfg.vocab_size, seed=seed,
                         topic=(c if non_iid else 0))
        for c in range(n_clients)])
    batch = {"tokens": jnp.asarray(toks)}
    if cfg.stub_frontend:
        n_front = cfg.encoder.src_len if cfg.encoder is not None else \
            min(cfg.stub_frontend_tokens, 16)
        emb = np.stack([
            synthetic_frontend_embeds(batch_size, n_front, cfg.d_model,
                                      seed=seed + c)
            for c in range(n_clients)])
        batch["embeds"] = jnp.asarray(emb)
    return batch


def parse_args(argv: Optional[List[str]] = None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--algorithm", default="fedadam_ssm",
                    choices=available_algorithms(),
                    help="any registered compressor (docs/compressors.md)")
    ap.add_argument("--alpha", type=float, default=0.05)
    ap.add_argument("--rounds", type=int, default=10)
    ap.add_argument("--local-epochs", type=int, default=3)
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced widths for CPU runs and tests (default: "
                         "the config's published widths)")
    ap.add_argument("--iid", action="store_true")
    ap.add_argument("--checkpoint", default=None)
    ap.add_argument("--kernel-adam", action="store_true")
    ap.add_argument("--threshold-topk", action="store_true",
                    help="production O(d) threshold masks instead of "
                         "exact sort-based top-k")
    ap.add_argument("--sparsify-backend", default="auto",
                    choices=("auto", "kernel", "reference"),
                    help="threshold-mask implementation (docs/kernels.md; "
                         "kernel = Pallas, interpret mode off-TPU)")
    ap.add_argument("--participation", type=float, default=1.0,
                    help="fraction of clients sampled per round (sync: "
                         "weight masking; async: dispatch pool)")
    # buffered-async mode (docs/async.md): K > 0 switches the driver
    ap.add_argument("--async-buffer", type=int, default=0, metavar="K",
                    help="server buffer size; 0 = synchronous round")
    ap.add_argument("--max-staleness", type=int, default=None,
                    help="discard updates staler than this at arrival")
    ap.add_argument("--staleness-power", type=float, default=0.5,
                    help="aggregation weight (1+s)**-power")
    ap.add_argument("--churn-seed", type=int, default=0)
    ap.add_argument("--churn-jitter", type=int, default=0)
    ap.add_argument("--churn-straggler-prob", type=float, default=0.0)
    ap.add_argument("--churn-drop-prob", type=float, default=0.0)
    return ap.parse_args(argv)


@dataclasses.dataclass
class TrainRun:
    """What one training run produced.  ``rounds``: per-round dicts with
    ``loss``, ``uplink_bits``, and ``value_fill_share`` and
    ``mask_dropped_share`` (%, ``wire.mask_shares``; None for schemes
    without a mask payload), plus ``seconds``, wall time blocked on the
    result, for the synchronous driver.  ``compiled`` and
    ``compile_seconds``: the synchronous round as compiled before its
    first call (None for the async driver)."""
    rounds: List[dict]
    state: Any
    compile_seconds: Optional[float] = None
    compiled: Any = None


def _share_fields(shares) -> dict:
    fill, dropped = shares if shares is not None else (None, None)
    return dict(value_fill_share=fill, mask_dropped_share=dropped)


def _share_text(shares) -> str:
    if shares is None:
        return ""
    return f" fill={shares[0]:.2f}% dropped={shares[1]:.4f}%"


def train(args: argparse.Namespace) -> TrainRun:
    """Run the FL trainer described by ``args`` (see :func:`parse_args`)."""
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = reduce_for_smoke(cfg)

    params = init_params(cfg, jax.random.PRNGKey(0))
    n_params = sum(x.size for x in jax.tree.leaves(params))

    fed = FedConfig(
        algorithm=args.algorithm, alpha=args.alpha,
        local_epochs=args.local_epochs, n_clients=args.clients,
        adam=AdamHyper(lr=args.lr), client_mode="scan",
        use_kernel_adam=args.kernel_adam,
        exact_topk=not args.threshold_topk,
        sparsify_backend=args.sparsify_backend,
        participation=args.participation)
    comp = make_compressor(fed)
    dev = jax.devices()[0]
    print(f"[train] device: {dev.platform} {dev.device_kind} "
          f"x{len(jax.devices())}")
    print(f"[train] {cfg.name}: {n_params/1e6:.2f}M params, "
          f"{args.clients} clients, L={args.local_epochs}, "
          f"alpha={args.alpha}, algo={args.algorithm} "
          f"(transport={comp.transport}, "
          f"{comp.bits_per_client(n_params)/8e6:.2f} MB/client/round)")

    def loss(p, batch):
        return loss_fn(cfg, p, batch["tokens"],
                       frontend_embeds=batch.get("embeds"), remat="none")

    state = fed_init(fed, params)

    if args.async_buffer > 0:
        # buffered-async mode: one virtual-clock simulation covers all
        # rounds (server steps); clients re-train the same per-client
        # shards at every dispatch — docs/async.md
        from repro.core.async_fed import AsyncConfig, make_async_round
        from repro.data.churn import ChurnConfig, ChurnModel

        churn = ChurnModel(
            ChurnConfig(seed=args.churn_seed, jitter=args.churn_jitter,
                        straggler_prob=args.churn_straggler_prob,
                        drop_prob=args.churn_drop_prob),
            args.clients)
        acfg = AsyncConfig(buffer_size=args.async_buffer,
                           max_staleness=args.max_staleness,
                           staleness_power=args.staleness_power)
        run = make_async_round(fed, loss, acfg, churn=churn)
        batch = build_client_batches(cfg, args.clients, args.batch,
                                     args.seq, non_iid=not args.iid)
        t0 = time.time()
        state, mets = run(state, batch, rounds=args.rounds)
        result = TrainRun(rounds=[], state=state)
        for r, (loss_v, bits, counts) in enumerate(zip(
                mets["loss_per_step"], mets["bits_per_step"],
                mets["counts_per_step"])):
            shares = wire.mask_shares(counts)
            result.rounds.append(dict(loss=float(loss_v),
                                      uplink_bits=float(bits),
                                      **_share_fields(shares)))
            print(f"[round {r:3d}] loss={loss_v:.4f} "
                  f"uplink={bits/8e6:.2f} MB{_share_text(shares)}")
        print(f"[train] async: {mets['server_steps']} server steps, "
              f"{mets['landed']} landed / {mets['dropped']} dropped / "
              f"{mets['discarded']} discarded, "
              f"total uplink={float(mets['uplink_bits'])/8e6:.2f} MB "
              f"({time.time()-t0:.1f}s)")
    else:
        batches = [build_client_batches(cfg, args.clients, args.batch,
                                        args.seq, seed=r,
                                        non_iid=not args.iid)
                   for r in range(args.rounds)]
        t0 = time.perf_counter()
        round_fn = jax.jit(make_fl_round(fed, loss)).lower(
            state, batches[0]).compile()
        result = TrainRun(rounds=[], state=state, compiled=round_fn,
                          compile_seconds=time.perf_counter() - t0)
        print(f"[train] compiled the round in "
              f"{result.compile_seconds:.1f}s")
        for r, batch in enumerate(batches):
            t0 = time.perf_counter()
            state, mets = jax.block_until_ready(round_fn(state, batch))
            dt = time.perf_counter() - t0
            loss_v = float(jnp.mean(mets["loss"]))
            bits = float(mets["uplink_bits"])
            shares = wire.mask_shares(
                {k: jnp.sum(mets[k]) for k in wire.COUNT_KEYS})
            result.rounds.append(dict(loss=loss_v, uplink_bits=bits,
                                      seconds=dt, **_share_fields(shares)))
            print(f"[round {r:3d}] loss={loss_v:.4f} "
                  f"uplink={bits/8e6:.2f} MB{_share_text(shares)}  "
                  f"({dt:.3f}s)")
        result.state = state

    if args.checkpoint:
        save_fed_state(state, args.checkpoint,
                       meta=dict(arch=cfg.name, algorithm=args.algorithm,
                                 rounds=args.rounds))
        print(f"[train] saved {args.checkpoint}")
    return result


def main(argv: Optional[List[str]] = None) -> None:
    args = parse_args(argv)
    enable_compile_cache()
    train(args)


if __name__ == "__main__":
    main()
