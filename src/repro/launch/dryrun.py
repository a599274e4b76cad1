import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) combo with
ShapeDtypeStruct inputs (no allocation) and emit memory / cost / collective
analyses as JSON for the roofline table.

MUST be run as its own process (the XLA_FLAGS above lock the backend at
first jax init): one combo per invocation, e.g.

    PYTHONPATH=src python -m repro.launch.dryrun \
        --arch mamba2-1-3b --shape decode_32k --mesh pod1 \
        --out experiments/dryrun/

or ``--all`` to iterate (slow; prefer the driver script
benchmarks/run_dryruns.sh which parallelizes across processes).
"""
import argparse
import json
import sys
import time
import traceback
from pathlib import Path

import jax

from repro import roofline as RL
from repro.configs import ASSIGNED_ARCHS, get_config
from repro.launch import steps as ST
from repro.launch.mesh import make_production_mesh


def run_one(arch: str, shape_name: str, mesh_name: str, out_dir: Path,
            **build_kw) -> dict:
    cfg = get_config(arch)
    shape = ST.SHAPES[shape_name]
    rec: dict = dict(arch=arch, shape=shape_name, mesh=mesh_name,
                     status="ok")
    reason = ST.skip_reason(cfg, shape)
    if reason:
        rec.update(status="skip", reason=reason)
        return rec

    multi_pod = mesh_name == "pod2"
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    rec["chips"] = chips

    bundle = ST.build_step(cfg, mesh, shape_name, **build_kw)
    t0 = time.time()
    with jax.set_mesh(mesh):
        jfn = jax.jit(bundle.fn,
                      in_shardings=bundle.in_shardings,
                      out_shardings=bundle.out_shardings,
                      donate_argnums=bundle.donate_argnums)
        lowered = jfn.lower(*bundle.args_sds)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    coll = RL.collective_bytes(
        hlo, bundle.static.get("loop_trips", ()))

    mem_rec = {}
    for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                 "temp_size_in_bytes", "alias_size_in_bytes",
                 "generated_code_size_in_bytes"):
        mem_rec[attr] = getattr(mem, attr, None)
    # peak per-device HBM = args + temps (aliased args are reused)
    args_b = mem_rec.get("argument_size_in_bytes") or 0
    temp_b = mem_rec.get("temp_size_in_bytes") or 0
    alias_b = mem_rec.get("alias_size_in_bytes") or 0
    out_b = mem_rec.get("output_size_in_bytes") or 0
    mem_rec["peak_per_device_bytes"] = args_b + temp_b + out_b - alias_b

    fed = bundle.static.get("fed")
    model_flops = RL.analytic_model_flops(
        cfg, shape.kind if shape.kind != "long" else "decode",
        shape.seq_len, shape.global_batch,
        local_epochs=(fed.local_epochs if fed else 1),
        n_virtual_clients=(bundle.static.get("n_clients", 1)
                           if fed and fed.client_mode == "scan" else 1))

    rec.update(
        t_lower_s=round(t_lower, 1),
        t_compile_s=round(t_compile, 1),
        memory=mem_rec,
        flops=cost.get("flops") if cost else None,
        bytes_accessed=cost.get("bytes accessed") if cost else None,
        collectives={k: v for k, v in coll.items()},
        model_flops=model_flops,
        n_params=cfg.param_count(),
        n_active=cfg.active_param_count(),
        plan=dataclass_str(bundle.static.get("plan")),
        hlo_lines=hlo.count("\n"),
    )
    # keep a trimmed HLO around for collective-schedule inspection
    out_dir.mkdir(parents=True, exist_ok=True)
    hlo_path = out_dir / f"{arch}__{shape_name}__{mesh_name}.hlo.txt"
    keep = [ln for ln in hlo.splitlines()
            if any(c in ln for c in RL._COLLECTIVES) or ln.startswith("HloModule")]
    hlo_path.write_text("\n".join(keep))
    return rec


def dataclass_str(p) -> str:
    return str(p) if p is not None else ""


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(ST.SHAPES))
    ap.add_argument("--mesh", default="pod1", choices=["pod1", "pod2"])
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--algorithm", default="fedadam_ssm")
    ap.add_argument("--aggregate", default=None)
    ap.add_argument("--local-epochs", type=int, default=2)
    ap.add_argument("--alpha", type=float, default=0.05)
    ap.add_argument("--remat", default="full")
    ap.add_argument("--serve-params", default=None,
                    choices=[None, "tp", "fsdp"],
                    help="override the deploy plan's serving param rules")
    ap.add_argument("--cache-seq-shard", default=None,
                    help="mesh axis (or comma tuple) to shard decode cache "
                         "sequence dim — split-KV decode optimization")
    ap.add_argument("--tag", default="")
    args = ap.parse_args()

    out_dir = Path(args.out)
    combos = []
    if args.all:
        for arch in ASSIGNED_ARCHS:
            for shape in ST.SHAPES:
                combos.append((arch, shape, args.mesh))
    else:
        assert args.arch and args.shape
        combos.append((args.arch, args.shape, args.mesh))

    build_kw = {}
    rc = 0
    for arch, shape, mesh_name in combos:
        kw = dict(build_kw)
        if ST.SHAPES[shape].kind == "train":
            kw.update(algorithm=args.algorithm, alpha=args.alpha,
                      local_epochs=args.local_epochs, remat=args.remat)
            if args.aggregate:
                kw["aggregate"] = args.aggregate
        else:
            if args.cache_seq_shard:
                css = tuple(args.cache_seq_shard.split(","))
                kw["cache_seq_shard"] = css if len(css) > 1 else css[0]
            if args.serve_params:
                import dataclasses as _dc
                from repro.sharding import plan_for
                kw["plan"] = _dc.replace(plan_for(arch),
                                         serve_params=args.serve_params)
        name = f"{arch}__{shape}__{mesh_name}{args.tag}"
        try:
            rec = run_one(arch, shape, mesh_name, out_dir, **kw)
        except Exception as e:  # noqa: BLE001 — record the failure
            rec = dict(arch=arch, shape=shape, mesh=mesh_name,
                       status="error", error=f"{type(e).__name__}: {e}",
                       traceback=traceback.format_exc()[-4000:])
            rc = 1
        out_dir.mkdir(parents=True, exist_ok=True)
        (out_dir / f"{name}.json").write_text(json.dumps(rec, indent=1))
        status = rec["status"]
        extra = ""
        if status == "ok":
            extra = (f"compile={rec['t_compile_s']}s "
                     f"coll={rec['collectives']['total']/1e9:.2f}GB "
                     f"mem/dev={rec['memory']['peak_per_device_bytes']/1e9:.2f}GB")
        elif status == "error":
            extra = rec["error"][:200]
        print(f"[dryrun] {name}: {status} {extra}", flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
