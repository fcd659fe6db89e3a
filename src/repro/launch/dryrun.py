"""Multi-pod dry-run on the CPU.

Lowers + compiles every (architecture x input-shape) cell against the
production meshes (single-pod 16x16 = 256 chips; multi-pod 2x16x16 = 512)
of virtual CPU devices and records memory analysis, loop-aware
FLOP/collective counts, and the three roofline terms per cell into
experiments/dryrun/<cell>.json.

Run one cell:   python -m repro.launch.dryrun --arch qwen2.5-3b \
                    --shape train_4k --mesh single
Run everything: python -m repro.launch.dryrun --all --jobs 4

This is an ahead-of-time tool for the CPU: importing it pins JAX to the
CPU backend with 512 host devices (before JAX initializes), and every
child it starts inherits that pin, so it never takes a TPU a host has.
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import argparse
import dataclasses
import json
import subprocess
import sys
import time
import traceback
from pathlib import Path

OUT_DIR = Path(__file__).resolve().parents[3] / "experiments" / "dryrun"


def _cell_name(arch, shape, mesh):
    return f"{arch}_{shape}_{mesh}".replace("/", "-")


def run_cell(arch: str, shape_name: str, mesh_kind: str,
             gamma: int = 16, k_branches: int = 4,
             loss_seq_chunk=None, remat_policy=None,
             tag: str = "", diagnose: bool = False,
             rules_override: dict = None, fsdp_override=None) -> dict:
    import jax
    import jax.numpy as jnp

    from repro.config.base import shape_by_name
    from repro.config.registry import get_config
    from repro.distributed import sharding as sh
    from repro.launch import steps as steps_lib
    from repro.launch.mesh import make_production_mesh
    from repro.roofline import analysis as roof
    from repro.roofline.hlo_analysis import analyze_hlo_text

    t0 = time.time()
    multi = mesh_kind == "multi"
    n_chips = 512 if multi else 256
    devices = jax.devices()[:n_chips]
    from repro.launch.mesh import mesh_of
    mesh = mesh_of(
        (2, 16, 16) if multi else (16, 16),
        ("pod", "data", "model") if multi else ("data", "model"),
        devices=devices)

    cell = steps_lib.build_cell(arch, shape_name, gamma=gamma,
                                k_branches=k_branches,
                                loss_seq_chunk=loss_seq_chunk,
                                remat_policy=remat_policy)
    if cell is None:
        return {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
                "skipped": "long_500k requires sub-quadratic attention",
                "ok": True}

    cfg = get_config(arch)
    shape = shape_by_name(shape_name)
    rules = dict(cell.rules)
    if rules_override:
        rules.update(rules_override)
    fsdp = cell.fsdp if fsdp_override is None else fsdp_override

    with sh.use_sharding(mesh, rules, fsdp=fsdp):
        in_shardings = sh.params_shardings(cell.args, mesh)
        jitted = jax.jit(cell.fn, in_shardings=in_shardings)
        lowered = jitted.lower(*cell.args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis() or {}
        hlo_text = compiled.as_text()
        hlo_stats = analyze_hlo_text(hlo_text)
        top_colls = None
        if diagnose:
            from repro.roofline.hlo_analysis import top_collectives
            top_colls = top_collectives(hlo_text, k=15)

    # ---- roofline terms ----
    # memory_analysis sizes are per-device (post-SPMD program)
    arg_bytes_dev = getattr(mem, "argument_size_in_bytes", 0)
    temp_bytes_dev = getattr(mem, "temp_size_in_bytes", 0)
    out_bytes_dev = getattr(mem, "output_size_in_bytes", 0)

    if cell.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        opt_bytes_dev = arg_bytes_dev * 0.5    # rough: opt state share
        hbm = roof.analytic_hbm_bytes(cfg, shape, "train", n_chips,
                                      arg_bytes_dev * 0.4,
                                      opt_bytes_per_dev=opt_bytes_dev)
    elif cell.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        hbm = roof.analytic_hbm_bytes(cfg, shape, "prefill", n_chips,
                                      arg_bytes_dev * 0.5,
                                      state_bytes_per_dev=arg_bytes_dev * 0.3)
    else:
        gamma_tok = gamma + k_branches * (gamma - 1)
        tokens = shape.global_batch * gamma_tok
        # decode traffic: params + the KV/feature caches actually read
        hbm = roof.analytic_hbm_bytes(cfg, shape, "decode", n_chips,
                                      arg_bytes_dev * 0.4,
                                      state_bytes_per_dev=arg_bytes_dev * 0.5,
                                      spec_overhead=3.0)
    terms = roof.derive_terms(cfg, shape, cell.kind, n_chips, hlo_stats,
                              hbm, tokens)

    rec = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind,
        "kind": cell.kind, "ok": True, "chips": n_chips,
        "tag": tag,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "memory": {
            "argument_bytes_per_dev": int(arg_bytes_dev),
            "temp_bytes_per_dev": int(temp_bytes_dev),
            "output_bytes_per_dev": int(out_bytes_dev),
            "peak_estimate_gb": round((arg_bytes_dev + temp_bytes_dev)
                                      / 2 ** 30, 3),
        },
        "cost_analysis_raw_flops": float(cost.get("flops", 0.0)),
        "hlo": {k: float(v) for k, v in hlo_stats.items()},
        "terms": terms.as_dict(),
    }
    if top_colls is not None:
        rec["top_collectives"] = top_colls
    return rec


ALL_SHAPES = ("train_4k", "prefill_32k", "decode_32k", "long_500k")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="single", choices=["single", "multi"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--jobs", type=int, default=3)
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--gamma", type=int, default=16)
    ap.add_argument("--k", type=int, default=4)
    ap.add_argument("--loss-seq-chunk", type=int, default=None)
    ap.add_argument("--remat-policy", default=None)
    ap.add_argument("--tag", default="")
    ap.add_argument("--diagnose", action="store_true")
    ap.add_argument("--rules", default=None,
                    help='JSON rules override, e.g. \'{"act_seq": null}\'')
    ap.add_argument("--fsdp", default=None, choices=["on", "off"])
    args = ap.parse_args()

    OUT_DIR.mkdir(parents=True, exist_ok=True)

    if not args.all:
        assert args.arch and args.shape
        name = _cell_name(args.arch, args.shape, args.mesh)
        if args.tag:
            name += f"_{args.tag}"
        out = OUT_DIR / f"{name}.json"
        try:
            rec = run_cell(args.arch, args.shape, args.mesh,
                           gamma=args.gamma, k_branches=args.k,
                           loss_seq_chunk=args.loss_seq_chunk,
                           remat_policy=args.remat_policy, tag=args.tag,
                           diagnose=args.diagnose,
                           rules_override=(json.loads(args.rules)
                                           if args.rules else None),
                           fsdp_override=(None if args.fsdp is None
                                          else args.fsdp == "on"))
        except Exception as e:  # noqa
            rec = {"arch": args.arch, "shape": args.shape, "mesh": args.mesh,
                   "ok": False, "error": repr(e),
                   "traceback": traceback.format_exc()[-4000:]}
        out.write_text(json.dumps(rec, indent=2))
        print(json.dumps({k: v for k, v in rec.items()
                          if k not in ("traceback",)}, indent=2))
        sys.exit(0 if rec.get("ok") else 1)

    # orchestrate all cells as subprocesses (isolation + parallelism)
    from repro.config.registry import ARCH_IDS
    jobs = []
    for mesh_kind in ("single", "multi"):
        for arch in ARCH_IDS:
            for shape in ALL_SHAPES:
                name = _cell_name(arch, shape, mesh_kind)
                out = OUT_DIR / f"{name}.json"
                if out.exists() and not args.force:
                    try:
                        if json.loads(out.read_text()).get("ok"):
                            continue
                    except Exception:
                        pass
                jobs.append((arch, shape, mesh_kind, out))

    print(f"{len(jobs)} cells to run")
    running = []
    results = []
    while jobs or running:
        while jobs and len(running) < args.jobs:
            arch, shape, mesh_kind, out = jobs.pop(0)
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", arch, "--shape", shape, "--mesh", mesh_kind]
            env = dict(os.environ, JAX_PLATFORMS="cpu")
            env["PYTHONPATH"] = str(Path(__file__).resolve().parents[2])
            proc = subprocess.Popen(cmd, env=env,
                                    stdout=subprocess.DEVNULL,
                                    stderr=subprocess.PIPE)
            running.append((proc, arch, shape, mesh_kind, out, time.time()))
            print(f"start {arch} {shape} {mesh_kind}")
        time.sleep(3)
        still = []
        for proc, arch, shape, mesh_kind, out, t0 in running:
            if proc.poll() is None:
                if time.time() - t0 > 3600:
                    proc.kill()
                    print(f"TIMEOUT {arch} {shape} {mesh_kind}")
                else:
                    still.append((proc, arch, shape, mesh_kind, out, t0))
                continue
            ok = proc.returncode == 0
            dt = time.time() - t0
            print(f"done {arch} {shape} {mesh_kind} ok={ok} {dt:.0f}s")
            results.append((arch, shape, mesh_kind, ok))
        running = still
    n_ok = sum(1 for r in results if r[3])
    print(f"\n{n_ok}/{len(results)} newly-run cells ok")


if __name__ == "__main__":
    main()
