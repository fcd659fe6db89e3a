"""Serving launcher: ``python -m repro.launch.serve [--mode d2sd] [...]``.

Serves a batch of synthetic requests through the D2SD engine and prints
acceptance and throughput statistics. The weights are the trained study
artifacts by default; ``--random`` draws a small random target and
drafters, and ``--arch <id>`` a registered target at its full published
widths (bf16 parameters) with two production drafters, from a seed,
reading the KV cache through the Pallas cascade kernels.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses

import jax
import numpy as np

from repro.config.base import ModelConfig, SpecConfig
from repro.core import pipeline as pl
from repro.core.drafter import DrafterConfig, drafter_init
from repro.data.synthetic import SyntheticDataset
from repro.launch.compile_cache import enable_compile_cache
from repro.models import lm
from repro.serving.engine import ServingEngine


def random_bundle(tcfg: ModelConfig, dcfg: DrafterConfig, spec: SpecConfig,
                  seed: int = 0) -> pl.SpecBundle:
    """Target and two drafters (one config) with weights drawn from
    ``seed``; parameters come out in each config's ``param_dtype``."""
    tp = lm.lm_init(jax.random.PRNGKey(seed), tcfg)
    d1 = drafter_init(jax.random.PRNGKey(seed + 1), dcfg)
    d2 = drafter_init(jax.random.PRNGKey(seed + 2), dcfg)
    return pl.SpecBundle(tcfg, dcfg, dcfg, spec, tp, d1, d2)


def arch_bundle(arch: str, *, mode: str = "d2sd", gamma: int = 16,
                k: int = 4, temperature: float = 0.0,
                seed: int = 0) -> pl.SpecBundle:
    """Registered target ``arch`` at its full widths with bfloat16
    parameters, plus two ``launch.steps.production_drafter``s, all drawn
    from ``seed``."""
    from repro.config.registry import get_config
    from repro.launch.steps import production_drafter
    tcfg = dataclasses.replace(get_config(arch), param_dtype="bfloat16")
    dcfg = production_drafter(tcfg, gamma)
    spec = SpecConfig(gamma=gamma, top_k_branches=k, mode=mode,
                      temperature=temperature)
    return random_bundle(tcfg, dcfg, spec, seed)


def mesh_context(mesh_data: int, mesh_model: int,
                 kv_seq_axis: str = "model"):
    """``use_sharding`` context over a (data, model) mesh, with the
    ``kv_seq`` logical axis on ``kv_seq_axis`` ("off" replicates the KV
    pool); a nullcontext for the default 1x1. The engine captures the
    context at CONSTRUCTION, places the weights on the mesh and re-enters
    the context around every device-facing call, so only the
    ``ServingEngine(...)`` call needs to run inside it."""
    if mesh_data * mesh_model <= 1:
        return contextlib.nullcontext()
    need = mesh_data * mesh_model
    if jax.device_count() < need:
        raise ValueError(
            f"a {mesh_data}x{mesh_model} mesh needs {need} devices but only "
            f"{jax.device_count()} are visible; on CPU export XLA_FLAGS="
            f"--xla_force_host_platform_device_count={need}")
    from repro.distributed.sharding import LOGICAL_RULES, use_sharding
    from repro.launch.mesh import make_mesh
    rules = dict(LOGICAL_RULES)
    rules["kv_seq"] = None if kv_seq_axis == "off" else kv_seq_axis
    return use_sharding(make_mesh(mesh_data, mesh_model), rules)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", default="d2sd",
                    choices=["d2sd", "dflash", "naive_k", "eagle"])
    ap.add_argument("--gamma", type=int, default=None)
    ap.add_argument("--k", type=int, default=4)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=64)
    ap.add_argument("--task", default="math")
    ap.add_argument("--random", action="store_true",
                    help="random weights (no study artifacts needed)")
    ap.add_argument("--arch", default=None,
                    help="serve this registered target (e.g. qwen2.5-3b) at "
                         "its full widths with random bf16 weights, two "
                         "production drafters (gamma defaults to 16) and "
                         "the Pallas cache-read kernels")
    ap.add_argument("--cache-impl", default="dense",
                    choices=["dense", "paged"],
                    help="KV storage: dense per-row buffers or the page-"
                         "pool subsystem (page-granular admission, "
                         "copy-free slot refill)")
    ap.add_argument("--page-size", type=int, default=64)
    ap.add_argument("--prefix-cache", action="store_true",
                    help="radix prefix cache: copy-on-write page sharing "
                         "of committed prefixes across requests (needs "
                         "--cache-impl paged, all-global-attention target)")
    ap.add_argument("--bucket-sizes", default=None,
                    help="comma-separated install-prefill length buckets "
                         "(bounds donated-install recompiles under varying "
                         "prompt lengths), e.g. 32,64,128; 'off' forces "
                         "exact-length installs; default: pow-2 ladder")
    ap.add_argument("--pool-scope", default="engine",
                    choices=["engine", "wave"],
                    help="paged pool lifetime: 'engine' (default) keeps ONE "
                         "page pool for the server's lifetime so cached "
                         "prefixes survive wave turnover (resident "
                         "serving); 'wave' restores the legacy per-wave "
                         "pools")
    ap.add_argument("--pool-pages", type=int, default=None,
                    help="explicit engine-lifetime pool size in pages "
                         "(default: auto-sized from the first wave's "
                         "candidate window by the engine-global rule)")
    ap.add_argument("--pool-headroom", type=float, default=1.0,
                    help="prefix-retention headroom as a fraction of the "
                         "worst-case concurrent live set (prefix cache "
                         "only; default 1.0 = retain up to one live-set's "
                         "worth of cached prefixes)")
    ap.add_argument("--traffic", default=None,
                    choices=["poisson", "bursty"],
                    help="open-loop serving: replay a seeded arrival trace "
                         "(poisson = memoryless, bursty = 2-state MMPP "
                         "clumps) through the async front-end instead of "
                         "submitting a fixed batch; per-request TTFT/TPOT "
                         "SLA percentiles are reported")
    ap.add_argument("--rate", type=float, default=1.0,
                    help="open-loop arrival rate in requests/s "
                         "(with --traffic)")
    ap.add_argument("--duration", type=float, default=30.0,
                    help="open-loop trace length in seconds "
                         "(with --traffic)")
    ap.add_argument("--traffic-seed", type=int, default=0,
                    help="arrival-trace seed (with --traffic); the same "
                         "seed replays the identical trace")
    ap.add_argument("--sync-baseline", action="store_true",
                    help="drive the trace with the synchronous baseline "
                         "(refill only at retire moments) instead of the "
                         "overlapped front-end (with --traffic)")
    ap.add_argument("--virtual-clock", action="store_true",
                    help="replay in deterministic simulated time (1 s per "
                         "decode cycle) instead of wall time "
                         "(with --traffic)")
    ap.add_argument("--mesh-data", type=int, default=1,
                    help="data mesh axis size: ONE resident engine spans "
                         "the (data, model) mesh; batch rows shard over "
                         "this axis when divisible (default 1)")
    ap.add_argument("--mesh-model", type=int, default=1,
                    help="model mesh axis size; with --cache-impl paged "
                         "the page pool's payload bytes shard along it "
                         "(the kv_seq logical axis: page_size must be "
                         "divisible by this) and the cascade verify runs "
                         "under shard_map with an LSE-psum merge — token-"
                         "identical to --mesh-model 1. Needs data*model "
                         "devices; on CPU export XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N "
                         "(default 1 = no mesh)")
    ap.add_argument("--kv-seq-axis", default="model",
                    choices=["model", "data", "off"],
                    help="mesh axis backing the kv_seq logical axis (KV "
                         "page payload placement + decode verify "
                         "sharding); 'off' replicates the KV pool while "
                         "keeping the rest of the mesh rules "
                         "(default: model)")
    args = ap.parse_args()
    try:
        mesh_ctx = mesh_context(args.mesh_data, args.mesh_model,
                                args.kv_seq_axis)
    except ValueError as e:
        ap.error(str(e))
    enable_compile_cache()

    if args.arch is not None:
        bundle = pl.with_attn_impl(
            arch_bundle(args.arch, mode=args.mode, gamma=args.gamma or 16,
                        k=args.k, temperature=args.temperature), "pallas")
    elif args.random:
        from repro.configs.paper_target import drafter_small, smoke
        dcfg = drafter_small(gamma=args.gamma or 8)
        spec = SpecConfig(gamma=dcfg.gamma, top_k_branches=args.k,
                          mode=args.mode, temperature=args.temperature)
        bundle = random_bundle(smoke(), dcfg, spec)
    else:
        import sys
        from pathlib import Path
        sys.path.insert(0, str(Path(__file__).resolve().parents[3]))
        from benchmarks.common import build_bundle
        bundle = build_bundle(args.mode, gamma=args.gamma, k=args.k,
                              temperature=args.temperature)

    kw = {}
    if args.bucket_sizes is not None:
        if args.bucket_sizes.strip().lower() in ("off", "none"):
            kw["bucket_sizes"] = None
        else:
            buckets = tuple(int(x) for x in args.bucket_sizes.split(",")
                            if x.strip())
            if not buckets or any(b <= 0 for b in buckets):
                ap.error(f"--bucket-sizes must be positive ints, got "
                         f"{args.bucket_sizes!r}")
            kw["bucket_sizes"] = buckets
    if args.traffic is not None:
        from repro.serving.frontend import ReplayDriver
        from repro.serving.metrics import (MetricsRecorder, MonotonicClock,
                                           VirtualClock)
        from repro.serving.traffic import make_trace
        clock = VirtualClock() if args.virtual_clock else MonotonicClock()
        rec = MetricsRecorder(clock)
        trace = make_trace(args.traffic, args.rate, args.duration,
                           seed=args.traffic_seed,
                           max_new=args.max_new,
                           vocab=bundle.target_cfg.vocab_size,
                           tasks=(args.task,))
        pool_pages = args.pool_pages
        if args.cache_impl == "paged" and pool_pages is None:
            # the engine's auto-sizing rule sees only the queue at the
            # first wave — under open-loop traffic that may be a single
            # request. The launcher has the whole trace, so size the
            # pool for the worst-case concurrent set up front.
            g = bundle.spec.gamma
            per = max(-(-(a.prompt_len + a.max_new + 2 * g + 8)
                        // args.page_size) for a in trace)
            pool_pages = 2 * args.requests * per
        with mesh_ctx:
            eng = ServingEngine(bundle, batch_size=args.requests,
                                cache_impl=args.cache_impl,
                                page_size=args.page_size,
                                prefix_cache=args.prefix_cache,
                                pool_scope=args.pool_scope,
                                pool_pages=pool_pages,
                                pool_headroom=args.pool_headroom,
                                clock=clock, recorder=rec, **kw)
        stats = ReplayDriver(eng, trace,
                             overlap=not args.sync_baseline).run()
        sla = stats["sla"]
        driver = "sync" if args.sync_baseline else "overlapped"
        print(f"mode={args.mode} traffic={args.traffic} rate={args.rate} "
              f"driver={driver} served {len(eng.done)}/{len(trace)} | "
              f"cycles={stats['engine_cycles']} "
              f"alpha={stats.get('alpha', 0):.2f}")
        print(f"  ttft p50={sla['ttft']['p50']:.2f}s "
              f"p99={sla['ttft']['p99']:.2f}s | "
              f"tpot p50={sla['tpot']['p50']:.3f}s "
              f"p99={sla['tpot']['p99']:.3f}s | "
              f"e2e p99={sla['e2e']['p99']:.2f}s | "
              f"queue max={sla['queue_depth']['max']}")
        return

    with mesh_ctx:
        eng = ServingEngine(bundle, batch_size=args.requests,
                            cache_impl=args.cache_impl,
                            page_size=args.page_size,
                            prefix_cache=args.prefix_cache,
                            pool_scope=args.pool_scope,
                            pool_pages=args.pool_pages,
                            pool_headroom=args.pool_headroom, **kw)
    ds = SyntheticDataset(args.task, 1, 64, seed=11)
    for p in ds.prompts(args.requests, 32, offset=10 ** 7):
        eng.submit(p, max_new=args.max_new)
    stats = eng.run()
    prefix = ""
    if args.prefix_cache:
        prefix = (f" | prefix_hits={stats['prefix_hits']} "
                  f"saved={stats['prefill_tokens_saved']}tok "
                  f"cow={stats['cow_copies']}")
    mesh_note = ""
    if stats.get("kv_shards", 1) > 1:
        mesh_note = (f" | kv_shards={stats['kv_shards']} "
                     f"shard_slots={stats['pool_shard_slots']}")
    dev = jax.devices()[0]
    print(f"mode={args.mode} served {len(eng.done)} requests | "
          f"alpha={stats.get('alpha', 0):.2f} | "
          f"{stats['tokens_per_s']:.1f} tok/s | "
          f"{stats['cycles']} cycles | device={dev.platform}:"
          f"{dev.device_kind}x{jax.device_count()}" + prefix + mesh_note)


if __name__ == "__main__":
    main()
