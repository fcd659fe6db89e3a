"""Serving micro-benchmark: batching policy AND KV storage A/B.

Replays the same mixed traffic (one long budget + sustained short
requests, mixed prompt lengths) through :class:`ServingEngine` three
times —

* ``legacy_waves``      — ``early_exit=False, refill=False``, dense KV
  (the drain-the-wave engine);
* ``early_exit_refill`` — both batching optimizations on, dense KV;
* ``paged``             — batching optimizations on, ``cache_impl="paged"``
  (page-pool KV storage, page-granular admission, copy-free refill);
* ``paged_pallas``      — the paged engine with ``attn_impl="pallas"``
  (cascade kernels read the pool + page table directly; interpret mode
  on CPU) — the kernelized read path, token-identical by assertion;

and reports tokens/s, ``warm_cycle_s`` (median post-warmup per-cycle
time — ``wall_s`` is trace/compile-dominated at tiny scale),
``wasted_row_cycles`` (batch rows that spent a
decode cycle without a live, unfinished request), pool utilization, and
``refill_copy_bytes`` — the accounting model of bytes each slot install
writes (dense: a full ``max_len`` row per cache; paged: prompt-sized
tail-page writes + one page-table row). Per-request token output is
asserted identical across ALL configurations (greedy decoding, per-row
isolation, exact logical-view equivalence of the paged layout), so the
deltas are pure batching / memory-subsystem efficiency. Results land in
``BENCH_serving.json`` at the repo root.

``--suite prefix`` replays shared-prefix traffic (a shared-system-prompt
fleet plus multi-turn follow-ups whose prompts extend turn-1's
prompt+answer) through the paged engine with the radix prefix cache OFF
and ON: per-request tokens are asserted identical, and the hit-rate
metrics (``prefix_hits`` / ``prefill_tokens_saved`` / ``cow_copies`` /
``prefix_evictions``) land in the ``prefix`` section of the same JSON.

``--suite resident`` replays a RESIDENT-server schedule: the engine stays
alive across several submit→drain rounds (each round is a wave turnover),
and round N+1's prompts extend round N's committed strings. With the
engine-lifetime pool (``pool_scope="engine"``, the default) the radix
tree survives the turnover, so the later rounds hit prefixes cached in
EARLIER waves (``prefix_hit_tokens`` grows after the first turnover —
asserted); per-request tokens are asserted identical cache-on vs
cache-off vs legacy per-wave pools. Results land in the ``resident``
section.

``--suite sharded`` replays the resident burst schedule through ONE
engine spanning a 4-way ``kv_seq`` host mesh (page payload bytes
sharded within-page, page identity host-global, cascade verify under
``shard_map``) vs the single-device engine: per-request tokens are
asserted identical, cross-wave prefix hits must survive turnover
through the sharded pool, and the ``sharded`` section reports the
per-shard pool placement (``pool_shard_slots``, utilization) and the
``decode_collective_bytes`` the verify LSE-psum moves. Re-execs itself
under ``XLA_FLAGS=--xla_force_host_platform_device_count=4`` when the
host exposes fewer than 4 devices.

``--suite bytes`` emits the ``bytes_model`` section: analytic
bytes-moved-per-decode-cycle for the gather vs pallas read paths
(``roofline/bytes_model.py``) swept over live length and capacity, plus
gather/dynamic-slice byte attribution of the actual compiled decode
cycle (``roofline/hlo_analysis.py``). Asserts the scaling claim: kernel
bytes grow with LIVE cache length, gather bytes with CAPACITY.

Needs no trained study artifacts — builds a tiny random bundle. The
bundle uses a SMALL vocab (17): with random-init drafters the chance a
draft token matches the target argmax scales as ~1/vocab, and the
original vocab-199 bundle produced the degenerate ``accepted == 0`` /
``alpha == 1.0`` in every config — the stats pipeline was real but the
workload couldn't exercise it. vocab=17 yields genuine multi-token
acceptance (asserted), so ``alpha`` / ``accepted`` now measure the
verify backends' real output.

    PYTHONPATH=src python -m benchmarks.run --suite serving [--quick]
    PYTHONPATH=src python -m benchmarks.run --suite prefix  [--quick]
"""
from __future__ import annotations

import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import numpy as np

from benchmarks.common import csv_row, merge_bench_json
from benchmarks.engine_bench import _tiny_bundle
from repro.serving.engine import ServingEngine

BENCH_PATH = Path(__file__).resolve().parents[1] / "BENCH_serving.json"
PAGE_SIZE = 16
VOCAB = 17          # small on purpose: real acceptance from random drafters


def _traffic(vocab: int, quick: bool):
    """One long request up front + sustained short/mid traffic."""
    rng = np.random.default_rng(0)
    if quick:
        budgets = [20, 4, 6, 4, 5, 4]
        plens = [10, 8, 9, 8, 11, 8]
    else:
        budgets = [32, 6, 8, 5, 10, 6, 7, 5, 9, 6, 8, 5]
        plens = [14, 8, 10, 9, 12, 8, 11, 9, 10, 8, 9, 12]
    return [(rng.integers(3, vocab, size=p).astype(np.int32), n)
            for p, n in zip(plens, budgets)]


def _merge_bench_json(section: str, payload: dict) -> None:
    """Update one section of BENCH_serving.json, keeping the others."""
    merge_bench_json(BENCH_PATH, section, payload)


def _serve(bundle, reqs, batch: int, early_exit: bool = True,
           refill: bool = True, cache_impl: str = "dense", **kw):
    eng = ServingEngine(bundle, batch_size=batch, seed=0,
                        early_exit=early_exit, refill=refill,
                        cache_impl=cache_impl, page_size=PAGE_SIZE, **kw)
    for p, n in reqs:
        eng.submit(p, max_new=n)
    stats = eng.run()
    outs = {r.uid: r.out.tolist() for r in eng.done}
    return stats, outs


def _row(name, s):
    extra = ""
    if s.get("pool_pages"):
        extra = (f" pool_util={s['pool_utilization']:.2f} "
                 f"pool_peak={s['pool_peak_pages']}/{s['pool_pages']}")
    if s.get("prefix_hits"):
        extra += (f" prefix_hits={s['prefix_hits']} "
                  f"saved_tokens={s['prefill_tokens_saved']} "
                  f"cow={s['cow_copies']}")
    print(csv_row(
        name, s["wall_s"] * 1e6,
        f"tokens_per_s={s['tokens_per_s']:.1f} "
        f"warm_cycle_s={s.get('warm_cycle_s', 0.0):.4f} "
        f"wasted_row_cycles={s['wasted_row_cycles']} "
        f"alpha={s['alpha']:.3f} accepted={s['accepted']} "
        f"waves={s['waves']} refills={s['refills']} "
        f"refill_copy_bytes={s['refill_copy_bytes']}" + extra))


def run(quick: bool = False) -> None:
    gamma, k = (4, 2) if quick else (6, 2)
    batch = 2 if quick else 3
    bundle = _tiny_bundle(gamma, k, vocab=VOCAB)
    reqs = _traffic(bundle.target_cfg.vocab_size, quick)

    from repro.core import pipeline as pl

    base, base_out = _serve(bundle, reqs, batch, early_exit=False,
                            refill=False)
    opt, opt_out = _serve(bundle, reqs, batch)
    pgd, pgd_out = _serve(bundle, reqs, batch, cache_impl="paged")
    # kernelized read path A/B: same paged engine, attn_impl="pallas"
    # (interpret mode on CPU) — must be token-identical to the gather path
    pal, pal_out = _serve(pl.with_attn_impl(bundle, "pallas"), reqs, batch,
                          cache_impl="paged")
    tokens_equal = base_out == opt_out == pgd_out == pal_out
    assert tokens_equal, "batching/storage config changed per-request output"
    # real acceptance statistics, wired from the verify backends' n_acc
    # (vocab=17 guarantees the random bundle accepts some draft tokens)
    for s in (base, opt, pgd, pal):
        assert s["accepted"] > 0 and s["alpha"] > 1.0, (
            "degenerate acceptance stats", s["accepted"], s["alpha"])
    # copy-free refill acceptance: paged installs write page-order bytes
    assert pgd["installs"] == opt["installs"]
    assert pgd["refill_copy_bytes"] * 2 < opt["refill_copy_bytes"], (
        pgd["refill_copy_bytes"], opt["refill_copy_bytes"])

    _row("serving_legacy_waves", base)
    _row("serving_early_exit_refill", opt)
    _row("serving_paged_kv", pgd)
    _row("serving_paged_kv_pallas", pal)
    saved = base["wasted_row_cycles"] - opt["wasted_row_cycles"]
    copy_ratio = (opt["refill_copy_bytes"] / pgd["refill_copy_bytes"]
                  if pgd["refill_copy_bytes"] else float("inf"))
    print(csv_row("serving_wasted_cycle_reduction", 0.0,
                  f"saved={saved} tokens_equal={tokens_equal}"))
    print(csv_row("serving_refill_copy_reduction", 0.0,
                  f"dense/paged={copy_ratio:.1f}x"))

    _merge_bench_json("serving", {
        "config": {"gamma": gamma, "k": k, "batch": batch,
                   "n_requests": len(reqs), "quick": quick,
                   "page_size": PAGE_SIZE, "vocab": VOCAB},
        "legacy_waves": dict(base),
        "early_exit_refill": dict(opt),
        "paged": dict(pgd),
        "paged_pallas": dict(pal),
        "tokens_equal": tokens_equal,
        "wasted_row_cycles_saved": saved,
        "refill_copy_bytes_dense_over_paged": copy_ratio,
    })


# -------------------------------------------------------------- sla suite --
def _sla_replay(bundle, trace, overlap, batch, pool_pages):
    """One open-loop replay on a fresh engine + deterministic clock."""
    from repro.serving.frontend import ReplayDriver
    from repro.serving.metrics import MetricsRecorder, VirtualClock
    clock = VirtualClock(cycle_s=1.0, install_s=0.25)
    rec = MetricsRecorder(clock)
    eng = ServingEngine(bundle, batch_size=batch, seed=0,
                        cache_impl="paged", page_size=PAGE_SIZE,
                        pool_pages=pool_pages, clock=clock, recorder=rec)
    stats = ReplayDriver(eng, trace, overlap=overlap).run()
    outs = {r.uid: r.out.tolist() for r in eng.done}
    return stats, outs, rec


def run_sla(quick: bool = False) -> None:
    """Open-loop SLA suite: overlapped front-end vs synchronous baseline.

    Replays seeded poisson + bursty arrival traces
    (:mod:`repro.serving.traffic`) through the paged engine twice — the
    overlapped front-end (mid-flight admission during the decode overlap
    window) and the synchronous baseline (refill only at retire moments)
    — on a shared deterministic :class:`VirtualClock`. Asserts
    per-request token identity on BOTH traces, a strict engine-cycle win
    for the overlapped driver on the bursty trace (burst clumps land
    mid-wave; the sync engine leaves idle slots idle until a retire
    happens), and batched same-bucket installs
    (``install_calls < installs``). Per-request TTFT/TPOT/e2e and
    p50/p90/p99 summaries land in the ``sla`` section of
    ``BENCH_serving.json``.
    """
    from repro.serving import traffic
    bundle = _tiny_bundle(6, 2, vocab=VOCAB)
    batch, pool_pages = 4, 48
    dur = 16.0 if quick else 40.0
    # uniform prompt length on purpose: requests then differ only in
    # decode budget, so a long-anchored wave can always admit a queued
    # request of either budget class (no head-of-line size blocking) and
    # the suite measures SCHEDULING, not wave-sizing luck
    shape = dict(prompt_lens=(8,), max_new=(4, 40), vocab=VOCAB)
    legs = {}
    for kind, trace in [
        ("poisson", traffic.poisson_trace(rate=0.8, duration=dur,
                                          seed=0, **shape)),
        ("bursty", traffic.bursty_trace(rate=1.0, duration=dur, seed=3,
                                        calm_scale=0.3, burst_scale=5.0,
                                        mean_dwell=5.0, **shape)),
    ]:
        ov, ov_out, rec = _sla_replay(bundle, trace, True, batch,
                                      pool_pages)
        sy, sy_out, _ = _sla_replay(bundle, trace, False, batch,
                                    pool_pages)
        assert ov_out == sy_out, \
            f"{kind}: overlapped admission changed per-request output"
        assert len(ov_out) == len(trace)
        legs[kind] = {
            "n_requests": len(trace),
            "overlapped": dict(ov), "sync": dict(sy),
            "per_request": rec.per_request(),
            "tokens_equal": True,
            "cycle_win": sy["engine_cycles"] - ov["engine_cycles"],
        }
        for name, s in (("overlapped", ov), ("sync", sy)):
            t = s["sla"]["ttft"]
            print(csv_row(
                f"sla_{kind}_{name}", s["sla"]["e2e"]["p99"] * 1e6,
                f"cycles={s['engine_cycles']} "
                f"ttft_p50={t['p50']:.1f}s ttft_p99={t['p99']:.1f}s "
                f"tpot_p50={s['sla']['tpot']['p50']:.2f}s "
                f"queue_max={s['sla']['queue_depth']['max']}"))
    # the headline assertions: the overlapped front-end finishes the
    # bursty workload in strictly fewer engine cycles, and same-bucket
    # admissions actually collapsed into batched installs
    burst = legs["bursty"]
    assert burst["cycle_win"] > 0, (
        "overlapped front-end showed no cycle win on bursty traffic",
        burst["overlapped"]["engine_cycles"],
        burst["sync"]["engine_cycles"])
    ov = burst["overlapped"]
    assert ov["install_calls"] < ov["installs"], (
        "no same-bucket admissions were batched", ov["install_calls"],
        ov["installs"])
    print(csv_row("sla_bursty_cycle_win", 0.0,
                  f"sync={burst['sync']['engine_cycles']} "
                  f"overlapped={ov['engine_cycles']} "
                  f"win={burst['cycle_win']} "
                  f"batched_installs={ov['installs'] - ov['install_calls']}"))

    _merge_bench_json("sla", {
        "config": {"batch": batch, "pool_pages": pool_pages,
                   "duration_s": dur, "quick": quick,
                   "page_size": PAGE_SIZE, "vocab": VOCAB,
                   "clock": {"cycle_s": 1.0, "install_s": 0.25},
                   "trace_shape": {k: list(v) for k, v in shape.items()
                                   if k != "vocab"}},
        **legs,
    })
    # schema gate: downstream consumers read these exact keys — fail the
    # suite (not the reader) if the emitted shape drifts
    data = json.loads(BENCH_PATH.read_text())["sla"]
    for kind in ("poisson", "bursty"):
        leg = data[kind]
        assert leg["n_requests"] > 0 and leg["tokens_equal"]
        assert leg["per_request"], "empty per-request SLA list"
        for row in leg["per_request"]:
            assert {"uid", "ttft", "tpot", "e2e"} <= set(row)
        for drv in ("overlapped", "sync"):
            sla = leg[drv]["sla"]
            for metric in ("ttft", "tpot", "e2e", "queue_wait"):
                assert {"p50", "p90", "p99"} <= set(sla[metric]), metric
    print(csv_row("sla_schema_ok", 0.0, "BENCH_serving.json[sla]"))


# ----------------------------------------------------------- prefix suite --
def _greedy(bundle, prompt, n):
    import jax.numpy as jnp
    from repro.core import pipeline as pl
    out = pl.generate(bundle, jnp.asarray(prompt)[None], max_new=n,
                      collect_stats=False)
    return np.asarray(out["tokens"])[0]


def run_prefix(quick: bool = False) -> None:
    gamma, k = (4, 2) if quick else (5, 2)
    batch = 2
    n_fleet = 3 if quick else 5
    bundle = _tiny_bundle(gamma, k, vocab=VOCAB)
    v = bundle.target_cfg.vocab_size
    rng = np.random.default_rng(0)
    sysp = rng.integers(3, v, size=21).astype(np.int32)
    turn1 = []
    for i in range(n_fleet):
        tail = rng.integers(3, v, size=4 + i).astype(np.int32)
        turn1.append((np.concatenate([sysp, tail]), 4 + (i % 3)))
    turn2 = []
    for p, n in turn1[: max(n_fleet - 1, 1)]:
        ans = _greedy(bundle, p, n)
        turn2.append((np.concatenate(
            [p, ans, rng.integers(3, v, size=5).astype(np.int32)]),
            3 if quick else 5))
    reqs = turn1 + turn2

    off, off_out = _serve(bundle, reqs, batch, cache_impl="paged")
    on, on_out = _serve(bundle, reqs, batch, cache_impl="paged",
                        prefix_cache=True)
    tokens_equal = off_out == on_out
    assert tokens_equal, "prefix cache changed per-request output"
    assert on["prefix_hits"] > 0, "shared-prefix replay produced no hits"
    assert on["prefill_tokens_saved"] > 0
    assert on["cow_copies"] > 0, "no mid-page match exercised COW"
    assert off["prefix_hits"] == 0

    _row("serving_paged_prefix_off", off)
    _row("serving_paged_prefix_on", on)
    total_prompt_tokens = sum(len(p) for p, _ in reqs)
    # hit rate = fraction of submitted prompt tokens served from shared
    # pages; prefill_tokens_saved is bucket-denominated (what the install
    # prefill actually skips vs a cold bucketed install) and can exceed
    # the raw matched count
    hit_rate = on["prefix_hit_tokens"] / total_prompt_tokens
    print(csv_row("serving_prefix_hit_rate", 0.0,
                  f"hit_tokens={on['prefix_hit_tokens']}/"
                  f"{total_prompt_tokens} ({hit_rate:.1%}) "
                  f"saved_prefill_tokens={on['prefill_tokens_saved']} "
                  f"hits={on['prefix_hits']}/"
                  f"{on['prefix_hits'] + on['prefix_misses']} "
                  f"cow={on['cow_copies']} "
                  f"evictions={on['prefix_evictions']} "
                  f"tokens_equal={tokens_equal}"))

    _merge_bench_json("prefix", {
        "config": {"gamma": gamma, "k": k, "batch": batch,
                   "n_requests": len(reqs), "quick": quick,
                   "page_size": PAGE_SIZE, "vocab": VOCAB,
                   "system_prompt_len": len(sysp)},
        "cache_off": dict(off),
        "cache_on": dict(on),
        "tokens_equal": tokens_equal,
        "prompt_tokens_total": total_prompt_tokens,
        "prefill_token_hit_rate": hit_rate,
    })


# ---------------------------------------------------------- resident suite -
def _resident_rounds(bundle, quick: bool):
    """Submit→drain rounds for a resident server: round 1 is a shared-
    system-prompt fleet, each later round's prompts extend the previous
    round's committed prompt+answer strings (multi-turn sessions)."""
    v = bundle.target_cfg.vocab_size
    rng = np.random.default_rng(0)
    sysp = rng.integers(3, v, size=18).astype(np.int32)
    n_fleet = 2 if quick else 4
    n_rounds = 2 if quick else 3
    rounds = [[]]
    for i in range(n_fleet):
        tail = rng.integers(3, v, size=4 + i).astype(np.int32)
        rounds[0].append((np.concatenate([sysp, tail]), 4 + (i % 2)))
    for _ in range(n_rounds - 1):
        prev, nxt = rounds[-1], []
        for p, n in prev:
            ans = _greedy(bundle, p, n)
            nxt.append((np.concatenate(
                [p, ans, rng.integers(3, v, size=4).astype(np.int32)]),
                3 if quick else 4))
        rounds.append(nxt)
    return rounds


def _serve_resident(bundle, rounds, batch: int, **kw):
    """One resident engine across every round; returns (per-round stats
    snapshots, final stats, per-request outputs)."""
    eng = ServingEngine(bundle, batch_size=batch, seed=0,
                        cache_impl="paged", page_size=PAGE_SIZE,
                        pool_headroom=1.5, **kw)
    marks = []
    for reqs in rounds:
        for p, n in reqs:
            eng.submit(p, max_new=n)
        marks.append(eng.run())     # cumulative snapshot incl. tokens_per_s
    outs = {r.uid: r.out.tolist() for r in eng.done}
    return marks, marks[-1], outs


def run_resident(quick: bool = False) -> None:
    gamma, k = (4, 2) if quick else (5, 2)
    batch = 2
    bundle = _tiny_bundle(gamma, k, vocab=VOCAB)
    rounds = _resident_rounds(bundle, quick)

    _, legacy, legacy_out = _serve_resident(bundle, rounds, batch,
                                            pool_scope="wave")
    _, off, off_out = _serve_resident(bundle, rounds, batch)
    marks, on, on_out = _serve_resident(bundle, rounds, batch,
                                        prefix_cache=True)
    tokens_equal = legacy_out == off_out == on_out
    assert tokens_equal, "pool scope / prefix cache changed request output"
    # the resident acceptance criterion: prompts of round N+1 hit prefixes
    # the radix tree committed in round N's wave — hits must be recorded
    # AFTER the first wave turnover
    assert on["waves"] >= len(rounds), (on["waves"], len(rounds))
    cross_wave_hit_tokens = (on["prefix_hit_tokens"]
                             - marks[0]["prefix_hit_tokens"])
    assert cross_wave_hit_tokens > 0, \
        "no prefix cached in wave N was hit in wave N+1"
    assert off["prefix_hits"] == 0 and legacy["prefix_hits"] == 0

    _row("resident_legacy_wave_pools", legacy)
    _row("resident_engine_pool_cache_off", off)
    _row("resident_engine_pool_cache_on", on)
    total_prompt_tokens = sum(len(p) for rs in rounds for p, _ in rs)
    hit_rate = on["prefix_hit_tokens"] / total_prompt_tokens
    print(csv_row(
        "resident_cross_wave_hits", 0.0,
        f"cross_wave_hit_tokens={cross_wave_hit_tokens} "
        f"hit_tokens={on['prefix_hit_tokens']}/{total_prompt_tokens} "
        f"({hit_rate:.1%}) saved_prefill_tokens="
        f"{on['prefill_tokens_saved']} waves={on['waves']} "
        f"cached_pages={on['prefix_cached_pages']}/{on['pool_pages']} "
        f"tokens_equal={tokens_equal}"))

    _merge_bench_json("resident", {
        "config": {"gamma": gamma, "k": k, "batch": batch,
                   "n_rounds": len(rounds),
                   "n_requests": sum(len(r) for r in rounds),
                   "quick": quick, "page_size": PAGE_SIZE, "vocab": VOCAB},
        "legacy_wave_pools": dict(legacy),
        "engine_pool_cache_off": dict(off),
        "engine_pool_cache_on": dict(on),
        "per_round_cache_on": marks,
        "tokens_equal": tokens_equal,
        "cross_wave_hit_tokens": cross_wave_hit_tokens,
        "prompt_tokens_total": total_prompt_tokens,
        "prefill_token_hit_rate": hit_rate,
    })


# ------------------------------------------------------- bytes-model suite -
def _cycle_hlo_stats(bundle, batch: int, max_len: int):
    """gather/dynamic-slice bytes of ONE compiled decode cycle."""
    import jax
    from repro.core import pipeline as pl
    from repro.core.state import engine_init
    from repro.roofline.hlo_analysis import analyze_hlo_text

    state = engine_init(bundle, batch, max_len, cache_impl="paged",
                        page_size=PAGE_SIZE)
    key = jax.random.PRNGKey(0)
    txt = (pl._cycle_jit.lower(bundle, state, key, collect_stats=False,
                               shard_tag=None).compile().as_text())
    t = analyze_hlo_text(txt)
    return {"gather_bytes": t["gather_bytes"],
            "dynamic_slice_bytes": t["dynamic_slice_bytes"]}


def run_bytes_model(quick: bool = False) -> None:
    """Attributable bytes-moved-per-decode-cycle: gather vs pallas.

    Two attributions land in ``BENCH_serving.json[bytes_model]``:

    * **analytic** (``roofline/bytes_model.py``): paged-cache read bytes
      per cycle priced from config + geometry, swept over live cache
      length at fixed capacity and over capacity at fixed live length.
      Asserted shape of the claim: kernel-path bytes grow with LIVE
      length and are capacity-flat; gather-path bytes grow with CAPACITY
      and are live-length-flat.
    * **hlo** (``roofline/hlo_analysis.py``): gather / dynamic-slice
      result bytes of the actual compiled decode cycle for both impls.
      The gather path materializes capacity-sized pool_view gathers; the
      kernel path (interpret mode on CPU) only dynamic-slices page
      blocks — asserted strictly fewer gather bytes.
    """
    from repro.core import pipeline as pl
    from repro.roofline import bytes_model as bm

    gamma, k = (4, 2) if quick else (5, 2)
    batch = 2
    bundle = _tiny_bundle(gamma, k, vocab=VOCAB)
    tcfg, d1, d2 = bundle.target_cfg, bundle.d1_cfg, bundle.d2_cfg

    cap_pages = 8 if quick else 16
    cap = cap_pages * PAGE_SIZE
    live_sweep = sorted({PAGE_SIZE, cap // 4, cap // 2, cap})
    curves = {"gather": [], "pallas": []}
    for impl in ("gather", "pallas"):
        for clen in live_sweep:
            curves[impl].append(bm.cycle_read_bytes(
                tcfg, d1, d2, batch=batch, page_size=PAGE_SIZE,
                max_pages=cap_pages, cache_len=clen, impl=impl))
    cap_sweep = [cap_pages, cap_pages * 2, cap_pages * 4]
    cap_curves = {"gather": [], "pallas": []}
    for impl in ("gather", "pallas"):
        for mp in cap_sweep:
            cap_curves[impl].append(bm.cycle_read_bytes(
                tcfg, d1, d2, batch=batch, page_size=PAGE_SIZE,
                max_pages=mp, cache_len=PAGE_SIZE * 2, impl=impl))

    # the acceptance-criterion shape, asserted
    pal_tot = [c["total"] for c in curves["pallas"]]
    gat_tot = [c["total"] for c in curves["gather"]]
    assert all(a < b for a, b in zip(pal_tot, pal_tot[1:])), (
        "kernel-path bytes must grow with live cache length", pal_tot)
    assert len(set(gat_tot)) == 1, (
        "gather-path bytes must be flat in live length", gat_tot)
    gat_cap = [c["total"] for c in cap_curves["gather"]]
    pal_cap = [c["total"] for c in cap_curves["pallas"]]
    assert all(a < b for a, b in zip(gat_cap, gat_cap[1:])), (
        "gather-path bytes must grow with capacity", gat_cap)
    assert len(set(pal_cap)) == 1, (
        "kernel-path bytes must be flat in capacity", pal_cap)

    # ---- rolling local layers (dense window-capped buffers) ----
    # gather reads the buffer + materializes the [cache; block] concat +
    # re-reads it (3x window cap); the kernel streams the buffer once,
    # padded to the split grid. Both are window-capped — flat in pool
    # capacity — so the claim here is 3x -> ~1x, not live-length scaling.
    import dataclasses as _dc
    win_sweep = [600, 1100, 2100]            # non-bk-aligned (bk=512)
    roll_curves = {"gather": [], "pallas": []}
    for impl in ("gather", "pallas"):
        for w in win_sweep:
            hcfg = _dc.replace(tcfg, layer_pattern=("local", "global"),
                               sliding_window=w)
            roll_curves[impl].append(bm.target_read_bytes(
                hcfg, batch=batch, page_size=PAGE_SIZE,
                max_pages=4 * max(win_sweep) // PAGE_SIZE,
                cache_len=PAGE_SIZE, impl=impl))
    for g, p in zip(roll_curves["gather"], roll_curves["pallas"]):
        assert g["rolling_attend_read"] > 0 and "rolling_kernel_stream" in p
        roll_g = sum(v for k2, v in g.items() if k2.startswith("rolling"))
        assert p["rolling_kernel_stream"] < roll_g, (
            "kernel must stream fewer rolling bytes than 3x gather",
            p["rolling_kernel_stream"], roll_g)
    # window-capped: capacity growth does not move rolling bytes
    hcfg = _dc.replace(tcfg, layer_pattern=("local",), sliding_window=600)
    flat = [bm.target_read_bytes(hcfg, batch=batch, page_size=PAGE_SIZE,
                                 max_pages=mp, cache_len=PAGE_SIZE,
                                 impl=i)["total"]
            for i in ("gather", "pallas") for mp in (64, 256)]
    assert flat[0] == flat[1] and flat[2] == flat[3], (
        "rolling bytes must be window-capped, flat in capacity", flat)

    # ---- sharded drafter feature-cache reads (shard_map hook) ----
    # per-shard bytes divide by kv_shards on both impls; the kernel~live /
    # gather~capacity scaling must survive sharding.
    nsh = 4
    sh_live = {"gather": [], "pallas": []}
    sh_cap = {"gather": [], "pallas": []}
    for impl in ("gather", "pallas"):
        for clen in live_sweep:
            sh_live[impl].append(bm.drafter_read_bytes(
                d1, batch=batch, page_size=PAGE_SIZE, max_pages=cap_pages,
                cache_len=clen, impl=impl, kv_shards=nsh))
        for mp in cap_sweep:
            sh_cap[impl].append(bm.drafter_read_bytes(
                d1, batch=batch, page_size=PAGE_SIZE, max_pages=mp,
                cache_len=PAGE_SIZE * 2, impl=impl, kv_shards=nsh))
    sp = [c["total"] for c in sh_live["pallas"]]
    sg = [c["total"] for c in sh_cap["gather"]]
    assert all(a < b for a, b in zip(sp, sp[1:])), (
        "sharded drafter kernel bytes must grow with live length", sp)
    assert all(a < b for a, b in zip(sg, sg[1:])), (
        "sharded drafter gather bytes must grow with capacity", sg)
    assert len({c["total"] for c in sh_live["gather"]}) == 1
    assert len({c["total"] for c in sh_cap["pallas"]}) == 1
    unsh = bm.drafter_read_bytes(
        d1, batch=batch, page_size=PAGE_SIZE, max_pages=cap_pages,
        cache_len=live_sweep[0], impl="pallas", kv_shards=1)
    assert sh_live["pallas"][0]["total"] * nsh == unsh["total"], (
        "per-shard kernel bytes must be the unsharded figure / kv_shards")

    hlo = {
        "gather": _cycle_hlo_stats(bundle, batch, cap),
        "pallas": _cycle_hlo_stats(pl.with_attn_impl(bundle, "pallas"),
                                   batch, cap),
    }
    assert hlo["pallas"]["gather_bytes"] < hlo["gather"]["gather_bytes"], (
        "kernel path should gather strictly fewer bytes per cycle", hlo)

    for impl in ("gather", "pallas"):
        lo, hi = curves[impl][0]["total"], curves[impl][-1]["total"]
        print(csv_row(
            f"bytes_model_{impl}", 0.0,
            f"live={live_sweep[0]}..{live_sweep[-1]} "
            f"bytes={lo:.0f}..{hi:.0f} "
            f"hlo_gather_bytes={hlo[impl]['gather_bytes']:.0f} "
            f"hlo_dynslice_bytes={hlo[impl]['dynamic_slice_bytes']:.0f}"))
    ratio = gat_tot[0] / pal_tot[0]
    print(csv_row("bytes_model_win_at_min_live", 0.0,
                  f"gather/pallas={ratio:.1f}x at live={live_sweep[0]} "
                  f"cap={cap}"))

    _merge_bench_json("bytes_model", {
        "config": {"gamma": gamma, "k": k, "batch": batch, "quick": quick,
                   "page_size": PAGE_SIZE, "vocab": VOCAB,
                   "capacity_pages": cap_pages, "live_sweep": live_sweep,
                   "capacity_sweep_pages": cap_sweep},
        "analytic_vs_live": curves,
        "analytic_vs_capacity": cap_curves,
        "rolling_vs_window": {"window_sweep": win_sweep, **roll_curves},
        "sharded_drafter": {"kv_shards": nsh,
                            "analytic_vs_live": sh_live,
                            "analytic_vs_capacity": sh_cap},
        "hlo_decode_cycle": hlo,
        "scaling": {
            "pallas_grows_with_live": True,
            "gather_flat_in_live": True,
            "gather_grows_with_capacity": True,
            "pallas_flat_in_capacity": True,
            "gather_over_pallas_at_min_live": ratio,
            "rolling_kernel_under_3x_gather": True,
            "rolling_flat_in_capacity": True,
            "sharded_drafter_pallas_grows_with_live": True,
            "sharded_drafter_gather_grows_with_capacity": True,
            "sharded_drafter_per_shard_division": True,
        },
    })


# ----------------------------------------------------------- sharded suite -
def _run_sharded_inline(quick: bool) -> None:
    import contextlib

    from repro.distributed import sharding as sh
    from repro.launch.mesh import make_mesh

    gamma, k = (4, 2) if quick else (5, 2)
    batch = 2
    bundle = _tiny_bundle(gamma, k, vocab=VOCAB)
    rounds = _resident_rounds(bundle, quick)

    def leg(mesh):
        ctx = (sh.use_sharding(mesh, dict(sh.LOGICAL_RULES, kv_seq="model"))
               if mesh is not None else contextlib.nullcontext())
        with ctx:
            return _serve_resident(bundle, rounds, batch, prefix_cache=True)

    marks_ref, ref, ref_out = leg(None)
    marks_sh, shd, sh_out = leg(make_mesh(data=1, model=4))
    tokens_equal = sh_out == ref_out
    assert tokens_equal, \
        "kv_seq sharding changed per-request output"
    assert shd["kv_shards"] == 4, shd["kv_shards"]
    # resident acceptance across the mesh: wave-N prefixes hit in wave N+1
    cross_wave_hit_tokens = (shd["prefix_hit_tokens"]
                             - marks_sh[0]["prefix_hit_tokens"])
    assert cross_wave_hit_tokens > 0, \
        "no prefix cached in wave N was hit in wave N+1 (sharded engine)"
    assert shd["decode_collective_bytes"] > 0, shd

    _row("sharded_single_device", ref)
    _row("sharded_kv_seq_4way", shd)
    print(csv_row(
        "sharded_pool_placement", 0.0,
        f"kv_shards={shd['kv_shards']} "
        f"shard_slots={shd['pool_shard_slots']} "
        f"pool_util={shd['pool_utilization']:.2f} "
        f"decode_collective_bytes={shd['decode_collective_bytes']} "
        f"cross_wave_hit_tokens={cross_wave_hit_tokens} "
        f"tokens_equal={tokens_equal}"))

    _merge_bench_json("sharded", {
        "config": {"gamma": gamma, "k": k, "batch": batch,
                   "n_rounds": len(rounds),
                   "n_requests": sum(len(r) for r in rounds),
                   "quick": quick, "page_size": PAGE_SIZE, "vocab": VOCAB,
                   "mesh": {"data": 1, "model": 4, "kv_seq_axis": "model"}},
        "single_device": dict(ref),
        "sharded": dict(shd),
        "per_round_sharded": marks_sh,
        "tokens_equal": tokens_equal,
        "cross_wave_hit_tokens": cross_wave_hit_tokens,
        # per-shard pool view: page IDENTITY is global, so occupancy (and
        # hence utilization) is identical on every shard — what differs
        # is the per-shard footprint, pool_shard_slots KV slots per shard
        "pool_shard_slots": shd["pool_shard_slots"],
        "pool_shard_utilization": shd["pool_utilization"],
        "decode_collective_bytes": shd["decode_collective_bytes"],
    })


def run_sharded(quick: bool = False) -> None:
    """Sharded resident serving: the resident submit→drain burst schedule
    replayed through ONE engine spanning a 4-way ``kv_seq`` host mesh vs
    the single-device engine. Asserts per-request token identity, cross-
    wave prefix hits through the sharded engine pool, and reports the
    per-shard pool placement (``pool_shard_slots`` slots/shard, identical
    per-shard utilization — page identity is global) plus the
    ``decode_collective_bytes`` the verify LSE-psum moves per run.
    Runs in this process, which must see at least 4 devices: on the CPU,
    start it with ``XLA_FLAGS=--xla_force_host_platform_device_count=4``.
    """
    import jax
    if jax.device_count() < 4:
        raise RuntimeError(
            f"sharded serving bench needs 4 devices, {jax.device_count()} "
            f"visible; on the CPU set XLA_FLAGS="
            f"--xla_force_host_platform_device_count=4")
    _run_sharded_inline(quick)


if __name__ == "__main__":
    if "--sla" in sys.argv:
        run_sla("--quick" in sys.argv)
    elif "--resident" in sys.argv:
        run_resident("--quick" in sys.argv)
    elif "--prefix" in sys.argv:
        run_prefix("--quick" in sys.argv)
    elif "--sharded" in sys.argv:
        run_sharded("--quick" in sys.argv)
    elif "--bytes-model" in sys.argv:
        run_bytes_model("--quick" in sys.argv)
    else:
        run("--quick" in sys.argv)
