"""Kernel microbench.

Two sections, both emitted into ``BENCH_kernels.json`` (section
``kernels``) via ``benchmarks/run.py --suite kernels``:

* ``ref`` — wall time of the pure-jnp reference flash path on CPU (the
  Pallas kernels target TPU and are validated in interpret mode — their
  CPU interpret time is not meaningful), plus analytic kernel FLOPs for
  roofline cross-checks.
* ``paged_cascade_ab`` — gather vs kernel READ-PATH A/B on one cascade
  verify call over a paged cache: the gather leg materializes the dense
  logical view from the page pool (exactly what ``kvcache.pool_view``
  does) and runs the dense cascade; the pallas leg calls
  ``ops.cascade_attention_paged`` directly on the pool + page table
  (interpret mode on CPU). Outputs are asserted numerically equal and
  each case reports the analytic read bytes of both paths
  (``roofline/bytes_model.py`` counting rules: gather moves 3x
  capacity-sized traffic, the kernel streams ceil(live/page) pages), so
  the A/B is attributable, not just timed.
* ``rolling_cascade_ab`` — the same A/B on ROLLING sliding-window
  buffers at non-block-aligned capacities (the configurations the old
  ``cap=s_pad`` plumbing recovered wrong positions for): gather
  materializes the [cache; block] concat (3x window-capped capacity),
  the kernel streams the buffer once, padded to the split grid. Outputs
  asserted equal against ``attend_cache_plus_block`` with rolling
  position recovery.
"""
from __future__ import annotations

import math
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import csv_row, merge_bench_json
from repro.kernels import ops as kops
from repro.kernels import ref

BENCH_PATH = Path(__file__).resolve().parents[1] / "BENCH_kernels.json"


def _time(fn, *args, iters=5):
    jax.block_until_ready(fn(*args))
    t0 = time.time()
    for _ in range(iters):
        jax.block_until_ready(fn(*args))
    return (time.time() - t0) / iters * 1e6


def _ref_section(quick: bool):
    cases = [
        ("flash_ref_prefill", (2, 8, 2, 512, 512, 64)),
        ("flash_ref_decode", (8, 8, 2, 16, 2048, 64)),
    ]
    if quick:
        cases = cases[:1]
    rows = []
    for name, (b, hq, hkv, tq, tkv, d) in cases:
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(ks[0], (b, hq, tq, d), jnp.float32)
        k = jax.random.normal(ks[1], (b, hkv, tkv, d), jnp.float32)
        v = jax.random.normal(ks[2], (b, hkv, tkv, d), jnp.float32)
        f = jax.jit(lambda q, k, v: ref.flash_attention_ref(q, k, v)[0])
        us = _time(f, q, k, v)
        flops = 4 * b * hq * tq * tkv * d
        print(csv_row(name, us, f"flops={flops:.3g}"))
        rows.append({"name": name, "us_per_call": us, "flops": flops})
    return rows


def _paged_case(b, hq, hkv, d, page, max_pages, cache_len, tq, iters):
    """One gather-vs-kernel cascade verify A/B over a paged cache."""
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    n_phys = b * max_pages
    pool_k = jax.random.normal(ks[0], (n_phys, page, hkv, d), jnp.float32)
    pool_v = jax.random.normal(ks[1], (n_phys, page, hkv, d), jnp.float32)
    # per-row page table: row b owns pages [b*mp, (b+1)*mp); pages past
    # the live length carry the out-of-range sentinel, like the engine's
    live_pages = math.ceil(cache_len / page)
    pt = np.full((b, max_pages), n_phys, np.int32)
    for r in range(b):
        pt[r, :live_pages] = r * max_pages + np.arange(live_pages)
    pt = jnp.asarray(pt)
    q = jax.random.normal(ks[2], (b, tq, hq, d), jnp.float32)
    blk_k = jax.random.normal(ks[3], (b, tq, hkv, d), jnp.float32)
    blk_v = jax.random.normal(ks[4], (b, tq, hkv, d), jnp.float32)
    clen = jnp.full((b,), cache_len, jnp.int32)
    q_abs = cache_len + jnp.broadcast_to(jnp.arange(tq, dtype=jnp.int32),
                                         (b, tq))
    tree = jnp.tril(jnp.ones((tq, tq), bool))

    def gather_leg(pool_k, pool_v, pt, q, blk_k, blk_v, clen, q_abs):
        # the pool_view read path: gather every table slot (capacity-
        # sized, dead pages clamped to a live one) into a dense view
        safe = jnp.minimum(pt, n_phys - 1)
        dk = pool_k[safe].reshape(b, max_pages * page, hkv, d)
        dv = pool_v[safe].reshape(b, max_pages * page, hkv, d)
        return kops.cascade_attention(
            q, dk, dv, blk_k, blk_v, cache_len=clen, q_abs=q_abs,
            tree_mask=tree, rolling=False, layout="BTHD")

    def pallas_leg(pool_k, pool_v, pt, q, blk_k, blk_v, clen, q_abs):
        return kops.cascade_attention_paged(
            q, pool_k, pool_v, pt, blk_k, blk_v, cache_len=clen,
            q_abs=q_abs, tree_mask=tree, layout="BTHD")

    args = (pool_k, pool_v, pt, q, blk_k, blk_v, clen, q_abs)
    yg = jax.jit(gather_leg)(*args)
    yp = jax.jit(pallas_leg)(*args)
    err = float(jnp.max(jnp.abs(yg - yp)))
    assert err < 1e-4, f"gather vs pallas mismatch: max err {err}"
    us_g = _time(jax.jit(gather_leg), *args, iters=iters)
    us_p = _time(jax.jit(pallas_leg), *args, iters=iters)
    # analytic read bytes (bytes_model counting rules, 1 layer, K+V)
    slot = hkv * d * 4
    gather_bytes = 3 * b * max_pages * page * slot * 2
    pallas_bytes = b * live_pages * page * slot * 2
    return {
        "batch": b, "page_size": page, "max_pages": max_pages,
        "cache_len": cache_len, "tq": tq,
        "gather_us": us_g, "pallas_interpret_us": us_p,
        "max_abs_err": err,
        "gather_read_bytes": gather_bytes,
        "pallas_read_bytes": pallas_bytes,
    }


def _rolling_case(b, hq, hkv, d, cap, window, cache_len, tq, iters,
                  n_splits=4, bk=64):
    """Gather-vs-kernel A/B on one ROLLING sliding-window cascade call:
    the gather leg concatenates [rolling cache; block] and attends with
    recovered positions (``attend_cache_plus_block`` semantics via the
    oracle); the kernel leg runs the dense cascade with rolling=True and
    the TRUE capacity as modulus."""
    from repro.models.attention import attend_cache_plus_block
    ks = jax.random.split(jax.random.PRNGKey(2), 5)
    q = jax.random.normal(ks[0], (b, tq, hq, d), jnp.float32)
    ck = jax.random.normal(ks[1], (b, cap, hkv, d), jnp.float32)
    cv = jax.random.normal(ks[2], (b, cap, hkv, d), jnp.float32)
    blk_k = jax.random.normal(ks[3], (b, tq, hkv, d), jnp.float32)
    blk_v = jax.random.normal(ks[4], (b, tq, hkv, d), jnp.float32)
    clen = jnp.full((b,), cache_len, jnp.int32)
    q_abs = cache_len + jnp.broadcast_to(jnp.arange(tq, dtype=jnp.int32),
                                         (b, tq))
    tree = jnp.tril(jnp.ones((tq, tq), bool))

    def gather_leg(q, ck, cv, blk_k, blk_v, clen, q_abs):
        kk = jnp.concatenate([ck, blk_k], axis=1)
        vv = jnp.concatenate([cv, blk_v], axis=1)
        return attend_cache_plus_block(
            q, kk, vv, cache_cap=cap, cache_len=clen, q_abs=q_abs,
            window=window, extra_mask=tree, attn_softcap=None,
            impl="dense", kv_chunk=1024, rolling=True)

    def kernel_leg(q, ck, cv, blk_k, blk_v, clen, q_abs):
        return kops.cascade_attention(
            q, ck, cv, blk_k, blk_v, cache_len=clen, q_abs=q_abs,
            tree_mask=tree, window=window, rolling=True,
            n_splits=n_splits, bk=bk, layout="BTHD")

    args = (q, ck, cv, blk_k, blk_v, clen, q_abs)
    yg = jax.jit(gather_leg)(*args)
    yp = jax.jit(kernel_leg)(*args)
    err = float(jnp.max(jnp.abs(yg - yp)))
    assert err < 1e-4, f"rolling gather vs kernel mismatch: max err {err}"
    us_g = _time(jax.jit(gather_leg), *args, iters=iters)
    us_p = _time(jax.jit(kernel_leg), *args, iters=iters)
    # analytic read bytes (bytes_model rolling rules, 1 layer, K+V):
    # gather = 3x window-capped capacity, kernel = split-grid-padded cap
    from repro.roofline.bytes_model import rolling_padded_cap
    slot = hkv * d * 4
    pad = rolling_padded_cap(cap, n_splits=n_splits, bk=bk)
    return {
        "batch": b, "capacity": cap, "window": window,
        "cache_len": cache_len, "tq": tq,
        "gather_us": us_g, "pallas_interpret_us": us_p,
        "max_abs_err": err,
        "gather_read_bytes": 3 * b * cap * slot * 2,
        "pallas_read_bytes": b * pad * slot * 2,
    }


def _rolling_section(quick: bool):
    # non-block-aligned capacities (bk=64), pre-wrap and wrapped lens —
    # the configurations the old cap=s_pad plumbing got WRONG
    geoms = [(97, 97, 150), (505, 200, 711)] if quick else [
        (97, 97, 150), (131, 96, 70), (505, 200, 711), (509, 509, 1000)]
    rows = []
    for cap, window, clen in geoms:
        r = _rolling_case(b=2, hq=4, hkv=2, d=16, cap=cap, window=window,
                          cache_len=clen, tq=4, iters=2 if quick else 3)
        print(csv_row(
            f"rolling_cascade_cap{cap}_win{window}_live{clen}",
            r["gather_us"],
            f"pallas_interpret_us={r['pallas_interpret_us']:.1f} "
            f"gather_bytes={r['gather_read_bytes']:.3g} "
            f"pallas_bytes={r['pallas_read_bytes']:.3g} "
            f"max_err={r['max_abs_err']:.2e}"))
        rows.append(r)
    # 3x capacity vs ~1x padded capacity, asserted on the analytic model
    for r in rows:
        assert r["pallas_read_bytes"] < r["gather_read_bytes"], r
    return rows


def _paged_section(quick: bool):
    # fixed live length, growing capacity: gather traffic scales with
    # capacity, the kernel's stays put (the attributable claim)
    geoms = [(4, 24), (16, 24)] if quick else [(4, 24), (16, 24), (32, 24),
                                               (32, 200)]
    rows = []
    for mp, clen in geoms:
        r = _paged_case(b=2, hq=4, hkv=2, d=16, page=16, max_pages=mp,
                        cache_len=clen, tq=4, iters=2 if quick else 3)
        print(csv_row(
            f"paged_cascade_cap{mp}_live{clen}", r["gather_us"],
            f"pallas_interpret_us={r['pallas_interpret_us']:.1f} "
            f"gather_bytes={r['gather_read_bytes']:.3g} "
            f"pallas_bytes={r['pallas_read_bytes']:.3g} "
            f"max_err={r['max_abs_err']:.2e}"))
        rows.append(r)
    # the claim itself, asserted on the analytic model
    by_cap = [r for r in rows if r["cache_len"] == 24]
    assert by_cap[-1]["gather_read_bytes"] > by_cap[0]["gather_read_bytes"]
    assert (by_cap[-1]["pallas_read_bytes"]
            == by_cap[0]["pallas_read_bytes"])
    return rows


def run(quick: bool = False):
    print("# kernel microbench  name,us_per_call,derived")
    ref_rows = _ref_section(quick)
    ab_rows = _paged_section(quick)
    roll_rows = _rolling_section(quick)
    merge_bench_json(BENCH_PATH, "kernels", {
        "ref": ref_rows,
        "paged_cascade_ab": ab_rows,
        "rolling_cascade_ab": roll_rows,
        "notes": "pallas legs run in interpret mode on CPU: correctness "
                 "and bytes attribution are meaningful, wall time is not",
    })
    return True


if __name__ == "__main__":
    import sys
    run("--quick" in sys.argv)
