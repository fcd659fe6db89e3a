"""Analytic bytes-moved-per-decode-cycle model for the KV read path.

The point of kernelizing the paged read path (``attn_impl="pallas"``) is a
BANDWIDTH claim: per decode cycle, the gather path's HBM traffic scales
with cache *capacity* (``max_pages * page_size`` slots are gathered into a
dense logical view, written back, and re-read by attention regardless of
how much of the cache is live), while the kernel path's traffic scales
with *live* length (the page-table index_map clamps dead logical pages to
the last live one, and Pallas elides repeated-block DMAs — see
``kernels/cascade_attention.cascade_phase1_paged``). Bytes are all this
model prices: the kernel takes one grid step per table entry, and a dead
entry's step moves no bytes and does no math but still costs the
pipeline's per-step overhead, so device time does not follow these bytes
alone.

This module prices both paths from config + geometry alone so the serving
bench can emit an attributable ``bytes_model`` section; the companion HLO
attribution (``hlo_analysis.HloModuleStats``: ``gather_bytes`` /
``dynamic_slice_bytes`` of the compiled decode cycle) cross-checks the
shape of the claim on the actual lowering.

Counting rules (deliberately simple, stated so the numbers are auditable):

* Only cache READ traffic is counted — the part the read-path choice
  changes. QKV/MLP matmuls, block KV, tree merge, and commit writes are
  identical across impls and excluded.
* K and V each count once per layer (factor 2).
* "gather" (paged global layers): pool gather read (capacity slots) +
  dense logical-view write (capacity slots) + attention re-read of the
  view (capacity slots) = 3x capacity-sized traffic per layer. This
  matches what XLA materializes for ``kvcache.pool_view`` +
  ``attend_cache_plus_block``.
* "pallas" (paged global layers): ceil(live / page_size) page-sized DMA
  streams per layer — live-length traffic, rounded up to page
  granularity (the kernel reads each live page once per KV head for all
  the query heads that share it). Split-K re-streaming is a
  hardware-scheduling detail the model ignores on both paths.
* ROLLING local layers (dense window-capped buffers, both cache impls):
  "gather" reads the rolling buffer, materializes the [cache; block]
  concat, and re-reads it in attention = 3x window-capped capacity per
  layer; "pallas" streams the buffer ONCE through the dense cascade
  kernel, padded up to the split grid (``ceil(cap / (ns*bk)) * ns*bk``
  with ``ns = min(n_splits, ceil(cap/bk))`` — the padded slots are
  masked dead but still DMA'd). 3x -> ~1x at window scale, NOT
  live-length scaling: every rolling slot is a live candidate.
* ``kv_shards`` > 1 (kv_seq-sharded pools read through the shard_map
  hook, ``distributed/spdecode.sharded_paged_cache_attend`` — verify
  layers AND drafter feature caches): pool payload bytes are sharded
  within each page, so PER-SHARD read traffic is the unsharded figure
  divided by ``kv_shards`` on both impls. The figures reported here are
  per-shard; the fp32 LSE psum that merges shard partials is collective
  (not HBM-read) traffic and is counted by the engine's PAYLOAD_TRACE
  stat, not this model. Rolling local layers are replicated (never
  kv_seq-sharded) and do not divide.
"""
from __future__ import annotations

import math
from typing import Dict

import jax.numpy as jnp


def _esize(dtype) -> int:
    return jnp.dtype(dtype).itemsize


def _global_layers(cfg) -> int:
    return sum(1 for k in cfg.pattern_for_depth() if k == "global")


def _local_layers(cfg) -> int:
    return sum(1 for k in cfg.pattern_for_depth() if k == "local")


def rolling_padded_cap(cap: int, *, n_splits: int = 8, bk: int = 512) -> int:
    """Slots the dense cascade kernel streams for a rolling buffer of
    capacity ``cap``: padded up to the split grid (the padded slots are
    masked dead — ``slot >= cap`` — but still DMA'd). Mirrors
    ``kernels/cascade_attention.cascade_phase1``'s split-count invariant
    ``ns = min(n_splits, ceil(cap/bk))``."""
    ns = max(1, min(n_splits, -(-cap // bk)))
    return -(-cap // (ns * bk)) * (ns * bk)


def target_read_bytes(cfg, *, batch: int, page_size: int, max_pages: int,
                      cache_len: int, impl: str, kv_shards: int = 1,
                      n_splits: int = 8, bk: int = 512) -> Dict[str, float]:
    """Per-cycle cache read bytes of the TARGET: paged global layers
    (per-shard when ``kv_shards`` > 1) plus dense ROLLING local layers
    (window-capped capacity; replicated, never sharded).

    Returns a dict with per-component attribution and a ``total``.
    """
    assert impl in ("gather", "pallas"), impl
    n_l = _global_layers(cfg)
    slot = cfg.num_kv_heads * cfg.head_dim * _esize(cfg.dtype)
    cap_slots = max_pages * page_size
    if impl == "gather":
        per_layer = batch * cap_slots * slot * 2 / kv_shards   # K and V
        comp = {
            "pool_gather_read": float(n_l * per_layer),
            "logical_view_write": float(n_l * per_layer),
            "attend_view_read": float(n_l * per_layer),
        }
    else:
        live_slots = math.ceil(cache_len / page_size) * page_size
        comp = {
            "kernel_page_stream": float(
                n_l * batch * live_slots * slot * 2 / kv_shards),
        }
    n_roll = _local_layers(cfg)
    if n_roll:
        roll_cap = min(max_pages * page_size, cfg.sliding_window)
        per_layer = batch * roll_cap * slot * 2               # K and V
        if impl == "gather":
            comp["rolling_cache_read"] = float(n_roll * per_layer)
            comp["rolling_concat_write"] = float(n_roll * per_layer)
            comp["rolling_attend_read"] = float(n_roll * per_layer)
        else:
            pad = rolling_padded_cap(roll_cap, n_splits=n_splits, bk=bk)
            comp["rolling_kernel_stream"] = float(
                n_roll * batch * pad * slot * 2)
    comp["total"] = float(sum(comp.values()))
    comp["layers"] = n_l + n_roll
    return comp


def drafter_read_bytes(dcfg, *, batch: int, page_size: int, max_pages: int,
                       cache_len: int, impl: str, kv_shards: int = 1,
                       drafts_per_cycle: int = 1) -> Dict[str, float]:
    """Per-cycle paged feature-cache read bytes of ONE drafter.

    Same counting rules as :func:`target_read_bytes`; every drafter layer
    reads the full feature cache (``core/drafter.py`` injects projected
    context K/V at each layer). ``drafts_per_cycle``: how many forward
    passes this drafter runs per decode cycle (the VP second draft runs
    once per branch batch, still one forward).

    ``kv_shards`` > 1: the feature pool is read through the shard_map
    hook (``sharded_paged_cache_attend``) — each shard touches only its
    within-page slice, so per-shard bytes divide by ``kv_shards``; the
    pre-hook behaviour (dense GSPMD ``pool_view`` gather every cycle) is
    the ``kv_shards=1`` gather figure. Note the sharded gather path has
    no once-for-all-layers view: the hook gathers the local slice inside
    every per-layer call, so gather read/write scale with ``layers``.
    """
    assert impl in ("gather", "pallas"), impl
    n_l = dcfg.num_layers
    slot = dcfg.num_kv_heads * dcfg.head_dim * _esize(dcfg.dtype)
    cap_slots = max_pages * page_size
    if impl == "gather":
        # unsharded: pool_view gathers ONCE for all layers
        # (core/drafter.py), then each layer re-reads the dense view;
        # sharded: every layer's shard_map call gathers its local slice
        once = batch * cap_slots * slot * 2 / kv_shards
        gathers = n_l if kv_shards > 1 else 1
        comp = {
            "pool_gather_read": float(drafts_per_cycle * gathers * once),
            "logical_view_write": float(drafts_per_cycle * gathers * once),
            "attend_view_read": float(drafts_per_cycle * n_l * once),
        }
    else:
        live_slots = math.ceil(cache_len / page_size) * page_size
        comp = {
            "kernel_page_stream": float(
                drafts_per_cycle * n_l * batch * live_slots * slot * 2
                / kv_shards),
        }
    comp["total"] = float(sum(comp.values()))
    comp["layers"] = n_l
    return comp


def cycle_read_bytes(tcfg, d1cfg, d2cfg, *, batch: int, page_size: int,
                     max_pages: int, cache_len: int, impl: str,
                     kv_shards: int = 1) -> Dict:
    """Whole-cycle cache read bytes: target verify + both drafters
    (per-shard figures when ``kv_shards`` > 1)."""
    tgt = target_read_bytes(tcfg, batch=batch, page_size=page_size,
                            max_pages=max_pages, cache_len=cache_len,
                            impl=impl, kv_shards=kv_shards)
    d1 = drafter_read_bytes(d1cfg, batch=batch, page_size=page_size,
                            max_pages=max_pages, cache_len=cache_len,
                            impl=impl, kv_shards=kv_shards)
    d2 = drafter_read_bytes(d2cfg, batch=batch, page_size=page_size,
                            max_pages=max_pages, cache_len=cache_len,
                            impl=impl, kv_shards=kv_shards)
    return {
        "impl": impl,
        "batch": batch,
        "page_size": page_size,
        "max_pages": max_pages,
        "cache_len": cache_len,
        "kv_shards": kv_shards,
        "target": tgt,
        "drafter1": d1,
        "drafter2": d2,
        "total": tgt["total"] + d1["total"] + d2["total"],
    }
