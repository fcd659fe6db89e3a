"""Typed decode-engine state.

:class:`EngineState` is the single pytree that flows through the decode
loop — target model states (KV caches / recurrent states), the two
drafter feature caches, and the anchor token of the next block. It is
frozen and pytree-registered, so it jits, donates, and crosses a
``jax.lax.while_loop`` boundary unchanged; every cycle produces a *new*
EngineState via :meth:`replace`.

Field shapes are allocated once per request wave by :func:`engine_init`
(static ``batch`` / ``max_len``), which is what lets the whole generation
loop run on device without host round-trips.

KV storage is pluggable (``cache_impl``): ``dense`` keeps per-row
contiguous buffers; ``paged`` backs the target global-attention KV and
both feature caches with shared page pools + per-row page tables (see
``repro.models.kvcache``). In paged mode slot refill is copy-free:
:func:`row_template` builds a batch-1 state that *shares* the wave's
pools with a one-row page table of freshly allocated pages, ``prefill``
writes the prompt KV straight into those pages, and :meth:`adopt_row`
then only patches the page-table row and splices the small dense leaves.
:func:`install_row` wraps that sequence in a donated ``jit`` so the whole
install lowers to in-place page writes (the dense path gets the same
donated treatment, turning the old full-state ``adopt_row`` copy into an
in-place row splice).

Pool ownership is external (the borrowed-pool contract): a serving wave's
state *borrows* its page-pool buffers from the engine-lifetime
``PagePool`` — :func:`capture_pools` harvests them at wave turnover and
:func:`engine_init` re-adopts them directly into the next wave's state
(``pools=``, skipping the transient zero allocation; :func:`adopt_pools`
is the post-hoc variant for states built elsewhere), so pages the radix
prefix cache retained keep their KV across ``start_wave``. The same
contract extends INSIDE a wave to overlapped installs: every install
(:func:`install_row` / the batched :func:`install_rows`) donates the wave
state and writes only freshly allocated pages plus its own page-table
rows and dense-leaf rows, so the host may dispatch installs for idle
slots while a decode cycle for the *other* rows is still in flight on
device — the two operations touch disjoint pages/rows, and JAX's async
dispatch serializes them on the donated state without a host sync. The
only host read of device state an install needs (the prefilled anchor
token) is deferred by the engine to the next retire boundary.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import drafter as dr
from repro.models import kvcache as kvc
from repro.models import lm


def _feat_axis(name: str) -> int:
    """Batch axis of a feature-cache leaf by key: "length" and "pt" are
    batch-leading [B, ...], k/v are [L, B, ...]. (Paged traversals handle
    "pt" before consulting this — the 0 here keeps the contract honest for
    any caller that does not.)"""
    return 0 if name in ("length", "pt") else 1


@dataclasses.dataclass(frozen=True)
class EngineState:
    """Per-wave decode state (all leaves batched on axis 0 or equivalent).

    target:  ``lm.init_states`` dict — per-layer KV caches / recurrent
             states plus per-example committed ``length`` [B].
    d1_feat: first-drafter feature cache (``drafter.init_feat_cache``).
    d2_feat: second-drafter feature cache.
    anchor:  [B] int32 — the bonus token that roots the next draft block.
    active:  [B] bool — rows still generating. Inactive rows draft a
             degenerate root-only tree, commit zero tokens, and skip every
             KV / feature-cache write, so a finished (or idle) row costs
             no state mutation inside the decode loop and its slot can be
             re-prefilled in place via :meth:`adopt_row`.
    """
    target: Dict[str, Any]
    d1_feat: Dict[str, Any]
    d2_feat: Dict[str, Any]
    anchor: jnp.ndarray
    active: jnp.ndarray

    @property
    def length(self) -> jnp.ndarray:
        """[B] number of committed target positions."""
        return self.target["length"]

    @property
    def batch(self) -> int:
        return self.anchor.shape[0]

    @property
    def cache_impl(self) -> str:
        """"dense" | "paged" — detected structurally (feature caches are
        paged exactly when the wave is)."""
        return "paged" if kvc.is_paged(self.d1_feat) else "dense"

    @property
    def max_len(self) -> int:
        """Static logical cache capacity this state was allocated with
        (max_pages * page_size when paged)."""
        if kvc.is_paged(self.d1_feat):
            return kvc.logical_len(self.d1_feat)
        return self.d1_feat["k"].shape[2]

    @property
    def page_size(self) -> int:
        return kvc.page_geometry(self.d1_feat)[0]

    @property
    def max_pages(self) -> int:
        return kvc.page_geometry(self.d1_feat)[1]

    def replace(self, **kw) -> "EngineState":
        return dataclasses.replace(self, **kw)

    def adopt_row(self, row, other: "EngineState",
                  src_row: int = 0) -> "EngineState":
        """Splice ``other``'s ``src_row`` into this state's ``row``.

        This is the slot-refill primitive: a retired request's row is
        overwritten with a freshly prefilled single-request state (same
        ``max_len``), leaving every other row untouched. ``row`` may be a
        traced index; ``other`` is typically batch-1.

        Paged caches follow the shared-pool contract: ``other`` must hold
        the *same* (updated) pools as ``self`` — built via
        :func:`row_template` — so its k/v pool arrays pass through
        wholesale and only the page-table row is spliced. Under a donated
        jit that makes the adopt an in-place row/table write instead of a
        full-state copy.
        """
        return EngineState(
            target=_adopt_dict(self.target, other.target, row, src_row,
                               lm.state_batch_axis),
            d1_feat=_adopt_block(self.d1_feat, other.d1_feat, row, src_row,
                                 _feat_axis),
            d2_feat=_adopt_block(self.d2_feat, other.d2_feat, row, src_row,
                                 _feat_axis),
            anchor=_splice_row(self.anchor, other.anchor, row, src_row, 0),
            active=_splice_row(self.active, other.active, row, src_row, 0),
        )


jax.tree_util.register_pytree_node(
    EngineState,
    lambda s: ((s.target, s.d1_feat, s.d2_feat, s.anchor, s.active), None),
    lambda _, ch: EngineState(*ch),
)


def _splice_row(dst, src, row, src_row, axis):
    """Write src[..., src_row, ...] into dst at ``row`` along ``axis``."""
    if not hasattr(dst, "ndim") or dst.ndim == 0:
        return dst
    sl = jax.lax.index_in_dim(src, src_row, axis, keepdims=False)
    return jax.lax.dynamic_update_index_in_dim(
        dst, sl.astype(dst.dtype), row, axis)


def _adopt_block(dst, src, row, src_row, axis_for):
    """Adopt one block/cache dict; ``axis_for(key)`` gives the batch axis
    of dense leaves. Paged pools pass through from ``src`` (shared-pool
    contract) and the page table splices along its own batch axis."""
    out = {}
    paged = kvc.is_paged(dst)
    for name, v in dst.items():
        if paged and name in ("k", "v"):
            out[name] = src[name]
        elif name == "pt":
            out[name] = _splice_row(v, src[name], row, src_row, v.ndim - 2)
        else:
            ax = axis_for(name)
            out[name] = jax.tree.map(
                lambda d, s, a=ax: _splice_row(d, s, row, src_row, a),
                v, src[name])
    return out


def _adopt_dict(dst, src, row, src_row, axis_for):
    out = {}
    for name, v in dst.items():
        if isinstance(v, dict):
            out[name] = _adopt_block(v, src[name], row, src_row,
                                     lambda _n, a=axis_for(name): a)
        else:
            out[name] = _splice_row(v, src[name], row, src_row,
                                    axis_for(name))
    return out


def engine_init(bundle, batch: int, max_len: int, ctx_len: int = 0,
                cache_impl: str = "dense", page_size: int = 64,
                pool_pages=None, page_table=None,
                pools: Optional[Dict[str, Any]] = None) -> EngineState:
    """Allocate caches for a request wave (``bundle``: pipeline.SpecBundle).

    cache_impl="paged": every paged cache of the wave (target global KV
    and both feature caches) shares ONE page-id space: ``page_table``
    [B, max_pages] applies to all of them, and ``pool_pages`` sizes each
    pool. Defaults reproduce the allocator-free identity layout (row i
    owns pages [i*MP, (i+1)*MP)) used by ``generate``; the serving engine
    passes an initially-unallocated table and patches rows at install.

    pools: retained device pool buffers from :func:`capture_pools` of the
    previous wave (the borrowed-pool contract). Caches named in it adopt
    the retained buffers DIRECTLY at init — the transient pool-sized zero
    allocation a post-hoc ``adopt_pools`` would immediately discard is
    never materialized. Geometry must match the allocation this call
    would have made; the caller must drop its own reference once the
    wave's first donated install consumes the state.
    """
    tcfg = bundle.target_cfg
    dt = jnp.dtype(tcfg.dtype)
    if cache_impl == "paged":
        pool_pages, page_table = kvc.default_page_layout(
            batch, max_len, page_size, pool_pages, page_table)
    else:
        assert not pools, "retained pool buffers require cache_impl='paged'"
    pools = pools or {}
    kw = dict(cache_impl=cache_impl, page_size=page_size,
              pool_pages=pool_pages, page_table=page_table)
    tgt_pools = {name[len("target/"):]: kv for name, kv in pools.items()
                 if name.startswith("target/")}
    return EngineState(
        target=lm.init_states(tcfg, batch, max_len, ctx_len=ctx_len,
                              dtype=dt, ext_pools=tgt_pools or None, **kw),
        d1_feat=dr.init_feat_cache(bundle.d1_cfg, batch, max_len,
                                   dtype=jnp.dtype(bundle.d1_cfg.dtype),
                                   ext_pool=pools.get("d1_feat"), **kw),
        d2_feat=dr.init_feat_cache(bundle.d2_cfg, batch, max_len,
                                   dtype=jnp.dtype(bundle.d2_cfg.dtype),
                                   ext_pool=pools.get("d2_feat"), **kw),
        anchor=jnp.zeros((batch,), jnp.int32),
        active=jnp.ones((batch,), bool),
    )


@jax.named_scope("d2sd.install")
def prefill(bundle, state: EngineState, prompts, key=None, ctx=None,
            temperature: float = 0.0, true_len=None,
            start=None) -> EngineState:
    """Process prompts [B, P]; sets anchor = first generated token.

    cache_len is passed as a SCALAR 0: prefill always starts at offset 0, so
    the KV write lowers to dynamic-update-slice (partitionable along the
    kv_seq axis with zero communication) instead of a gather-scatter
    (§Perf: this was 2x9.6GB/layer of all-gather on 32k prefill).

    true_len ([B] or scalar, traced): ``prompts`` is padded to a bucketed
    length and only the first ``true_len`` tokens per row are real — KV
    writes and feature-cache entries beyond are dropped, recurrent states
    snapshot at exactly ``true_len`` consumed tokens, the committed
    ``length`` advances by ``true_len``, and the anchor reads the logits
    at position ``true_len - 1``. Lets one install trace serve every
    prompt length in a bucket (O(buckets) compiles, not O(lengths)).

    start ([B] or scalar, traced): warm start — the caches already hold
    ``start`` committed positions (a prefix-cache hit spliced the shared
    pages into this row), ``prompts`` is only the *uncached suffix*, and
    the forward attends [cache ++ suffix] with positions offset by
    ``start``. Caller must have set the state's lengths to ``start``.
    """
    b, p = prompts.shape
    warm = start is not None
    if warm:
        cl = jnp.broadcast_to(jnp.asarray(start, jnp.int32).reshape(-1), (b,))
    else:
        cl = jnp.zeros((), jnp.int32)
    snap = None
    if true_len is not None:
        snap = jnp.broadcast_to(jnp.asarray(true_len, jnp.int32).reshape(-1),
                                (b,))
    out = lm.forward(bundle.target_params, prompts, bundle.target_cfg,
                     states=state.target, cache_len=cl,
                     write_kv=True, snap_at=snap, attend_cache_on_write=warm,
                     ctx=ctx, want_features=True, remat=False)
    base = cl[:, None] if warm else jnp.zeros((b, 1), jnp.int32)
    positions = base + jnp.arange(p, dtype=jnp.int32)[None, :]
    counts = snap if snap is not None else jnp.full((b,), p)
    d1_feat = dr.extend_feat_cache(
        bundle.d1_params, bundle.d1_cfg, state.d1_feat, out["features"],
        positions, counts)
    d2_feat = dr.extend_feat_cache(
        bundle.d2_params, bundle.d2_cfg, state.d2_feat, out["features"],
        positions, counts)
    if snap is None:
        last = out["logits"][:, -1].astype(jnp.float32)
    else:
        last = jnp.take_along_axis(
            out["logits"], jnp.maximum(snap - 1, 0)[:, None, None],
            axis=1)[:, 0].astype(jnp.float32)
    if temperature > 0:
        anchor = jax.random.categorical(key, last / temperature)
    else:
        anchor = jnp.argmax(last, axis=-1)
    return state.replace(target=out["states"], d1_feat=d1_feat,
                         d2_feat=d2_feat,
                         anchor=anchor.astype(jnp.int32))


# ------------------------------------------------------- slot install -------
def _zeros_rows(a, ax, k):
    if not hasattr(a, "ndim") or a.ndim == 0:
        return a
    return jnp.zeros(a.shape[:ax] + (k,) + a.shape[ax + 1:], a.dtype)


def rows_template(state: EngineState, row_tables) -> EngineState:
    """Batch-K install target *sharing* this wave's page pools.

    ``row_tables`` [K, max_pages] int32: one row of physical pages per
    incoming request (unallocated slots = :data:`kvc.PAGE_SENTINEL`).
    Dense leaves (local rolling KV, recurrent states, lengths, anchor)
    become zeroed batch-K rows; paged pools are passed by reference with
    the K-row table, so a ``prefill`` on the result writes every
    request's KV directly into the wave's pools at its own pages.
    ``adopt_row(..., src_row=i)`` afterwards only patches page-table rows
    and splices the small dense leaves — the copy-free refill contract,
    K requests per donated trace.
    """
    rt = jnp.asarray(row_tables, jnp.int32)                 # [K, MP]
    k = rt.shape[0]

    def blk(d, axis_for):
        paged = kvc.is_paged(d)
        out = {}
        for name, v in d.items():
            if paged and name in ("k", "v"):
                out[name] = v
            elif name == "pt":
                out[name] = jnp.broadcast_to(
                    rt, v.shape[:-2] + (k, v.shape[-1]))
            else:
                ax = axis_for(name)
                out[name] = jax.tree.map(
                    lambda a, x=ax: _zeros_rows(a, x, k), v)
        return out

    target = {}
    for name, v in state.target.items():
        if isinstance(v, dict):
            target[name] = blk(v, lambda _n, a=lm.state_batch_axis(name): a)
        else:
            target[name] = _zeros_rows(v, 0, k)
    return EngineState(
        target=target,
        d1_feat=blk(state.d1_feat, _feat_axis),
        d2_feat=blk(state.d2_feat, _feat_axis),
        anchor=jnp.zeros((k,), jnp.int32),
        active=jnp.ones((k,), bool),
    )


def row_template(state: EngineState, row_table) -> EngineState:
    """Batch-1 :func:`rows_template` (``row_table`` [max_pages])."""
    return rows_template(state, jnp.asarray(row_table, jnp.int32)[None])


def _with_lengths(sub: EngineState, length) -> EngineState:
    """Batch-K state with every committed-length leaf set to ``length``
    ([K] vector or scalar — warm install: the spliced shared pages
    already hold that many committed positions per row)."""
    k = sub.anchor.shape[0]
    lk = jnp.broadcast_to(jnp.asarray(length, jnp.int32).reshape(-1), (k,))
    return sub.replace(target={**sub.target, "length": lk},
                       d1_feat={**sub.d1_feat, "length": lk},
                       d2_feat={**sub.d2_feat, "length": lk})


def _map_paged_pools(state: EngineState, fn) -> EngineState:
    """Apply ``fn(pool)`` to the k/v pool of every paged cache dict."""
    def blk(d):
        if not kvc.is_paged(d):
            return d
        return {**d, "k": fn(d["k"]), "v": fn(d["v"])}

    target = {name: (blk(v) if isinstance(v, dict) else v)
              for name, v in state.target.items()}
    return state.replace(target=target, d1_feat=blk(state.d1_feat),
                         d2_feat=blk(state.d2_feat))


# ------------------------------------------------- borrowed-pool contract ---
def capture_pools(state: EngineState) -> Dict[str, Any]:
    """Harvest the physical k/v page-pool buffers of every paged cache.

    The pool buffers ``[*lead, P, page, H, D]`` are batch-free — only the
    page table and the dense leaves depend on the wave geometry — so an
    engine-lifetime :class:`~repro.models.kvcache.PagePool` can carry them
    *across* waves: at wave turnover the engine captures them here and
    re-installs them into the next wave's freshly allocated state via
    :func:`adopt_pools`, keeping every page the radix prefix cache owns
    bit-intact (cached prefixes survive ``start_wave``). Keys name the
    cache ("target/<entry>", "d1_feat", "d2_feat"); values are ``(k, v)``.

    Per-shard contract (mesh residency): the captured values are the
    device buffers THEMSELVES, placement included — on a mesh each
    buffer's payload is laid out along the ``kv_seq`` axis
    (:func:`~repro.models.kvcache.shard_pool`), and carrying the buffer
    across the turnover carries that per-shard layout with it, zero-copy
    (no gather to host, no resharding). Pool geometry stays the GLOBAL
    logical shape throughout; only the bytes are distributed.
    """
    pools: Dict[str, Any] = {}
    for name, v in state.target.items():
        if isinstance(v, dict) and kvc.is_paged(v):
            pools[f"target/{name}"] = (v["k"], v["v"])
    if kvc.is_paged(state.d1_feat):
        pools["d1_feat"] = (state.d1_feat["k"], state.d1_feat["v"])
    if kvc.is_paged(state.d2_feat):
        pools["d2_feat"] = (state.d2_feat["k"], state.d2_feat["v"])
    return pools


def adopt_pools(state: EngineState, pools: Dict[str, Any]) -> EngineState:
    """Install externally owned pool buffers (from :func:`capture_pools`)
    into a freshly initialized wave state — the borrowed-pool contract:
    the wave does not own its page pools, the engine does.

    Pool geometry (pool_pages / page_size / heads) must match the state's
    allocation; batch size and table width may differ freely. The caller
    must drop its own reference after the wave's first donated install
    consumes the state (the engine re-captures at wave turnover).

    Shapes are compared against the GLOBAL logical geometry: a borrowed
    buffer whose payload is sharded along ``kv_seq`` still reports its
    global shape, so the adoption check (and the zero-copy pass-through —
    the adopted array is installed as-is, never re-``device_put``) is
    layout-agnostic. Do not mix buffers captured under one mesh into an
    engine built under another; the engine's construction-time context is
    the single source of placement truth.
    """
    def blk(d, path):
        if not kvc.is_paged(d) or path not in pools:
            return d
        k, v = pools[path]
        assert k.shape == d["k"].shape and k.dtype == d["k"].dtype, (
            "borrowed pool geometry mismatch", path, k.shape, d["k"].shape)
        return {**d, "k": k, "v": v}

    target = {name: (blk(v, f"target/{name}") if isinstance(v, dict) else v)
              for name, v in state.target.items()}
    return state.replace(target=target,
                         d1_feat=blk(state.d1_feat, "d1_feat"),
                         d2_feat=blk(state.d2_feat, "d2_feat"))


def _cow_copy_impl(state: EngineState, src, dst) -> EngineState:
    return _map_paged_pools(state, lambda p: kvc.copy_page(p, src, dst))


_cow_copy_donated = functools.partial(
    jax.jit, donate_argnames=("state",))(_cow_copy_impl)


def cow_copy_page(state: EngineState, src, dst) -> EngineState:
    """Copy physical page ``src`` -> ``dst`` in EVERY paged pool of the
    wave (target global-attention KV and both drafter feature caches) —
    the copy-on-write step of a prefix-cache hit whose matched length
    ends inside a page. ``state`` is DONATED (in-place page write); one
    trace per state shapes (``src``/``dst`` are traced)."""
    assert state.cache_impl == "paged", "COW only exists for paged caches"
    return _cow_copy_donated(state, jnp.asarray(src, jnp.int32),
                             jnp.asarray(dst, jnp.int32))


@jax.named_scope("d2sd.install")
def _install_impl(bundle, state, row, prompt, key, row_table,
                  temperature: float, ctx_len: int, prefix_hit=None,
                  true_len=None, shard_tag=None):
    # shard_tag: static cache-splitter only (sharding.mesh_tag()) — the
    # trace reads the ambient mesh context (constrain / shard_map hooks),
    # which jit's aval-keyed cache cannot see; threading the tag lets one
    # process hold sharded and unsharded specializations side by side.
    del shard_tag
    if state.cache_impl == "paged":
        sub = row_template(state, row_table)
    else:
        sub = engine_init(bundle, 1, state.max_len, ctx_len=ctx_len)
    if prefix_hit is not None:
        sub = _with_lengths(sub, prefix_hit)
    sub = prefill(bundle, sub, prompt[None, :], key=key,
                  temperature=temperature, true_len=true_len,
                  start=prefix_hit)
    return state.adopt_row(row, sub)


# Donated install: `state` is consumed — XLA rewrites the row / tail pages
# in place instead of copying the wave state. One trace per
# (prompt-bucket length, warm/cold, state shapes); `row`, `row_table`,
# `prefix_hit` and `true_len` are traced.
_install_row_donated = functools.partial(
    jax.jit, static_argnames=("temperature", "ctx_len", "shard_tag"),
    donate_argnames=("state",))(_install_impl)


def install_row(bundle, state: EngineState, row, prompt, key=None,
                temperature: float = 0.0, row_table=None,
                ctx_len: int = 0, prefix_hit=None,
                true_len=None, shard_tag=None) -> EngineState:
    """Serving fast path: prefill ``prompt`` into ``row`` with the input
    ``state`` DONATED (caller must drop its reference). Paged states
    require ``row_table`` (the allocated pages); dense states splice via
    an in-place row write.

    prefix_hit (paged only): number of committed tokens already present
    in the row's spliced pages (a prefix-cache hit) — ``prompt`` then
    holds only the *uncached suffix* and the batch-1 prefill runs over
    it alone, attending to the shared prefix KV. Token-identical to a
    cold install of the full prompt (asserted by tests/serving bench).

    true_len: real token count when ``prompt`` is padded to a length
    bucket (see :func:`prefill`).
    """
    prompt = jnp.asarray(prompt, jnp.int32)
    if state.cache_impl == "paged":
        assert row_table is not None, "paged install needs allocated pages"
        row_table = jnp.asarray(row_table, jnp.int32)
    else:
        assert prefix_hit is None, "prefix-cache hits require paged KV"
    key = key if key is not None else jax.random.PRNGKey(0)
    if prefix_hit is not None:
        prefix_hit = jnp.asarray(prefix_hit, jnp.int32)
    if true_len is not None:
        true_len = jnp.asarray(true_len, jnp.int32)
    return _install_row_donated(bundle, state, jnp.asarray(row, jnp.int32),
                                prompt, key, row_table,
                                temperature=temperature, ctx_len=ctx_len,
                                prefix_hit=prefix_hit, true_len=true_len,
                                shard_tag=shard_tag)


@jax.named_scope("d2sd.install")
def _install_rows_impl(bundle, state, rows, prompts, key, row_tables,
                       temperature: float, ctx_len: int, true_len=None,
                       prefix_hits=None, shard_tag=None):
    del shard_tag                       # static cache-splitter (see above)
    k = prompts.shape[0]
    if state.cache_impl == "paged":
        sub = rows_template(state, row_tables)
    else:
        sub = engine_init(bundle, k, state.max_len, ctx_len=ctx_len)
    if prefix_hits is not None:
        # warm batch: every row's shared pages are already spliced into
        # its table row (and COW-copied where needed) by the host; the
        # per-row start vector offsets each suffix independently
        sub = _with_lengths(sub, prefix_hits)
    sub = prefill(bundle, sub, prompts, key=key, temperature=temperature,
                  true_len=true_len, start=prefix_hits)
    # K static adopts: paged pools pass through wholesale (every row's
    # prefill writes already landed in the shared pools), so each adopt
    # is one page-table row patch + small dense-leaf splices
    for i in range(k):
        state = state.adopt_row(rows[i], sub, src_row=i)
    return state


# Donated batched install: one trace per (K, prompt-bucket length,
# warm/cold, state shapes); `rows`, `row_tables`, `true_len` and
# `prefix_hits` are traced.
_install_rows_donated = functools.partial(
    jax.jit, static_argnames=("temperature", "ctx_len", "shard_tag"),
    donate_argnames=("state",))(_install_rows_impl)


def install_rows(bundle, state: EngineState, rows, prompts, key=None,
                 temperature: float = 0.0, row_tables=None,
                 ctx_len: int = 0, true_len=None, prefix_hits=None,
                 shard_tag=None) -> EngineState:
    """Batched serving install: prefill K same-length prompts into K rows
    under ONE donated jit call — the multi-slot analogue of
    :func:`install_row`, collapsing K per-request installs (K dispatches,
    K batch-1 prefills) into one batch-K prefill plus K in-place row
    splices. The async front-end uses it to drain same-length-bucket
    admission groups during the overlap window.

    rows:        [K] slot indices (traced).
    prompts:     [K, P] int32, all padded to one bucket length.
    row_tables:  [K, max_pages] allocated pages per request (paged only).
    true_len:    [K] real prompt lengths under bucket padding.
    prefix_hits: [K] warm-start lengths (paged only): row i's table
        already holds ``prefix_hits[i]`` committed tokens of shared
        prefix-cache pages — ``prompts[i]`` is only its (bucket-padded)
        uncached suffix and ``true_len[i]`` the suffix's real length. The
        host does all per-row COW orchestration BEFORE this call (the
        spliced tables must be write-safe); mixed hit/miss groups are not
        allowed — route misses through the cold path (``prefix_hits``
        absent) so every row shares one warm/cold trace.

    Semantics note: sampling (temperature > 0) draws the K anchors from
    one shared key — not bitwise-identical to K per-request keys — so the
    engine only routes temperature-0 installs here (greedy anchors are
    key-independent, making the batched path token-identical to K single
    installs — warm and cold; asserted by tests/test_frontend.py).
    """
    prompts = jnp.asarray(prompts, jnp.int32)
    rows = jnp.asarray(rows, jnp.int32)
    if state.cache_impl == "paged":
        assert row_tables is not None, "paged install needs allocated pages"
        row_tables = jnp.asarray(row_tables, jnp.int32)
    else:
        assert prefix_hits is None, "prefix-cache hits require paged KV"
    key = key if key is not None else jax.random.PRNGKey(0)
    if true_len is not None:
        true_len = jnp.asarray(true_len, jnp.int32)
    if prefix_hits is not None:
        prefix_hits = jnp.asarray(prefix_hits, jnp.int32)
    return _install_rows_donated(bundle, state, rows, prompts, key,
                                 row_tables, temperature=temperature,
                                 ctx_len=ctx_len, true_len=true_len,
                                 prefix_hits=prefix_hits,
                                 shard_tag=shard_tag)


def prefill_row(bundle, state: EngineState, row, prompt, key=None, ctx=None,
                temperature: float = 0.0, ctx_len: int = 0,
                row_table=None, prefix_hit=None,
                true_len=None) -> EngineState:
    """Prefill a single request into one row of an in-flight state
    (non-donating; ``state`` stays valid — see :func:`install_row` for the
    donated serving path).

    Dense: allocates a batch-1 state with the same ``max_len``, runs the
    normal prefill over ``prompt`` [P], and splices the result into
    ``row`` via :meth:`EngineState.adopt_row`. Paged: prefills through a
    pool-sharing :func:`row_template`; ``row_table`` defaults to the
    identity layout's pages for ``row`` (requires a concrete ``row``).
    """
    prompt = jnp.asarray(prompt, jnp.int32)
    if state.cache_impl == "paged" and row_table is None:
        mp = state.max_pages
        row_table = int(row) * mp + jnp.arange(mp, dtype=jnp.int32)
    if ctx is None:
        return _install_impl(bundle, state, row, prompt,
                             key if key is not None else jax.random.PRNGKey(0),
                             row_table, temperature, ctx_len,
                             prefix_hit=prefix_hit, true_len=true_len)
    # cross-attention contexts stay on the eager path (ctx shapes vary);
    # warm starts / bucketed padding are not plumbed through it
    assert prefix_hit is None and true_len is None, \
        "prefix_hit / true_len are not supported with a cross-attention ctx"
    sub = (row_template(state, row_table)
           if state.cache_impl == "paged"
           else engine_init(bundle, 1, state.max_len, ctx_len=ctx_len))
    sub = prefill(bundle, sub, prompt[None, :], key=key, ctx=ctx,
                  temperature=temperature)
    return state.adopt_row(row, sub)


# ------------------------------------------------------- install accounting -
def _row_nbytes(a, ax) -> int:
    if not hasattr(a, "ndim") or a.ndim == 0 or ax >= a.ndim:
        return 0
    return a.nbytes // a.shape[ax]


def refill_copy_bytes(state: EngineState, n_tokens: int) -> int:
    """Bytes one slot install writes into the wave state (accounting model
    for ``BENCH_serving.json``).

    Dense: ``adopt_row`` rewrites a full row of every cache — max_len
    positions of target KV and drafter features regardless of the prompt
    length. Paged: only the ``n_tokens`` prompt positions land in the
    pools (tail-page writes) plus one page-table row and the small dense
    leaves (window-capped local KV, recurrent states, scalars) — page-size
    order, which is the acceptance criterion for copy-free refill.
    """
    def block_bytes(d, axis_for) -> int:
        total = 0
        paged = kvc.is_paged(d)
        for name, v in d.items():
            if paged and name in ("k", "v"):
                lead = int(np.prod(v.shape[:-4], dtype=np.int64))
                h, dh = v.shape[-2], v.shape[-1]
                total += int(n_tokens) * lead * h * dh * v.dtype.itemsize
            elif name == "pt":
                total += _row_nbytes(v, v.ndim - 2)
            else:
                ax = axis_for(name)
                total += sum(_row_nbytes(a, ax)
                             for a in jax.tree.leaves(v))
        return total

    total = 0
    for name, v in state.target.items():
        if isinstance(v, dict):
            total += block_bytes(v, lambda _n, a=lm.state_batch_axis(name): a)
        else:
            total += _row_nbytes(v, 0)
    total += block_bytes(state.d1_feat, _feat_axis)
    total += block_bytes(state.d2_feat, _feat_axis)
    total += _row_nbytes(state.anchor, 0) + _row_nbytes(state.active, 0)
    return total
