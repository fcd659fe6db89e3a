"""KV-sequence-parallel decode attention (flash-decoding across chips).

At decode time the KV cache dominates memory and bandwidth; GQA archs with
2-8 KV heads cannot fill a 16-way tensor axis, so we shard the cache along
the SEQUENCE axis of the ``model`` mesh axis instead. Each shard computes
flash partials (acc, m, l) over its cache slice; partials merge across the
axis with a log-sum-exp psum (tiny: O(q_tokens * head_dim) per chip vs the
KV bytes that stay put). The in-flight tree/block KV is replicated, its
contribution computed identically on every shard and merged locally.

This is the TPU analogue of the paper's cascade attention phase-1/phase-2
split (shared long prefix once + small tree-local part), extended across
chips — see DESIGN §3.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.attention import (NEG_INF, attend, attend_chunked,
                                    merge_attn_stats, softcap)
from repro.distributed import sharding as sh


def kv_seq_axis() -> Optional[str]:
    """The mesh axis the KV cache sequence dim is sharded over, if any."""
    mesh = sh.active_mesh()
    if mesh is None:
        return None
    ax = sh._CTX.rules.get("kv_seq")
    if isinstance(ax, (tuple, list)):
        ax = ax[0] if ax else None
    if ax is None or ax not in mesh.axis_names:
        return None
    return ax


def kv_seq_shards() -> int:
    """Size of the kv_seq mesh axis (1 without a mesh / unsharded)."""
    mesh = sh.active_mesh()
    axis = kv_seq_axis()
    if mesh is None or axis is None:
        return 1
    return dict(zip(mesh.axis_names, mesh.devices.shape))[axis]


# Trace-time log of per-shard LSE-merge collective payload bytes: one entry
# per ``sharded_paged_cache_attend`` call traced (shape-only, so it is
# stable across executions of the same trace). The serving engine snapshots
# this around its first cycle dispatch to attribute decode-collective bytes
# per cycle; see ``ServingEngine.dispatch_cycle``.
PAYLOAD_TRACE: list = []


def sharded_cache_attend(q, cache_k, cache_v, blk_k, blk_v, *, cache_len,
                         q_abs, window, attn_softcap, blk_mask, rolling,
                         kv_chunk: int = 1024, merge_dtype=jnp.bfloat16):
    """Single-softmax attention over [sharded cache ++ replicated block].

    q: [B,Tq,Hq,Dh] (replicated over model axis)
    cache_k/v: [B,S,Hkv,Dh] logically; S sharded over the kv_seq axis
    blk_k/v: [B,Tblk,Hkv,Dh] replicated; blk_mask [B,Tq,Tblk] or [Tq,Tblk]
    cache_len: [B] valid cache length; q_abs: [B,Tq] absolute positions.

    merge_dtype: dtype of the cross-chip LSE-merge payload. bf16 halves the
    dominant decode collective (partials psum) at bf16-model accuracy
    (§Perf iteration 2); pass float32 for exact merging.
    """
    mesh = sh.active_mesh()
    axis = kv_seq_axis()
    assert mesh is not None and axis is not None
    b, tq, hq, dh = q.shape
    hkv = cache_k.shape[2]
    if blk_mask is not None and blk_mask.ndim == 2:
        blk_mask = jnp.broadcast_to(blk_mask[None], (b, tq, blk_mask.shape[-1]))
    clen = jnp.asarray(cache_len)
    if clen.ndim == 0:
        clen = jnp.full((b,), clen)
    qa = jnp.asarray(q_abs)
    if qa.ndim == 1:
        qa = jnp.broadcast_to(qa[None], (b, tq))
    cap = cache_k.shape[1]

    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    batch_axes = []
    prod = 1
    for a in ("pod", "data"):
        if a in mesh.axis_names and b % (prod * sizes[a]) == 0:
            batch_axes.append(a)
            prod *= sizes[a]
    bspec = tuple(batch_axes) if len(batch_axes) > 1 else (
        batch_axes[0] if batch_axes else None)

    vary_cache = tuple(batch_axes) + (axis,)
    vary_blk = tuple(batch_axes)

    def shard_fn(qs, ck, cv, bk, bv, cl, qab, bm):
        ax_idx = jax.lax.axis_index(axis)
        s_loc = ck.shape[1]
        offset = ax_idx * s_loc
        # ---- cache slice partials ----
        # mask by absolute key position (rolling caches store position
        # p at slot p % cap, recovered against the local slot offset)
        qv = jax.lax.pcast(qs, (axis,), to="varying")
        acc, m, l = _cache_stats(qv, ck, cv, offset=offset, cap=cap,
                                 clen=cl, qab=qab, window=window,
                                 attn_softcap=attn_softcap, rolling=rolling,
                                 kv_chunk=kv_chunk, vary_axes=vary_cache)
        acc_g, m_g, l_g = _axis_lse_merge(acc, m, l, axis, merge_dtype)
        # ---- replicated block part (computed identically per shard) ----
        acc_b, m_b, l_b = attend_chunked(
            qs, bk, bv, causal=False, q_offset=0, extra_mask=bm,
            attn_softcap=attn_softcap, kv_chunk=max(bk.shape[1], 8),
            return_stats=True, vary_axes=vary_blk)
        out = merge_attn_stats([(acc_g, m_g, l_g), (acc_b, m_b, l_b)],
                               qs.shape, qs.dtype)
        return out

    # check_vma=True: psum/pmax establish replication over the kv_seq axis,
    # so shard_map emits NO output all-gather (the check_vma=False baseline
    # re-gathered the merged output redundantly — §Perf iteration 1).
    return jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P(bspec), P(bspec, axis), P(bspec, axis), P(bspec),
                  P(bspec), P(bspec), P(bspec), P(bspec)),
        out_specs=P(bspec),
        check_vma=True,
    )(q, cache_k, cache_v, blk_k, blk_v, clen, qa, blk_mask)


def _axis_lse_merge(acc, m, l, axis, merge_dtype):
    """LSE-merge flash partials across a mesh axis (pmax + 2 psums).

    Partials are normalized by the global max first so the psum payload can
    travel in bf16 without range loss (values in [0, l_local]); pass
    ``merge_dtype=float32`` for exact merging (the serving engine's
    default — token identity with the single-device engine requires argmax
    stability, not just rtol-closeness).
    """
    m_g = jax.lax.pmax(m, axis)
    corr = jnp.exp(m - m_g)
    l_g = jax.lax.psum((l * corr).astype(merge_dtype),
                       axis).astype(jnp.float32)
    acc_g = jax.lax.psum((acc * corr[..., None]).astype(merge_dtype),
                         axis).astype(jnp.float32)
    return acc_g, m_g, l_g


def sharded_paged_cache_attend(q, pool_k, pool_v, table, blk_k, blk_v, *,
                               cache_len, q_abs, attn_softcap, blk_mask,
                               page_size: int, kv_chunk: int = 1024,
                               merge_dtype=jnp.float32,
                               read_impl: str = "gather",
                               interpret=None):
    """Paged cascade-verify attention under shard_map: single-softmax over
    [paged cache ++ replicated block] with the pool's page *payloads*
    sharded along the kv_seq axis.

    Layout ("page identity is global, page bytes are per-shard"): the pool
    is sharded on its within-page position axis — shard i of P holds slots
    ``[i*page_loc, (i+1)*page_loc)`` of EVERY page, ``page_loc =
    page_size // P``. Page tables stay host-global integer ids, so one
    replicated gather per shard resolves its local view and no cross-shard
    page traffic exists; the absolute position of local flat slot t is
    ``(t // page_loc)*page_size + i*page_loc + (t % page_loc)``. Shards'
    flash partials merge with the same LSE psum as the dense path; the
    in-flight tree/block KV is replicated and merged locally.

    q: [B,Tq,Hq,Dh] replicated; pool_k/v: [P_pages, page, Hkv, Dh]
    logically (within-page axis sharded over kv_seq); table: [B, MP] int32
    page ids (PAGE_SENTINEL rows masked out by ``cache_len``);
    blk_k/v: [B,Tblk,Hkv,Dh]; cache_len [B]; q_abs [B,Tq] or [Tq].

    Non-rolling global-attention reads only (the prefix cache's gating) —
    serves both the verifier's paged KV layers and the drafter's paged
    feature caches (``core.drafter.drafter_forward``, which are always
    non-rolling and windowless); ``merge_dtype`` defaults to float32 —
    see :func:`_axis_lse_merge`.

    ``read_impl`` selects how each shard reads its local pool slice:
    "gather" (default) materializes the local logical view via
    ``pool_view``; "pallas" runs the paged cascade phase-1 kernel directly
    on the local pool + global page table, placing logical page ``i`` at
    absolute positions ``i*page_size + ax_idx*page_loc + [0, page_loc)``
    via the kernel's pos_stride/pos_offset parameters. Both feed the SAME
    fp32 LSE psum merge, so per-request tokens are identical. The pallas
    branch runs the shard_map with ``check_vma=False`` (jax has no
    replication rule for pallas_call); outputs are psum-merged, hence
    replicated, either way.
    """
    from repro.models import kvcache as kvc
    if read_impl == "pallas":
        from repro.kernels import cascade_attention as casc
        from repro.kernels import ops as kops
        interpret = (kops.default_interpret() if interpret is None
                     else interpret)

    mesh = sh.active_mesh()
    axis = kv_seq_axis()
    assert mesh is not None and axis is not None
    nsh = kv_seq_shards()
    assert page_size % nsh == 0, (page_size, nsh)
    page_loc = page_size // nsh
    b, tq, hq, dh = q.shape
    hkv = pool_k.shape[-2]
    mp = table.shape[1]
    if blk_mask is not None and blk_mask.ndim == 2:
        blk_mask = jnp.broadcast_to(blk_mask[None],
                                    (b, tq, blk_mask.shape[-1]))
    clen = jnp.asarray(cache_len)
    if clen.ndim == 0:
        clen = jnp.full((b,), clen)
    qa = jnp.asarray(q_abs)
    if qa.ndim == 1:
        qa = jnp.broadcast_to(qa[None], (b, tq))

    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    batch_axes = []
    prod = 1
    for a in ("pod", "data"):
        if a in mesh.axis_names and b % (prod * sizes[a]) == 0:
            batch_axes.append(a)
            prod *= sizes[a]
    bspec = tuple(batch_axes) if len(batch_axes) > 1 else (
        batch_axes[0] if batch_axes else None)
    vary_cache = tuple(batch_axes) + (axis,)
    vary_blk = tuple(batch_axes)

    # per-shard collective payload: acc+l in merge_dtype, m via fp32 pmax
    md = jnp.dtype(merge_dtype).itemsize
    PAYLOAD_TRACE.append(int(b * hkv * (hq // hkv) * tq * ((dh + 1) * md + 4)))

    def shard_fn(qs, pk, pv, tbl, bk, bv, cl, qab, bm):
        ax_idx = jax.lax.axis_index(axis)
        if read_impl == "pallas":
            # kernel on the local pool slice: one grid step per local page
            # run; stride/offset place the run at its absolute positions
            acc, m, l = casc.cascade_phase1_paged(
                jnp.swapaxes(jax.lax.pcast(qs, (axis,), to="varying"), 1, 2),
                jnp.swapaxes(pk, 1, 2), jnp.swapaxes(pv, 1, 2),
                jax.lax.pcast(tbl, (axis,), to="varying"),
                cache_len=jax.lax.pcast(cl, (axis,), to="varying"),
                q_abs=jax.lax.pcast(qab, (axis,), to="varying"),
                window=None, attn_softcap=attn_softcap,
                pos_stride=page_size, pos_offset=ax_idx * page_loc,
                interpret=interpret)
            # local split merge, then reshape [B,Hq,...] -> the
            # attend_chunked stats layout [B,Hkv,G,...] (head h = (h//g, h%g))
            m_l = m.max(axis=2)
            cr = jnp.exp(m - m_l[:, :, None])
            l_l = (l * cr).sum(axis=2)
            acc_l = (acc * cr[..., None]).sum(axis=2)
            bl, g = qs.shape[0], hq // hkv
            acc = acc_l.reshape(bl, hkv, g, tq, dh)
            m = m_l.reshape(bl, hkv, g, tq)
            l = l_l.reshape(bl, hkv, g, tq)
        else:
            # local logical view: [B, MP*page_loc, Hkv, Dh] — every page's
            # local slot run, in page-table order
            vk = kvc.pool_view(pk, tbl)
            vv = kvc.pool_view(pv, tbl)
            t = jnp.arange(mp * page_loc)
            pos = ((t // page_loc) * page_size + ax_idx * page_loc
                   + (t % page_loc))[None, None, :]
            acc, m, l = _cache_stats(
                jax.lax.pcast(qs, (axis,), to="varying"), vk, vv, offset=0,
                cap=mp * page_size, clen=cl, qab=qab, window=None,
                attn_softcap=attn_softcap, rolling=False, kv_chunk=kv_chunk,
                vary_axes=vary_cache, pos=pos)
        acc_g, m_g, l_g = _axis_lse_merge(acc, m, l, axis, merge_dtype)
        acc_b, m_b, l_b = attend_chunked(
            qs, bk, bv, causal=False, q_offset=0, extra_mask=bm,
            attn_softcap=attn_softcap, kv_chunk=max(bk.shape[1], 8),
            return_stats=True, vary_axes=vary_blk)
        return merge_attn_stats([(acc_g, m_g, l_g), (acc_b, m_b, l_b)],
                                qs.shape, qs.dtype)

    return jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P(bspec), P(None, axis), P(None, axis), P(bspec),
                  P(bspec), P(bspec), P(bspec), P(bspec), P(bspec)),
        out_specs=P(bspec),
        check_vma=(read_impl != "pallas"),
    )(q, pool_k, pool_v, table, blk_k, blk_v, clen, qa, blk_mask)


def _cache_stats(q, k, v, *, offset, cap, clen, qab, window, attn_softcap,
                 rolling, kv_chunk, vary_axes=(), pos=None):
    """Flash partials over a local cache slice with absolute-position masks.

    ``pos``: optional precomputed absolute key positions [1,1,S_loc] (the
    paged layout's positions are non-contiguous per shard); defaults to the
    contiguous ``offset + arange`` of a sequence-sliced dense cache.
    """
    b, tq = q.shape[:2]
    s_loc = k.shape[1]
    if pos is None:
        jc = offset + jnp.arange(s_loc)[None, None, :]      # global slot ids
    else:
        jc = pos
    qpos = qab[:, :, None]
    cl = clen[:, None, None]
    if rolling:
        last = cl - 1
        abs_kpos = last - jnp.mod(last - jc, cap)
        ok = (abs_kpos >= 0) & (abs_kpos < cl) & (abs_kpos <= qpos)
        if window is not None:
            ok &= abs_kpos > (qpos - window)
    else:
        ok = (jc < cl) & (jc <= qpos)
        if window is not None:
            ok &= jc > (qpos - window)
    return attend_chunked(q, k, v, causal=False, q_offset=0, extra_mask=ok,
                          attn_softcap=attn_softcap,
                          kv_chunk=min(kv_chunk, s_loc), return_stats=True,
                          vary_axes=vary_axes)
