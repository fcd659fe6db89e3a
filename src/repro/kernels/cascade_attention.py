"""Cascade tree-verification attention — the paper's verify op, TPU-native.

One D2SD verification joins K+1 shared-prefix candidates (a comb prefix
tree of T_tree tokens) against a LONG committed KV cache. FlashInfer's GPU
cascade kernel is re-thought for TPU (DESIGN §3):

  phase 1 (this Pallas kernel): the query block (all tree tokens, <= ~128)
    stays resident in VMEM while the kernel sweeps the KV cache HBM->VMEM in
    BlockSpec tiles, split-K over a grid axis so many cache slices progress
    in parallel; each split emits un-normalized flash partials (acc, m, l).
  phase 2 (jnp): partials merge by log-sum-exp with the tree-masked local
    part (tree tokens attending each other via the comb ancestor mask) —
    tiny (T_tree^2), not worth a kernel.

This is also the decode kernel: a chain of 1 token is a degenerate tree.

Masking supports per-example cache lengths (ragged batch), sliding windows
(gemma2/recurrentgemma local layers; rolling-buffer position recovery), and
per-query absolute positions (tree nodes sit at cache_len + depth).

Paged variant (:func:`cascade_phase1_paged`): the KV cache is a page pool
``[P, Hkv, page, D]`` plus per-row page tables ``[B, max_pages]`` (the
serving engine's ``cache_impl="paged"`` layout, models/kvcache.py). The
page table rides in as a scalar-prefetch operand
(``pltpu.PrefetchScalarGridSpec``) so the K/V BlockSpec ``index_map``
resolves each grid step's LOGICAL page to its PHYSICAL pool page before
the DMA is issued — the kernel streams exactly the row's pages out of HBM
with no gather materialization, keeping the split-K partials and
ragged/sliding-window masking of the dense kernel (logical key positions
are unchanged; only the addressing is indirected). Its grid runs over KV
heads, not query heads: each page is loaded once for the query heads that
share it, and steps past a row's live pages skip the update.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _q_abs_col(q_abs, b, tq):
    """Per-query absolute positions as an int32 ``[B, Tq, 1]`` VMEM column.

    Mosaic loads only scalars from SMEM, so the ``[Tq]`` vector the mask
    compares against each key row rides in as a VMEM block instead."""
    return jnp.broadcast_to(
        jnp.asarray(q_abs, jnp.int32).reshape(-1, tq), (b, tq))[..., None]


def _phase1_kernel(cache_len_ref,                             # SMEM
                   q_abs_ref, q_ref, k_ref, v_ref,            # VMEM blocks
                   acc_ref, m_ref, l_ref,                     # outputs
                   racc, rm, rl,                              # scratch
                   *, bk, nk_inner, tq, window, softcap, scale, rolling,
                   cap):
    """``cap`` is the TRUE buffer capacity (``s_len``), NOT the padded
    grid extent: rolling position recovery ``kpos = last - rem(last -
    slot, cap)`` inverts the writer's ``slot = pos % cap``, so any other
    modulus recovers wrong absolute positions. Slots the split padding
    added (``slot >= cap``) hold no data and are masked dead explicitly —
    without that mask a padded slot at ``last + cap`` would alias the
    rolling recovery back onto a live position."""
    b = pl.program_id(0)
    s = pl.program_id(2)       # split index
    jj = pl.program_id(3)      # inner kv step within the split

    @pl.when(jj == 0)
    def _init():
        racc[...] = jnp.zeros_like(racc)
        rm[...] = jnp.full_like(rm, NEG_INF)
        rl[...] = jnp.zeros_like(rl)

    q = q_ref[0, 0].astype(jnp.float32) * scale              # [tq, D]
    k = k_ref[0, 0].astype(jnp.float32)                      # [bk, D]
    v = v_ref[0, 0].astype(jnp.float32)

    sc = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # [tq, bk]
    if softcap is not None:
        sc = softcap * jnp.tanh(sc / softcap)

    clen = cache_len_ref[b]
    base = (s * nk_inner + jj) * bk
    slot = base + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)  # [1, bk]
    live = slot < cap          # padded slots carry no cache data
    qp = q_abs_ref[0]                                        # [tq, 1]
    if rolling:
        # slot j holds the largest t < clen with t % cap == j; rem (not
        # mod) is safe: last - slot < 0 only pre-wrap (clen <= cap, so
        # last < cap <= any candidate), where the recovered kpos > last
        # dies on kpos < clen exactly like the oracle's kpos < 0.
        last = clen - 1
        kpos = last - jax.lax.rem(last - slot, cap)
        ok = live & (kpos >= 0) & (kpos < clen) & (kpos <= qp)
    else:
        kpos = slot
        ok = live & (kpos < clen) & (kpos <= qp)
    if window is not None:
        ok &= kpos > (qp - window)
    sc = jnp.where(ok, sc, NEG_INF)

    m_prev = rm[...]                                         # [tq, 1]
    m_new = jnp.maximum(m_prev, sc.max(axis=1, keepdims=True))
    p = jnp.exp(sc - m_new)
    alpha = jnp.exp(m_prev - m_new)
    rl[...] = rl[...] * alpha + p.sum(axis=1, keepdims=True)
    racc[...] = racc[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())))
    rm[...] = m_new

    @pl.when(jj == nk_inner - 1)
    def _final():
        acc_ref[0, 0, 0] = racc[...]
        m_ref[0, 0, 0] = rm[...]
        l_ref[0, 0, 0] = rl[...]


def cascade_phase1(q, cache_k, cache_v, *, cache_len, q_abs, window=None,
                   attn_softcap=None, scale=None, rolling=False,
                   n_splits=8, bk=512, interpret=False):
    """q [B,Hq,Tq,D]; cache [B,Hkv,S,D] -> flash partials per split:
    acc [B,Hq,ns,Tq,D], m/l [B,Hq,ns,Tq].

    Split-count invariant: the effective split count is
    ``min(n_splits, ceil(S / bk))`` — the cache is PADDED up to a
    ``n_splits * bk`` multiple instead of degrading the split count when
    ``S`` is not block-aligned (prime-ish capacities used to collapse
    split-K parallelism to 1). Padded slots are dead by construction:
    the kernel masks ``slot >= S`` before any position recovery, so the
    padding is invisible to both rolling and non-rolling semantics and
    ``cap`` (the rolling modulus) stays the TRUE capacity ``S``.
    """
    b, hq, tq, d = q.shape
    hkv, s_len = cache_k.shape[1], cache_k.shape[2]
    g = hq // hkv
    scale = scale if scale is not None else d ** -0.5
    bk = min(bk, s_len)
    n_splits = max(1, min(n_splits, -(-s_len // bk)))
    pk = (-s_len) % (n_splits * bk)
    if pk:
        cache_k = jnp.pad(cache_k, ((0, 0), (0, 0), (0, pk), (0, 0)))
        cache_v = jnp.pad(cache_v, ((0, 0), (0, 0), (0, pk), (0, 0)))
    s_pad = s_len + pk
    nk_inner = s_pad // (n_splits * bk)

    clen = jnp.broadcast_to(
        jnp.asarray(cache_len, jnp.int32).reshape(-1), (b,))
    qa = _q_abs_col(q_abs, b, tq)

    kernel = functools.partial(
        _phase1_kernel, bk=bk, nk_inner=nk_inner, tq=tq, window=window,
        softcap=attn_softcap, scale=scale, rolling=rolling, cap=s_len)

    acc, m, l = pl.pallas_call(
        kernel,
        name="cascade_read_dense",
        grid=(b, hq, n_splits, nk_inner),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, tq, 1), lambda b_, h, s, j: (b_, 0, 0)),
            pl.BlockSpec((1, 1, tq, d), lambda b_, h, s, j: (b_, h, 0, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda b_, h, s, j, g=g, nki=nk_inner:
                         (b_, h // g, s * nki + j, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda b_, h, s, j, g=g, nki=nk_inner:
                         (b_, h // g, s * nki + j, 0)),
        ],
        out_specs=[pl.BlockSpec((1, 1, 1, tq, n),
                                lambda b_, h, s, j: (b_, h, s, 0, 0))
                   for n in (d, 1, 1)],
        scratch_shapes=_partial_scratch(tq, d),
        out_shape=_partial_shapes(b, hq, n_splits, tq, d),
        interpret=interpret,
    )(clen, qa, q, cache_k, cache_v)
    return acc, m[..., 0], l[..., 0]


def _partial_shapes(b, hq, n_splits, tq, d):
    """Phase-1 outputs acc ``[B,Hq,ns,Tq,D]`` and m/l ``[B,Hq,ns,Tq,1]``.

    m/l keep a trailing unit lane dim: a ``(.., 1, Tq)`` block would put a
    unit dim second-minor, which Mosaic's (8, 128) tiling refuses."""
    return [jax.ShapeDtypeStruct((b, hq, n_splits, tq, n), jnp.float32)
            for n in (d, 1, 1)]


def _partial_scratch(tq, d):
    """Running flash state: acc ``[Tq, D]``, m and l ``[Tq, 1]`` (2-D, as
    Mosaic requires of VMEM scratch)."""
    return [pltpu.VMEM((tq, n), jnp.float32) for n in (d, 1, 1)]


def _merge_with_tree_block(q, blk_k, blk_v, acc, m, l, *, tree_mask,
                           attn_softcap, scale):
    """Shared phase 2: merge phase-1 split partials by log-sum-exp with the
    tree-masked local attention (tiny, T_tree^2 — fp32 jnp)."""
    g = q.shape[1] // blk_k.shape[1]
    # merge splits
    m_g = m.max(axis=2)                                        # [B,Hq,Tq]
    corr = jnp.exp(m - m_g[:, :, None])
    l_g = (l * corr).sum(axis=2)
    acc_g = (acc * corr[..., None]).sum(axis=2)               # [B,Hq,Tq,D]

    # phase 2: tree-local attention
    qf = q.astype(jnp.float32) * scale
    kq = jnp.repeat(blk_k.astype(jnp.float32), g, axis=1)
    vq = jnp.repeat(blk_v.astype(jnp.float32), g, axis=1)
    sc = jnp.einsum("bhqd,bhtd->bhqt", qf, kq)
    if attn_softcap is not None:
        sc = attn_softcap * jnp.tanh(sc / attn_softcap)
    tm = tree_mask
    if tm.ndim == 2:
        tm = tm[None]
    sc = jnp.where(tm[:, None], sc, NEG_INF)
    m_b = sc.max(axis=-1)
    p_b = jnp.exp(sc - m_b[..., None])
    l_b = p_b.sum(axis=-1)
    acc_b = jnp.einsum("bhqt,bhtd->bhqd", p_b, vq)

    m_tot = jnp.maximum(m_g, m_b)
    a1 = jnp.exp(m_g - m_tot)
    a2 = jnp.exp(m_b - m_tot)
    out = (acc_g * a1[..., None] + acc_b * a2[..., None]) / jnp.maximum(
        l_g * a1 + l_b * a2, 1e-30)[..., None]
    return out.astype(q.dtype)


def cascade_attention(q, cache_k, cache_v, blk_k, blk_v, *, cache_len,
                      q_abs, tree_mask, window=None, attn_softcap=None,
                      scale=None, rolling=False, n_splits=8, bk=512,
                      interpret=False):
    """Full cascade verify: phase-1 kernel over the cache + jnp tree-local
    phase-2 + LSE merge.

    q [B,Hq,Tq,D]; cache [B,Hkv,S,D]; blk [B,Hkv,Tb,D];
    tree_mask [B,Tq,Tb] (ancestor mask); returns [B,Hq,Tq,D].
    """
    d = q.shape[-1]
    scale_v = scale if scale is not None else d ** -0.5
    acc, m, l = cascade_phase1(
        q, cache_k, cache_v, cache_len=cache_len, q_abs=q_abs, window=window,
        attn_softcap=attn_softcap, scale=scale_v, rolling=rolling,
        n_splits=n_splits, bk=bk, interpret=interpret)
    return _merge_with_tree_block(q, blk_k, blk_v, acc, m, l,
                                  tree_mask=tree_mask,
                                  attn_softcap=attn_softcap, scale=scale_v)


# ------------------------------------------------------------- paged -------
def _phase1_paged_kernel(pt_ref, cache_len_ref, off_ref,   # scalar prefetch
                         q_abs_ref, q_ref, k_ref, v_ref,      # VMEM blocks
                         acc_ref, m_ref, l_ref,               # outputs
                         racc, rm, rl,                        # scratch
                         *, g, page, pos_stride, nk_inner, tq, window,
                         softcap, scale):
    """``_phase1_kernel``'s flash accumulation with one KV page per inner
    step, for all ``g`` query heads that share the step's KV head: their
    rows come stacked as one ``[g*tq, D]`` block, so the page is loaded
    once and each product is one dot. The physical page was already
    resolved by the BlockSpec index_map (scalar-prefetched page table), so
    the body only deals in LOGICAL key positions: page
    ``step = s*nk_inner + jj`` holds positions [base, base+page).

    A step past the row's live pages (``ceil(cache_len / pos_stride)``,
    the count ``kv_map`` clamps to) does no work: its page would only be
    masked dead, and a split that is dead throughout writes the init state
    (``m = -1e30``, ``l = 0``, ``acc = 0``), which the LSE merge weights to
    zero. Garbage a live page surfaces past ``cache_len`` dies on the
    ``kpos < cache_len`` mask, exactly like the dense kernel's tail
    padding.

    ``pos_stride``/``off_ref`` decouple logical positions from the local
    page extent: logical page ``i`` of this buffer covers absolute
    positions ``[i*pos_stride + off, i*pos_stride + off + page)``. The
    single-device engine uses the identity (stride == page, off == 0);
    a kv_seq shard whose pages hold slots ``[ax*page_loc, (ax+1)*page_loc)``
    of every GLOBAL page passes stride=global page size, off=ax*page_loc.
    The live count ignores the offset, so a shard may run a step whose
    slots all lie past ``cache_len``, and the mask kills it.
    """
    b = pl.program_id(0)
    s = pl.program_id(2)       # split index
    jj = pl.program_id(3)      # inner page step within the split
    step = s * nk_inner + jj

    @pl.when(jj == 0)
    def _init():
        racc[...] = jnp.zeros_like(racc)
        rm[...] = jnp.full_like(rm, NEG_INF)
        rl[...] = jnp.zeros_like(rl)

    clen = cache_len_ref[b]

    @pl.when(step < (clen + pos_stride - 1) // pos_stride)
    def _update():
        q = q_ref[0, 0].astype(jnp.float32) * scale          # [g*tq, D]
        k = k_ref[0, 0].astype(jnp.float32)                  # [page, D]
        v = v_ref[0, 0].astype(jnp.float32)

        sc = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))
        if softcap is not None:
            sc = softcap * jnp.tanh(sc / softcap)

        base = step * pos_stride + off_ref[0]
        kpos = base + jax.lax.broadcasted_iota(jnp.int32, (1, page), 1)
        qp = q_abs_ref[0]                                    # [g*tq, 1]
        ok = (kpos < clen) & (kpos <= qp)
        if window is not None:
            ok &= kpos > (qp - window)
        sc = jnp.where(ok, sc, NEG_INF)

        m_prev = rm[...]                                     # [g*tq, 1]
        m_new = jnp.maximum(m_prev, sc.max(axis=1, keepdims=True))
        p = jnp.exp(sc - m_new)
        alpha = jnp.exp(m_prev - m_new)
        rl[...] = rl[...] * alpha + p.sum(axis=1, keepdims=True)
        racc[...] = racc[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())))
        rm[...] = m_new

    @pl.when(jj == nk_inner - 1)
    def _final():
        for h in range(g):
            rows = slice(h * tq, (h + 1) * tq)
            acc_ref[0, h, 0] = racc[rows]
            m_ref[0, h, 0] = rm[rows]
            l_ref[0, h, 0] = rl[rows]


def paged_table_width(max_pages: int, n_splits: int = 8) -> int:
    """Table width :func:`cascade_phase1_paged` walks: ``max_pages`` padded
    up to a multiple of the split count ``min(n_splits, max_pages)``."""
    ns = max(1, min(n_splits, max_pages))
    return max_pages + (-max_pages) % ns


def cascade_phase1_paged(q, pool_k, pool_v, page_table, *, cache_len, q_abs,
                         window=None, attn_softcap=None, scale=None,
                         n_splits=8, interpret=False, pos_stride=None,
                         pos_offset=None):
    """Split-K flash partials over a PAGED cache.

    q [B,Hq,Tq,D]; pools [P,Hkv,page,D]; page_table [B,max_pages] physical
    page ids (out-of-range entries = unallocated; they are clamped for the
    DMA and masked by ``cache_len``). The grid is (B, Hkv, splits, pages
    per split): one step reads one page of one KV head for the ``Hq/Hkv``
    query heads that share it, stacked into one ``[Hq/Hkv * Tq, D]``
    block. The table is a scalar-prefetch operand so
    the index_map can address pages data-dependently — the TPU analogue
    of paged attention's block table. Returns flash partials
    acc [B,Hq,ns,Tq,D], m/l [B,Hq,ns,Tq]; head ``h`` reads KV head
    ``h // (Hq/Hkv)``.

    Work scales with LIVE length, not capacity: ``cache_len`` is also a
    scalar-prefetch operand, so the index_map clamps the logical page step
    to the row's last live page, and Pallas elides the DMA when
    consecutive grid steps resolve to the same block index; the body skips
    those steps outright. A dead table entry still costs one grid step of
    pipeline overhead; only a loop over live pages inside the body would
    remove it.

    ``pos_stride`` (static; default = pool page extent) and ``pos_offset``
    (traced scalar; default 0) place logical page ``i`` at absolute
    positions ``i*pos_stride + pos_offset + [0, page)`` — how a kv_seq
    shard attends its non-contiguous slice of every global page
    (``distributed/spdecode.py``).
    """
    b, hq, tq, d = q.shape
    hkv, page = pool_k.shape[1], pool_k.shape[2]
    n_phys = pool_k.shape[0]
    g = hq // hkv
    mp = page_table.shape[-1]
    scale = scale if scale is not None else d ** -0.5
    # keep the requested split count by padding the TABLE (not the pool)
    # with sentinel pages — mirrors the dense kernel's cache padding, so a
    # prime max_pages does not collapse the split-K parallelism. Padded
    # pages lie past every live page, so the body skips them.
    n_splits = max(1, min(n_splits, mp))
    width = paged_table_width(mp, n_splits)
    page_table = jnp.asarray(page_table, jnp.int32).reshape(-1, mp)
    if width > mp:
        page_table = jnp.pad(page_table, ((0, 0), (0, width - mp)),
                             constant_values=n_phys)
        mp = width
    nk_inner = mp // n_splits

    if pos_stride is None:
        pos_stride = page
    pt = jnp.minimum(page_table, n_phys - 1).reshape(-1)      # [B*MP]
    clen = jnp.broadcast_to(
        jnp.asarray(cache_len, jnp.int32).reshape(-1), (b,))
    # query head h*g + i is rows [i*tq, (i+1)*tq) of KV head h's stacked
    # block, and every head's rows carry the same per-query positions
    qs = q.reshape(b, hkv, g * tq, d)
    qa = jnp.tile(_q_abs_col(q_abs, b, tq), (1, g, 1))       # [B, g*tq, 1]
    off = jnp.asarray(0 if pos_offset is None else pos_offset,
                      jnp.int32).reshape(-1)[:1]

    kernel = functools.partial(
        _phase1_paged_kernel, g=g, page=page, pos_stride=pos_stride,
        nk_inner=nk_inner, tq=tq, window=window, softcap=attn_softcap,
        scale=scale)

    def kv_map(b_, h, s, j, pt_ref, clen_ref, off_ref, nki=nk_inner, mp=mp,
               stride=pos_stride):
        # Clamp the logical step to the last LIVE page: Pallas elides the
        # DMA when the resolved block index repeats across grid steps, so
        # the dead tail of the table moves no extra bytes (and the body
        # skips it).
        step = s * nki + j
        live = (clen_ref[b_] + stride - 1) // stride
        step = jnp.minimum(step, jnp.maximum(live - 1, 0))
        return (pt_ref[b_ * mp + step], h, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, hkv, n_splits, nk_inner),
        in_specs=[
            pl.BlockSpec((1, g * tq, 1),
                         lambda b_, h, s, j, pt_, cl_, off_: (b_, 0, 0)),
            pl.BlockSpec((1, 1, g * tq, d),
                         lambda b_, h, s, j, pt_, cl_, off_: (b_, h, 0, 0)),
            pl.BlockSpec((1, 1, page, d), kv_map),
            pl.BlockSpec((1, 1, page, d), kv_map),
        ],
        out_specs=[pl.BlockSpec((1, g, 1, tq, n),
                                lambda b_, h, s, j, pt_, cl_, off_:
                                (b_, h, s, 0, 0))
                   for n in (d, 1, 1)],
        scratch_shapes=_partial_scratch(g * tq, d),
    )
    acc, m, l = pl.pallas_call(
        kernel,
        name="cascade_read_paged",
        grid_spec=grid_spec,
        out_shape=_partial_shapes(b, hq, n_splits, tq, d),
        interpret=interpret,
    )(pt, clen, off, qa, qs, pool_k, pool_v)
    return acc, m[..., 0], l[..., 0]


def cascade_attention_paged(q, pool_k, pool_v, page_table, blk_k, blk_v, *,
                            cache_len, q_abs, tree_mask, window=None,
                            attn_softcap=None, scale=None, n_splits=8,
                            interpret=False, pos_stride=None,
                            pos_offset=None):
    """Paged cascade verify: page-table phase-1 + shared phase-2 merge.

    Same contract as :func:`cascade_attention` with the long cache given
    as (pool [P,Hkv,page,D], page_table [B,max_pages]) instead of a dense
    [B,Hkv,S,D] buffer; logical key position ``j`` of row ``b`` lives at
    ``pool[page_table[b, j // page], :, j % page]``.
    """
    d = q.shape[-1]
    scale_v = scale if scale is not None else d ** -0.5
    acc, m, l = cascade_phase1_paged(
        q, pool_k, pool_v, page_table, cache_len=cache_len, q_abs=q_abs,
        window=window, attn_softcap=attn_softcap, scale=scale_v,
        n_splits=n_splits, interpret=interpret, pos_stride=pos_stride,
        pos_offset=pos_offset)
    return _merge_with_tree_block(q, blk_k, blk_v, acc, m, l,
                                  tree_mask=tree_mask,
                                  attn_softcap=attn_softcap, scale=scale_v)
