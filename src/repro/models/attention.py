"""Attention: GQA projections + mask construction + memory-efficient impls.

Three implementations share one semantics (validated against each other):

* ``dense``   — materializes [Tq, Tkv] scores; tiny shapes / oracle.
* ``chunked`` — flash-style running-softmax over KV chunks in pure JAX
                (lax.scan); O(Tq * chunk) memory; used on compile paths so the
                dry-run HLO never materializes S^2 scores.
* ``pallas``  — TPU kernels in ``repro.kernels`` (flash fwd/bwd, cascade).

Mask semantics (composable):
  causal with query offset ``q_offset`` (prefill/decode with cache),
  sliding window, gemma2 attention-logit softcap, explicit extra mask
  (tree/bidirectional-block), and KV length masking for padded caches.

Cache READ path (``ModelConfig.attn_impl``, distinct from the ``impl``
call parameter above): "gather" (default) materializes the dense logical
view of a paged cache via ``kvcache.pool_view`` and attends over it with
one of the three impls; "pallas" routes decode/verify steps on paged
global layers straight to ``kernels.ops.cascade_attention_paged`` (pool
buffers + page table, no dense gather — see ``models/blocks.py``).
``attn_impl`` is a jit-static carried by the config (SpecBundle registers
configs as pytree aux_data), token-identical by tier-1 assertion, and
falls back to interpret mode off-TPU.

Coverage matrix under ``attn_impl="pallas"``:

* paged GLOBAL layers — ``cascade_attention_paged`` on pool + table;
* sliding-window ROLLING local layers — the DENSE cascade kernel over
  the rolling buffer with ``rolling=True`` and the TRUE capacity as
  position-recovery modulus (``models/blocks.py``);
* kv_seq-sharded paged reads (verify KV AND drafter feature caches) —
  the per-shard kernel inside ``shard_map``
  (``distributed/spdecode.sharded_paged_cache_attend``);
* still on gather: recurrent/rwkv blocks (no KV cache to kernelize),
  cross-attention, dense-cache engines under a kv_seq mesh, and
  GSPMD prefill.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models import param as pm
from repro.models.layers import dense, apply_rope, softcap
from repro.distributed.sharding import constrain

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


# ------------------------------------------------------------------ masks --
def make_attention_mask(tq: int, tkv: int, *, causal: bool, q_offset,
                        window: Optional[int] = None,
                        kv_len=None) -> jnp.ndarray:
    """Boolean mask (True = attend): [Tq,Tkv], or [B,Tq,Tkv] when
    ``q_offset``/``kv_len`` are per-example vectors.

    Query i has absolute position q_offset + i; key j has absolute position j.
    """
    q_off = jnp.asarray(q_offset)
    batched = q_off.ndim > 0 or (kv_len is not None
                                 and jnp.asarray(kv_len).ndim > 0)
    if batched:
        q_off = q_off.reshape(-1, 1, 1)
        qpos = jnp.arange(tq)[None, :, None] + q_off      # [B,Tq,1]
        kpos = jnp.arange(tkv)[None, None, :]
    else:
        qpos = jnp.arange(tq)[:, None] + q_off            # [Tq,1]
        kpos = jnp.arange(tkv)[None, :]
    shape = jnp.broadcast_shapes(qpos.shape, kpos.shape)
    mask = (kpos <= qpos) if causal else jnp.ones(shape, dtype=bool)
    if window is not None:
        mask &= kpos > (qpos - window)
    if kv_len is not None:
        kl = jnp.asarray(kv_len)
        if batched:
            kl = kl.reshape(-1, 1, 1)
        mask &= kpos < kl
    return mask


# ------------------------------------------------------------ dense impl --
def attend_dense(q, k, v, mask=None, *, scale=None, attn_softcap=None,
                 sinks=None):
    """q:[B,Tq,Hq,Dh] k,v:[B,Tkv,Hkv,Dh] mask:[B?,Tq,Tkv] or [B,Hq,Tq,Tkv]."""
    b, tq, hq, dh = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    scale = scale if scale is not None else dh ** -0.5
    qf = q.astype(jnp.float32) * scale
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    qf = qf.reshape(b, tq, hkv, g, dh)
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", qf, kf)
    logits = softcap(logits, attn_softcap)
    if mask is not None:
        if mask.ndim == 2:
            mask = mask[None]
        m = mask[:, None, None]  # [B,1,1,Tq,Tkv]
        logits = jnp.where(m, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, vf)
    return out.reshape(b, tq, hq, dh).astype(q.dtype)


# ---------------------------------------------------------- chunked impl --
def attend_chunked(q, k, v, *, causal, q_offset, window=None, kv_len=None,
                   extra_mask=None, scale=None, attn_softcap=None,
                   kv_chunk: int = 1024, return_stats: bool = False,
                   key_offset=0, vary_axes=()):
    """Flash-style running softmax over KV chunks; never builds [Tq,Tkv].

    extra_mask: optional [Tq,Tkv] or [B,Tq,Tkv] bool, ANDed with causal etc.
    q_offset / kv_len: scalar or per-example [B].
    key_offset: absolute position of k[0] (cross-device KV sharding).
    return_stats: return (acc, m, l) un-normalized flash stats
        (acc [B,Hkv,G,Tq,Dh], m/l [B,Hkv,G,Tq]) for LSE merging.
    """
    b, tq, hq, dh = q.shape
    tkv, hkv = k.shape[1], k.shape[2]
    g = hq // hkv
    scale = scale if scale is not None else dh ** -0.5
    kv_chunk = min(kv_chunk, tkv)
    n_chunks = (tkv + kv_chunk - 1) // kv_chunk
    pad = n_chunks * kv_chunk - tkv
    if extra_mask is not None and extra_mask.ndim == 2:
        extra_mask = extra_mask[None]
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        if extra_mask is not None:
            extra_mask = jnp.pad(extra_mask, ((0, 0), (0, 0), (0, pad)))
    eff_kv_len = kv_len if kv_len is not None else tkv
    eff_kv_len = jnp.asarray(eff_kv_len)
    if eff_kv_len.ndim == 0:
        eff_kv_len = jnp.full((b,), eff_kv_len)

    qf = (q.astype(jnp.float32) * scale).reshape(b, tq, hkv, g, dh)
    kc = jnp.moveaxis(k.reshape(b, n_chunks, kv_chunk, hkv, dh), 1, 0)
    vc = jnp.moveaxis(v.reshape(b, n_chunks, kv_chunk, hkv, dh), 1, 0)
    if extra_mask is not None:
        em = jnp.moveaxis(
            extra_mask.reshape(extra_mask.shape[0], tq, n_chunks, kv_chunk),
            2, 0)                                        # [C, B?, Tq, ck]
    else:
        em = None

    q_off = jnp.asarray(q_offset)
    if q_off.ndim == 0:
        q_off = jnp.full((b,), q_off)
    qpos = jnp.arange(tq)[None, :, None] + q_off[:, None, None]  # [B,Tq,1]

    def body(carry, inp):
        m_i, l_i, acc = carry
        if em is None:
            kcj, vcj, cidx = inp
            emj = None
        else:
            kcj, vcj, cidx, emj = inp
        logits = jnp.einsum("bqhgd,bkhd->bhgqk", qf, kcj.astype(jnp.float32))
        logits = softcap(logits, attn_softcap)
        kpos = (key_offset + cidx * kv_chunk
                + jnp.arange(kv_chunk)[None, None, :])
        mask = jnp.ones((b, tq, kv_chunk), dtype=bool)
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= kpos > (qpos - window)
        mask &= kpos < eff_kv_len[:, None, None]
        if emj is not None:
            mask &= emj
        logits = jnp.where(mask[:, None, None], logits, NEG_INF)
        m_new = jnp.maximum(m_i, logits.max(axis=-1))
        p = jnp.exp(logits - m_new[..., None])
        alpha = jnp.exp(m_i - m_new)
        l_new = l_i * alpha + p.sum(axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bhgqk,bkhd->bhgqd", p, vcj.astype(jnp.float32))
        return (m_new, l_new, acc), None

    m0 = jnp.full((b, hkv, g, tq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, hkv, g, tq), jnp.float32)
    a0 = jnp.zeros((b, hkv, g, tq, dh), jnp.float32)
    if vary_axes:
        # inside shard_map with check_vma: scan carries must start with the
        # same varying-manual-axes type as the loop-carried updates
        m0 = jax.lax.pcast(m0, tuple(vary_axes), to="varying")
        l0 = jax.lax.pcast(l0, tuple(vary_axes), to="varying")
        a0 = jax.lax.pcast(a0, tuple(vary_axes), to="varying")
    xs = (kc, vc, jnp.arange(n_chunks)) if em is None else (
        kc, vc, jnp.arange(n_chunks), em)
    (m_f, l_f, acc), _ = jax.lax.scan(body, (m0, l0, a0), xs)
    if return_stats:
        return acc, m_f, l_f
    out = acc / jnp.maximum(l_f, 1e-30)[..., None]
    out = jnp.moveaxis(out, 3, 1)  # [B,Tq,Hkv,g,Dh]
    return out.reshape(b, tq, hq, dh).astype(q.dtype)


def merge_attn_stats(parts, q_shape, dtype):
    """Merge flash partials [(acc, m, l), ...] by log-sum-exp -> [B,Tq,Hq,Dh].
    """
    b, tq, hq, dh = q_shape
    m_g = parts[0][1]
    for _, m, _ in parts[1:]:
        m_g = jnp.maximum(m_g, m)
    l_g = sum(l * jnp.exp(m - m_g) for _, m, l in parts)
    acc_g = sum(acc * jnp.exp(m - m_g)[..., None] for acc, m, _ in parts)
    out = acc_g / jnp.maximum(l_g, 1e-30)[..., None]
    out = jnp.moveaxis(out, 3, 1)
    return out.reshape(b, tq, hq, dh).astype(dtype)


def attend_cache_plus_block(q, kk, vv, *, cache_cap, cache_len, q_abs,
                            window, extra_mask, attn_softcap, impl,
                            kv_chunk, rolling):
    """Single-softmax attention over [cache(cap) ++ block(T)] — the
    decode/verify read path shared by every attention block.

    ``kk``/``vv``: the cache's *logical view* concatenated with the
    in-flight block's K/V. For a dense cache the logical view is the
    buffer itself; for a paged cache it is :func:`repro.models.kvcache.
    pool_view` (page-table-ordered gather), which holds identical values
    at every committed position, so both layouts produce bit-identical
    attention (garbage beyond ``cache_len`` is masked the same way).

    ``q_abs``: [Tq] or [B,Tq] absolute position of each query token (tree
    nodes carry depth-based positions). ``cache_len``: scalar or [B]. Cache
    slot j of a non-rolling cache holds absolute position j; a rolling cache
    slot j holds the largest t<cache_len with t % cap == j. ``extra_mask``:
    [Tq,T_blk] or [B,Tq,T_blk] tree/bidir mask for the in-flight block tail
    (defaults to causal-in-block by block order).
    """
    b, tq = q.shape[:2]
    total = kk.shape[1]
    t_blk = total - cache_cap
    clen = jnp.asarray(cache_len)
    batched = (clen.ndim > 0) or (jnp.asarray(q_abs).ndim > 1) or (
        extra_mask is not None and extra_mask.ndim > 2)
    if batched:
        clen = jnp.broadcast_to(clen.reshape(-1, 1, 1), (b, 1, 1))
        qpos = jnp.broadcast_to(
            jnp.asarray(q_abs).reshape(-1, tq)[..., None], (b, tq, 1))
        jc = jnp.arange(cache_cap)[None, None, :]
    else:
        qpos = jnp.asarray(q_abs)[:, None]                  # [Tq,1]
        jc = jnp.arange(cache_cap)[None, :]
    if rolling:
        last = clen - 1
        abs_kpos = last - jnp.mod(last - jc, cache_cap)
        cache_ok = (abs_kpos >= 0) & (abs_kpos < clen) & (abs_kpos <= qpos)
        if window is not None:
            cache_ok &= abs_kpos > (qpos - window)
    else:
        cache_ok = (jc < clen) & (jc <= qpos)
        if window is not None:
            cache_ok &= jc > (qpos - window)
    tgt_shape = (b, tq, cache_cap) if batched else (tq, cache_cap)
    cache_ok = jnp.broadcast_to(cache_ok, tgt_shape)
    if extra_mask is not None:
        blk = extra_mask
        if batched and blk.ndim == 2:
            blk = jnp.broadcast_to(blk[None], (b, tq, t_blk))
    else:
        blk = jnp.tril(jnp.ones((tq, t_blk), dtype=bool), k=t_blk - tq)
        if window is not None:
            ji = jnp.arange(t_blk)[None, :]
            ii = jnp.arange(tq)[:, None] + (t_blk - tq)
            blk = blk & (ji > (ii - window))
        if batched:
            blk = jnp.broadcast_to(blk[None], (b, tq, t_blk))
    full_mask = jnp.concatenate([cache_ok, blk], axis=-1)
    return attend(q, kk, vv, causal=False, q_offset=0, extra_mask=full_mask,
                  attn_softcap=attn_softcap, impl=impl, kv_chunk=kv_chunk)


def attend(q, k, v, *, causal=True, q_offset=0, window=None, kv_len=None,
           extra_mask=None, scale=None, attn_softcap=None, impl="auto",
           kv_chunk=1024):
    """Unified attention entry point."""
    tq, tkv = q.shape[1], k.shape[1]
    if impl == "auto":
        impl = "dense" if (tq * tkv <= 256 * 1024) else "chunked"
    if impl == "dense":
        mask = make_attention_mask(tq, tkv, causal=causal, q_offset=q_offset,
                                   window=window, kv_len=kv_len)
        if extra_mask is not None:
            mask = mask & extra_mask
        return attend_dense(q, k, v, mask, scale=scale,
                            attn_softcap=attn_softcap)
    if impl == "chunked":
        return attend_chunked(q, k, v, causal=causal, q_offset=q_offset,
                              window=window, kv_len=kv_len,
                              extra_mask=extra_mask, scale=scale,
                              attn_softcap=attn_softcap, kv_chunk=kv_chunk)
    if impl == "pallas":
        from repro.kernels import ops as kops
        return kops.flash_attention(
            q, k, v, causal=causal, q_offset=q_offset, window=window,
            kv_len=kv_len, scale=scale, attn_softcap=attn_softcap)
    raise ValueError(f"unknown attention impl {impl!r}")


# ------------------------------------------------------------- module -----
def attn_init(key, cfg, cross: bool = False):
    """QKV/O projections. Fused layouts: wq [d, Hq*Dh], wk/wv [d, Hkv*Dh]."""
    d, hq, hkv, dh = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = pm.split(key, 4)
    p = {
        "wq": pm.dense_init(ks[0], d, hq * dh),
        "wk": pm.dense_init(ks[1], d, hkv * dh),
        "wv": pm.dense_init(ks[2], d, hkv * dh),
        "wo": pm.dense_init(ks[3], hq * dh, d, scale=(hq * dh) ** -0.5),
    }
    if cfg.qkv_bias and not cross:
        p["bq"] = pm.zeros((hq * dh,))
        p["bk"] = pm.zeros((hkv * dh,))
        p["bv"] = pm.zeros((hkv * dh,))
    if cfg.qk_norm:
        p["q_norm"] = pm.ones((dh,))
        p["k_norm"] = pm.ones((dh,))
    return p


def project_qkv(p, x, cfg, positions=None, rope: bool = True):
    """x:[B,T,d] -> q:[B,T,Hq,Dh], k,v:[B,T,Hkv,Dh] (+rope, +qknorm)."""
    b, t, _ = x.shape
    hq, hkv, dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = dense(p["wq"], x, p.get("bq")).reshape(b, t, hq, dh)
    k = dense(p["wk"], x, p.get("bk")).reshape(b, t, hkv, dh)
    v = dense(p["wv"], x, p.get("bv")).reshape(b, t, hkv, dh)
    if cfg.qk_norm:
        q = _rms_head(q, p["q_norm"], cfg.norm_eps)
        k = _rms_head(k, p["k_norm"], cfg.norm_eps)
    if rope and positions is not None:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    q = constrain(q, ("batch", None, "heads", None))
    k = constrain(k, ("batch", None, "kv_heads", None))
    v = constrain(v, ("batch", None, "kv_heads", None))
    return q, k, v


def _rms_head(x, scale, eps):
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * scale).astype(dt)


def out_proj(p, attn_out):
    b, t, hq, dh = attn_out.shape
    y = dense(p["wo"], attn_out.reshape(b, t, hq * dh))
    return y
