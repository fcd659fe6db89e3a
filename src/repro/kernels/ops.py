"""Jit'd public wrappers for the Pallas kernels.

``interpret`` defaults to the backend: compiled Mosaic kernels on TPU,
the Pallas interpreter on CPU (where the tests check the kernels against
``kernels/ref.py``). Any other backend is an error rather than a silent
interpreter fallback.

``flash_attention`` carries a custom_vjp wired to the Pallas backward
kernels, so the same op serves training.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import cascade_attention as casc
from repro.kernels import flash_attention as fa


def default_interpret() -> bool:
    """Interpret mode on CPU, compiled kernels on TPU; nothing else runs
    these kernels."""
    backend = jax.default_backend()
    if backend not in ("tpu", "cpu"):
        raise RuntimeError(
            f"Pallas kernels here target TPU (compiled) or CPU (interpret "
            f"mode); backend {backend!r} is neither")
    return backend == "cpu"


# ---------------------------------------------------------------- flash ----
@functools.partial(
    jax.custom_vjp,
    nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _flash(q, k, v, causal, q_offset, window, kv_len, attn_softcap, scale,
           interpret):
    o, _ = fa.flash_attention_fwd(
        q, k, v, causal=causal, q_offset=q_offset, window=window,
        kv_len=kv_len, attn_softcap=attn_softcap, scale=scale,
        interpret=interpret)
    return o


def _flash_fwd(q, k, v, causal, q_offset, window, kv_len, attn_softcap,
               scale, interpret):
    o, lse = fa.flash_attention_fwd(
        q, k, v, causal=causal, q_offset=q_offset, window=window,
        kv_len=kv_len, attn_softcap=attn_softcap, scale=scale,
        interpret=interpret)
    return o, (q, k, v, o, lse)


def _flash_bwd(causal, q_offset, window, kv_len, attn_softcap, scale,
               interpret, res, do):
    q, k, v, o, lse = res
    dq, dk, dv = fa.flash_attention_bwd(
        q, k, v, o, lse, do, causal=causal, q_offset=q_offset, window=window,
        kv_len=kv_len, attn_softcap=attn_softcap, scale=scale,
        interpret=interpret)
    return dq.astype(q.dtype), dk, dv


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, *, causal=True, q_offset=0, window=None,
                    kv_len=None, attn_softcap=None, scale=None,
                    interpret: Optional[bool] = None, layout="BTHD"):
    """Differentiable flash attention.

    layout "BTHD": q [B,T,Hq,D] (model-stack layout) or "BHTD" (kernel
    layout). Returns attention output in the same layout.
    """
    interpret = default_interpret() if interpret is None else interpret
    if layout == "BTHD":
        q_, k_, v_ = (jnp.swapaxes(x, 1, 2) for x in (q, k, v))
    else:
        q_, k_, v_ = q, k, v
    o = _flash(q_, k_, v_, causal, q_offset, window, kv_len, attn_softcap,
               scale, interpret)
    return jnp.swapaxes(o, 1, 2) if layout == "BTHD" else o


# -------------------------------------------------------------- cascade ----
def cascade_attention(q, cache_k, cache_v, blk_k, blk_v, *, cache_len,
                      q_abs, tree_mask, window=None, attn_softcap=None,
                      scale=None, rolling=False, n_splits=8, bk=512,
                      interpret: Optional[bool] = None, layout="BTHD"):
    """The paper's cascade verify op (inference only)."""
    interpret = default_interpret() if interpret is None else interpret
    if layout == "BTHD":
        q_, ck, cv, bk_, bv = (jnp.swapaxes(x, 1, 2)
                               for x in (q, cache_k, cache_v, blk_k, blk_v))
    else:
        q_, ck, cv, bk_, bv = q, cache_k, cache_v, blk_k, blk_v
    o = casc.cascade_attention(
        q_, ck, cv, bk_, bv, cache_len=cache_len, q_abs=q_abs,
        tree_mask=tree_mask, window=window, attn_softcap=attn_softcap,
        scale=scale, rolling=rolling, n_splits=n_splits, bk=bk,
        interpret=interpret)
    return jnp.swapaxes(o, 1, 2) if layout == "BTHD" else o


def cascade_attention_paged(q, pool_k, pool_v, page_table, blk_k, blk_v, *,
                            cache_len, q_abs, tree_mask, window=None,
                            attn_softcap=None, scale=None, n_splits=8,
                            interpret: Optional[bool] = None,
                            layout="BTHD", pos_stride=None, pos_offset=None):
    """Cascade verify over a PAGED cache (``cache_impl="paged"`` storage).

    ``pool_k`` / ``pool_v``: page pools in the engine's storage layout
    [P, page, Hkv, D] (``layout="BTHD"``, matching models/kvcache.py) or
    the kernel layout [P, Hkv, page, D] (``layout="BHTD"``).
    ``page_table`` [B, max_pages]: physical page of each logical page
    (out-of-range sentinel entries mark unallocated pages). The page table
    is scalar-prefetched so the Pallas kernel DMAs pages straight from the
    pool — no dense gather of the logical view, and the index_map clamps
    dead logical pages to the last live one so HBM traffic scales with
    ``cache_len``, not table capacity. ``pos_stride``/``pos_offset``
    relocate logical page ``i`` to absolute positions
    ``i*pos_stride + pos_offset + [0, page)`` for kv_seq-sharded pools
    (see ``cascade_attention.cascade_phase1_paged``).
    """
    interpret = default_interpret() if interpret is None else interpret
    if layout == "BTHD":
        q_, bk_, bv = (jnp.swapaxes(x, 1, 2) for x in (q, blk_k, blk_v))
        pk, pv = (jnp.swapaxes(x, 1, 2) for x in (pool_k, pool_v))
    else:
        q_, bk_, bv, pk, pv = q, blk_k, blk_v, pool_k, pool_v
    o = casc.cascade_attention_paged(
        q_, pk, pv, page_table, bk_, bv, cache_len=cache_len, q_abs=q_abs,
        tree_mask=tree_mask, window=window, attn_softcap=attn_softcap,
        scale=scale, n_splits=n_splits, interpret=interpret,
        pos_stride=pos_stride, pos_offset=pos_offset)
    return jnp.swapaxes(o, 1, 2) if layout == "BTHD" else o
