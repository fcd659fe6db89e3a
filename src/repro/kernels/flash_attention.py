"""Pallas TPU flash attention (fwd + bwd) with GQA, causal/window masks,
gemma-style attention-logit softcap, and KV-length masking.

TARGET: TPU (MXU 128x128; VMEM-tiled via BlockSpec). Validated on CPU in
interpret mode against the pure-jnp oracle in ``ref.py``, and compiled for
a described v5e in tests/test_tpu_compile.py.

Layouts (kernel-internal): q [B, Hq, Tq, D]; k,v [B, Hkv, Tkv, D].
Grid: (B, Hq, nq, nk) — the kv dimension is the minor (sequential) grid axis,
carrying running (m, l, acc) in VMEM scratch across kv steps (the standard
TPU flash schedule). Block sizes default to (128, 128) and are clamped and
padded to hardware-aligned shapes.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _block_pos(i, j, q_off, bq, bk):
    """Absolute positions of a (q block, kv block) pair as a ``[bq, 1]``
    column and a ``[1, bk]`` row (Mosaic has no 1-D iota)."""
    qp = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, 1), 0) + q_off
    kp = j * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
    return qp, kp


def _mask_block(qp, kp, *, causal, window, kv_len):
    m = jnp.ones((qp.shape[0], kp.shape[1]), dtype=bool)
    if causal:
        m &= kp <= qp
    if window is not None:
        m &= kp > (qp - window)
    if kv_len is not None:
        m &= kp < kv_len
    return m


def _fwd_kernel(q_off_ref, kv_len_ref, q_ref, k_ref, v_ref,  # inputs
                o_ref, lse_ref,                              # outputs
                acc_ref, m_ref, l_ref,                       # scratch
                *, causal, window, softcap, scale, bq, bk, nk,
                has_kvlen):
    i, j = pl.program_id(2), pl.program_id(3)
    b = pl.program_id(0)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32) * scale       # [bq, D]
    k = k_ref[0, 0].astype(jnp.float32)               # [bk, D]
    v = v_ref[0, 0].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # [bq, bk]
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)

    qpos, kpos = _block_pos(i, j, q_off_ref[0], bq, bk)
    kv_len = kv_len_ref[b] if has_kvlen else None
    mask = _mask_block(qpos, kpos, causal=causal, window=window,
                       kv_len=kv_len)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                               # [bq, 1]
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())))
    m_ref[...] = m_new

    @pl.when(j == nk - 1)
    def _final():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)
        lse_ref[0, 0] = m_ref[...] + jnp.log(l)


def flash_attention_fwd(q, k, v, *, causal=True, q_offset=0, window=None,
                        kv_len=None, attn_softcap=None, scale=None,
                        bq=128, bk=128, interpret=False):
    """q [B,Hq,Tq,D]; k,v [B,Hkv,Tkv,D] -> (o [B,Hq,Tq,D], lse [B,Hq,Tq]).

    Inside the kernel the per-row statistics (lse, running m/l) carry a
    trailing unit lane dim, ``[.., bq, 1]``: Mosaic tiles the last two dims
    (8, 128) and refuses a block whose second-minor dim is a unit slice."""
    b, hq, tq, d = q.shape
    hkv, tkv = k.shape[1], k.shape[2]
    g = hq // hkv
    scale = scale if scale is not None else d ** -0.5
    bq = min(bq, tq)
    bk = min(bk, tkv)
    # pad to block multiples
    pq = (-tq) % bq
    pk = (-tkv) % bk
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, pq), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, pk), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, pk), (0, 0)))
    nq = (tq + pq) // bq
    nk = (tkv + pk) // bk
    # padded keys masked via kv_len
    eff_kv_len = jnp.full((b,), tkv, jnp.int32) if kv_len is None else \
        jnp.broadcast_to(jnp.asarray(kv_len, jnp.int32).reshape(-1), (b,))
    q_off = jnp.broadcast_to(
        jnp.asarray(q_offset, jnp.int32).reshape(-1), (1,))

    kernel = functools.partial(
        _fwd_kernel, causal=causal, window=window, softcap=attn_softcap,
        scale=scale, bq=bq, bk=bk, nk=nk, has_kvlen=True)

    out_shape = [
        jax.ShapeDtypeStruct(qp.shape, q.dtype),
        jax.ShapeDtypeStruct((b, hq, tq + pq, 1), jnp.float32),
    ]
    o, lse = pl.pallas_call(
        kernel,
        name="flash_attention_fwd",
        grid=(b, hq, nq, nk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, bq, d), lambda b_, h, i, j: (b_, h, i, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda b_, h, i, j, g=g: (b_, h // g, j, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda b_, h, i, j, g=g: (b_, h // g, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b_, h, i, j: (b_, h, i, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b_, h, i, j: (b_, h, i, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        out_shape=out_shape,
        interpret=interpret,
    )(q_off, eff_kv_len, qp, kp, vp)
    return o[:, :, :tq], lse[:, :, :tq, 0]


# --------------------------------------------------------------- backward --
def _bwd_dq_kernel(q_off_ref, kv_len_ref, q_ref, k_ref, v_ref, do_ref,
                   lse_ref, delta_ref, dq_ref, dq_acc,
                   *, causal, window, softcap, scale, bq, bk, nk):
    i, j = pl.program_id(2), pl.program_id(3)
    b = pl.program_id(0)

    @pl.when(j == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    q = q_ref[0, 0].astype(jnp.float32) * scale
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    do = do_ref[0, 0].astype(jnp.float32)
    lse = lse_ref[0, 0]
    delta = delta_ref[0, 0]

    s_raw = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))
    if softcap is not None:
        t = jnp.tanh(s_raw / softcap)
        s = softcap * t
        dcap = 1.0 - t * t
    else:
        s = s_raw
        dcap = None
    qpos, kpos = _block_pos(i, j, q_off_ref[0], bq, bk)
    mask = _mask_block(qpos, kpos, causal=causal, window=window,
                       kv_len=kv_len_ref[b])
    s = jnp.where(mask, s, NEG_INF)
    p = jnp.exp(s - lse)                                # lse, delta [bq, 1]
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())))
    ds = p * (dp - delta)
    if dcap is not None:
        ds = ds * dcap
    dq_acc[...] += jax.lax.dot_general(ds, k, (((1,), (0,)), ((), ())))

    @pl.when(j == nk - 1)
    def _final():
        dq_ref[0, 0] = (dq_acc[...] * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_off_ref, kv_len_ref, q_ref, k_ref, v_ref, do_ref,
                    lse_ref, delta_ref, dk_ref, dv_ref, dk_acc, dv_acc,
                    *, causal, window, softcap, scale, bq, bk, nq, g):
    # grid: (B, Hq, nk, nq) — q is the minor axis; dk/dv accumulate per
    # kv block summing over q-heads handled by separate (B, Hq) programs
    # writing into per-head buffers reduced outside for GQA.
    j, i = pl.program_id(2), pl.program_id(3)
    b = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q = q_ref[0, 0].astype(jnp.float32) * scale
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    do = do_ref[0, 0].astype(jnp.float32)
    lse = lse_ref[0, 0]
    delta = delta_ref[0, 0]

    s_raw = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))
    if softcap is not None:
        t = jnp.tanh(s_raw / softcap)
        s = softcap * t
        dcap = 1.0 - t * t
    else:
        s = s_raw
        dcap = None
    qpos, kpos = _block_pos(i, j, q_off_ref[0], bq, bk)
    mask = _mask_block(qpos, kpos, causal=causal, window=window,
                       kv_len=kv_len_ref[b])
    s = jnp.where(mask, s, NEG_INF)
    p = jnp.exp(s - lse)                                # [bq, bk]
    dv_acc[...] += jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())))
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())))
    ds = p * (dp - delta)
    if dcap is not None:
        ds = ds * dcap
    dk_acc[...] += jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())))

    @pl.when(i == nq - 1)
    def _final():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


def flash_attention_bwd(q, k, v, o, lse, do, *, causal=True, q_offset=0,
                        window=None, kv_len=None, attn_softcap=None,
                        scale=None, bq=128, bk=128, interpret=False):
    b, hq, tq, d = q.shape
    hkv, tkv = k.shape[1], k.shape[2]
    g = hq // hkv
    scale = scale if scale is not None else d ** -0.5
    bq = min(bq, tq)
    bk = min(bk, tkv)
    pq = (-tq) % bq
    pk = (-tkv) % bk
    pad4 = lambda x, p: jnp.pad(x, ((0, 0), (0, 0), (0, p), (0, 0)))
    pad3 = lambda x, p, val=0.0: jnp.pad(
        x, ((0, 0), (0, 0), (0, p)), constant_values=val)
    qp, kp2, vp = pad4(q, pq), pad4(k, pk), pad4(v, pk)
    dop = pad4(do, pq)
    # lse padding must keep exp(s - lse) == 0 on padded q rows
    lsep = pad3(lse, pq, 1.0)[..., None]
    delta = (do.astype(jnp.float32) * o.astype(jnp.float32)).sum(-1)
    deltap = pad3(delta, pq)[..., None]
    nq = (tq + pq) // bq
    nk = (tkv + pk) // bk
    eff_kv_len = jnp.full((b,), tkv, jnp.int32) if kv_len is None else \
        jnp.broadcast_to(jnp.asarray(kv_len, jnp.int32).reshape(-1), (b,))
    q_off = jnp.broadcast_to(
        jnp.asarray(q_offset, jnp.int32).reshape(-1), (1,))

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, causal=causal, window=window,
                          softcap=attn_softcap, scale=scale, bq=bq, bk=bk,
                          nk=nk),
        name="flash_attention_bwd_dq",
        grid=(b, hq, nq, nk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, bq, d), lambda b_, h, i, j: (b_, h, i, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda b_, h, i, j, g=g: (b_, h // g, j, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda b_, h, i, j, g=g: (b_, h // g, j, 0)),
            pl.BlockSpec((1, 1, bq, d), lambda b_, h, i, j: (b_, h, i, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b_, h, i, j: (b_, h, i, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b_, h, i, j: (b_, h, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d),
                               lambda b_, h, i, j: (b_, h, i, 0)),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        out_shape=jax.ShapeDtypeStruct(qp.shape, q.dtype),
        interpret=interpret,
    )(q_off, eff_kv_len, qp, kp2, vp, dop, lsep, deltap)

    # dk/dv per q-head, then reduce over the GQA group
    dk_h, dv_h = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, causal=causal, window=window,
                          softcap=attn_softcap, scale=scale, bq=bq, bk=bk,
                          nq=nq, g=g),
        name="flash_attention_bwd_dkv",
        grid=(b, hq, nk, nq),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, bq, d), lambda b_, h, j, i: (b_, h, i, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda b_, h, j, i, g=g: (b_, h // g, j, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda b_, h, j, i, g=g: (b_, h // g, j, 0)),
            pl.BlockSpec((1, 1, bq, d), lambda b_, h, j, i: (b_, h, i, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b_, h, j, i: (b_, h, i, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b_, h, j, i: (b_, h, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bk, d), lambda b_, h, j, i: (b_, h, j, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b_, h, j, i: (b_, h, j, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32)],
        out_shape=[
            jax.ShapeDtypeStruct((b, hq, tkv + pk, d), jnp.float32),
            jax.ShapeDtypeStruct((b, hq, tkv + pk, d), jnp.float32),
        ],
        interpret=interpret,
    )(q_off, eff_kv_len, qp, kp2, vp, dop, lsep, deltap)
    dk = dk_h.reshape(b, hkv, g, tkv + pk, d).sum(2)[:, :, :tkv]
    dv = dv_h.reshape(b, hkv, g, tkv + pk, d).sum(2)[:, :, :tkv]
    return dq[:, :, :tq], dk.astype(k.dtype), dv.astype(v.dtype)
