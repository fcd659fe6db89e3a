"""Plain reference of the served target, and the comparison that decides
``correct``.

The reference is a straightforward decoder forward (Qwen2 / Qwen3
family: RMSNorm, RoPE on rotated halves, grouped-query attention with
optional q/k/v biases and per-head q/k RMSNorm, SwiGLU MLP, tied or
untied head) written in ``jax.numpy`` from the configuration file alone.
It imports nothing of the program. It computes in float32 with every
matrix product at ``Precision.HIGHEST``, one layer at a time, over the
benchmark's own weights (``bench/model.py``).

The comparison: for a served request with prompt ``p`` and served tokens
``s``, the reference runs once over ``p + s`` and, at every position that
produced a served token, reads how far the served token's logit lies
below the reference's best logit there. ``widest_gap`` is the largest
such gap over the requests a run checks. Greedy serving that computes what
the configuration states leaves only rounding in it.

The control (``precision="fp8"``) is the same forward with every weight
matrix rounded to float8 e4m3 (per output channel scale) and activations
in bfloat16: the next precision below the configuration's bfloat16. Its
gap is read for the token it puts first at each position.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 512           # query rows per attention block
T_STEP = 1024           # sequence lengths are multiples of this
OUT_STEP = 256          # served positions are read in multiples of this


def _rms(x, scale, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def _rope(x, pos, theta):
    """x [T, H, Dh]: rotate the two halves of each head."""
    dh = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    ang = pos[:, None].astype(jnp.float32) * inv            # [T, Dh/2]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, -1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           -1).astype(x.dtype)


def _fp8(w):
    """Round a weight matrix [..., in, out] to float8 e4m3 with one scale
    per output column; returned dequantized, in bfloat16."""
    wf = w.astype(jnp.float32)
    s = jnp.max(jnp.abs(wf), axis=-2, keepdims=True) / 448.0
    s = jnp.where(s > 0, s, 1.0)
    q = (wf / s).astype(jnp.float8_e4m3fn)
    return (q.astype(jnp.float32) * s).astype(jnp.bfloat16)


def _forward_hidden(w, tokens, arch, act, quant):
    """Final-normed hidden states [T, d] of one sequence ``tokens`` [T]
    (causal; positions 0..T-1), activations in ``act``, every weight
    matrix passed through ``quant`` where it is used (one layer at a
    time)."""
    t = tokens.shape[0]
    pos = jnp.arange(t)
    hq, hkv, dh = arch.heads, arch.kv_heads, arch.head_dim
    g = hq // hkv
    prec = HI if act == jnp.float32 else None

    def mm(x, m):
        return jnp.einsum("ti,io->to", x, quant(m).astype(act),
                          precision=prec)

    def layer(x, lw):
        h = _rms(x, lw["attn_norm"], arch.eps)
        q, k, v = mm(h, lw["wq"]), mm(h, lw["wk"]), mm(h, lw["wv"])
        if arch.qkv_bias:
            q = q + lw["bq"].astype(act)
            k = k + lw["bk"].astype(act)
            v = v + lw["bv"].astype(act)
        q, k, v = (q.reshape(t, hq, dh), k.reshape(t, hkv, dh),
                   v.reshape(t, hkv, dh))
        if arch.qk_norm:
            q = _rms(q, lw["q_norm"], arch.eps)
            k = _rms(k, lw["k_norm"], arch.eps)
        q, k = _rope(q, pos, arch.theta), _rope(k, pos, arch.theta)
        qb = (q.astype(jnp.float32) * dh ** -0.5).reshape(
            t // Q_BLOCK, Q_BLOCK, hkv, g, dh)
        kf, vf = k.astype(jnp.float32), v.astype(jnp.float32)

        def block(args):
            qi, i = args
            s = jnp.einsum("qhgd,khd->hgqk", qi, kf, precision=prec)
            qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
            s = jnp.where(pos[None, :] <= qpos[:, None], s, -jnp.inf)
            p = jax.nn.softmax(s, axis=-1)
            return jnp.einsum("hgqk,khd->qhgd", p, vf, precision=prec)

        o = jax.lax.map(block, (qb, jnp.arange(t // Q_BLOCK)))
        o = o.reshape(t, hq * dh).astype(act)
        x = x + mm(o, lw["wo"])
        h = _rms(x, lw["mlp_norm"], arch.eps)
        gate = mm(h, lw["w_gate"]).astype(jnp.float32)
        up = mm(h, lw["w_up"]).astype(jnp.float32)
        x = x + mm((jax.nn.silu(gate) * up).astype(act), lw["w_down"])
        return x, None

    x = quant(w["embed"][tokens].T).T.astype(act)
    x, _ = jax.lax.scan(layer, x, w["layers"])
    return _rms(x, w["final_norm"], arch.eps)


def _precision(precision):
    """(activation dtype, weight rounding) of a reference precision."""
    if precision == "fp32":
        return jnp.float32, lambda m: m
    if precision == "fp8":
        return jnp.bfloat16, _fp8
    raise ValueError(f"unknown reference precision {precision!r}")


@functools.partial(jax.jit, static_argnames=("arch", "n_out", "precision"))
def tail_logits(w, tokens, start, *, arch, n_out, precision="fp32"):
    """Float32 logits [n_out, V] at positions ``start .. start+n_out-1`` of
    ``tokens`` [T] (T a multiple of ``Q_BLOCK``; padding after the
    request's last token does not reach these positions)."""
    act, quant = _precision(precision)
    h = _forward_hidden(w, tokens, arch, act, quant)
    h = jax.lax.dynamic_slice_in_dim(h, start, n_out, 0)
    head = w["embed"].T if arch.tied else w["head"]
    prec = HI if act == jnp.float32 else None
    return jnp.einsum("td,dv->tv", h, quant(head).astype(act),
                      precision=prec).astype(jnp.float32)


@jax.jit
def _gaps(ref_logits, toks):
    """Per position: reference best minus reference logit of ``toks``."""
    picked = jnp.take_along_axis(ref_logits, toks[:, None], 1)[:, 0]
    return jnp.max(ref_logits, -1) - picked


@jax.jit
def _control_gaps(ref_logits, ctl_logits):
    return _gaps(ref_logits, jnp.argmax(ctl_logits, -1).astype(jnp.int32))


def seq_len(n: int) -> int:
    """The reference's sequence length for ``n`` tokens: a multiple of
    ``T_STEP``, so that a run needs few programs."""
    return -(-n // T_STEP) * T_STEP


def out_len(n: int) -> int:
    """The number of positions read for ``n`` served tokens."""
    return -(-n // OUT_STEP) * OUT_STEP


def gaps(w, arch, prompt, served, *, t_len, n_out, control=False):
    """Gaps of one request: (served-token gaps [n], control gaps [n] or
    None). ``served`` are the tokens the program produced after
    ``prompt``."""
    p, n = len(prompt), len(served)
    assert n <= n_out and p + n <= t_len, (p, n, t_len, n_out)
    seq = np.zeros((t_len,), np.int32)
    seq[:p] = prompt
    seq[p:p + n] = served
    start = p - 1
    # the tail window must fit inside the sequence; shift it left when the
    # request sits near the end and read the served positions within it
    off = max(0, start + n_out - t_len)
    seq_d = jnp.asarray(seq)
    ref = tail_logits(w, seq_d, start - off, arch=arch, n_out=n_out)
    toks = np.zeros((n_out,), np.int32)
    toks[off:off + n] = served
    g = np.asarray(_gaps(ref, jnp.asarray(toks)))[off:off + n]
    c = None
    if control:
        ctl = tail_logits(w, seq_d, start - off, arch=arch, n_out=n_out,
                          precision="fp8")
        c = np.asarray(_control_gaps(ref, ctl))[off:off + n]
    return g, c
