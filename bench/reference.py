"""The comparison that decides ``correct``, shared by every architecture,
and the plain reference of the dense target.

An architecture's reference module (this one for a configuration that
names no ``model``; ``bench/archs/<name>_reference.py`` for one that
does, see ``bench/spec.py``) provides one function::

    tail_logits(w, tokens, start, *, arch, n_out, precision) -> [n_out, V]

the float32 logits at positions ``start .. start+n_out-1`` of one
sequence ``tokens`` (causal, positions 0..T-1) over the benchmark's
weights ``w`` (its architecture module's layout), computed from the
configuration file alone: ``precision="fp32"`` in float32 with every
matrix product at ``Precision.HIGHEST``; ``precision="fp8"`` the control,
with every weight matrix rounded to float8 e4m3 (one scale per output
channel) and activations in bfloat16, the next precision below the
configurations' bfloat16. It imports nothing of the program. The pieces
below (:func:`rms`, :func:`rope`, :func:`attention`,
:func:`decoder_layer`, :func:`embed`, :func:`head_logits`,
:func:`precision_of`) are there for a reference module to reuse; an architecture brings its own
mathematics and no comparison of its own.

The dense reference is a straightforward decoder forward (Qwen2 / Qwen3
family: RMSNorm, RoPE on rotated halves, grouped-query attention with
optional q/k/v biases and per-head q/k RMSNorm, SwiGLU MLP, tied or
untied head), one layer at a time, over ``bench/model.py``'s weights.

The comparison (:func:`gaps`): for a served request with prompt ``p`` and
served tokens ``s``, the cell's ``tail_logits`` runs once over ``p + s``
and, at every position that produced a served token, reads how far the
served token's logit lies below the reference's best logit there.
``widest_gap`` is the largest such gap over the requests a run checks.
Greedy serving that computes what the configuration states leaves only
rounding in it. The control's gap is read for the token it puts first at
each position.
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


def rms(x, scale, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def rope(x, pos, theta):
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


def precision_of(precision):
    """(activation dtype, weight rounding, matrix-product precision) of a
    reference precision."""
    if precision == "fp32":
        return jnp.float32, lambda m: m, HI
    if precision == "fp8":
        return jnp.bfloat16, _fp8, None
    raise ValueError(f"unknown reference precision {precision!r}")


def attention(q, k, v, *, prec, window=None):
    """Causal grouped-query attention of one sequence in float32, in blocks
    of ``Q_BLOCK`` query rows: q [T, Hq, Dh] and k, v [T, Hkv, Dh], after
    RoPE; returns [T, Hq, Dh]. With ``window``, the query at position p
    reads the keys at p - window + 1 .. p."""
    t, hq, dh = q.shape
    hkv = k.shape[1]
    pos = jnp.arange(t)
    qb = (q.astype(jnp.float32) * dh ** -0.5).reshape(
        t // Q_BLOCK, Q_BLOCK, hkv, hq // hkv, dh)
    kf, vf = k.astype(jnp.float32), v.astype(jnp.float32)

    def block(args):
        qi, i = args
        s = jnp.einsum("qhgd,khd->hgqk", qi, kf, precision=prec)
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        seen = pos[None, :] <= qpos[:, None]
        if window is not None:
            seen &= pos[None, :] > qpos[:, None] - window
        s = jnp.where(seen, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hgqk,khd->qhgd", p, vf, precision=prec)

    return jax.lax.map(block, (qb, jnp.arange(t // Q_BLOCK))).reshape(
        t, hq, dh)


def decoder_layer(x, lw, pos, *, arch, act, quant, prec, window=None):
    """One pre-norm decoder layer of the dense family over x [T, d]:
    attention (q/k/v biases and q/k RMSNorm as ``arch`` states, RoPE, a
    sliding ``window`` where given), then a SwiGLU MLP; the weights of
    ``lw`` (one layer of ``bench/model.py``'s ``layer_stack``) pass
    through ``quant`` where they are used."""
    t = x.shape[0]
    hq, hkv, dh = arch.heads, arch.kv_heads, arch.head_dim

    def mm(y, m):
        return jnp.einsum("ti,io->to", y, quant(m).astype(act),
                          precision=prec)

    h = rms(x, lw["attn_norm"], arch.eps)
    q, k, v = mm(h, lw["wq"]), mm(h, lw["wk"]), mm(h, lw["wv"])
    if arch.qkv_bias:
        q = q + lw["bq"].astype(act)
        k = k + lw["bk"].astype(act)
        v = v + lw["bv"].astype(act)
    q, k, v = (q.reshape(t, hq, dh), k.reshape(t, hkv, dh),
               v.reshape(t, hkv, dh))
    if arch.qk_norm:
        q = rms(q, lw["q_norm"], arch.eps)
        k = rms(k, lw["k_norm"], arch.eps)
    q, k = rope(q, pos, arch.theta), rope(k, pos, arch.theta)
    o = attention(q, k, v, prec=prec, window=window)
    x = x + mm(o.reshape(t, hq * dh).astype(act), lw["wo"])
    h = rms(x, lw["mlp_norm"], arch.eps)
    gate = mm(h, lw["w_gate"]).astype(jnp.float32)
    up = mm(h, lw["w_up"]).astype(jnp.float32)
    return x + mm((jax.nn.silu(gate) * up).astype(act), lw["w_down"])


def embed(w, tokens, act, quant):
    """The embedding rows of ``tokens``, in ``act``."""
    return quant(w["embed"][tokens].T).T.astype(act)


def head_logits(w, x, start, *, arch, n_out, act, quant, prec):
    """Float32 logits [n_out, V] of the final-normed hidden states of
    ``x`` [T, d] at positions ``start .. start+n_out-1``, through the tied
    embedding or the untied ``head``."""
    h = jax.lax.dynamic_slice_in_dim(rms(x, w["final_norm"], arch.eps),
                                     start, n_out, 0)
    head = w["embed"].T if arch.tied else w["head"]
    return jnp.einsum("td,dv->tv", h, quant(head).astype(act),
                      precision=prec).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("arch", "n_out", "precision"))
def tail_logits(w, tokens, start, *, arch, n_out, precision="fp32"):
    """The dense target's float32 logits [n_out, V] at positions ``start
    .. start+n_out-1`` of ``tokens`` [T] (T a multiple of ``Q_BLOCK``;
    padding after the request's last token does not reach these
    positions); every layer attends to the whole context."""
    act, quant, prec = precision_of(precision)
    pos = jnp.arange(tokens.shape[0])

    def layer(x, lw):
        return decoder_layer(x, lw, pos, arch=arch, act=act, quant=quant,
                             prec=prec), None

    x, _ = jax.lax.scan(layer, embed(w, tokens, act, quant), w["layers"])
    return head_logits(w, x, start, arch=arch, n_out=n_out, act=act,
                       quant=quant, prec=prec)


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


def gaps(tail_logits, w, arch, prompt, served, *, t_len, n_out,
         control=False):
    """Gaps of one request against the cell's ``tail_logits``: (served-token
    gaps [n], control gaps [n] or None). ``served`` are the tokens the
    program produced after ``prompt``."""
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
