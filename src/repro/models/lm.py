"""Causal LM: embedding -> scanned block stack -> final norm -> lm head.

One ``forward`` covers train / prefill / decode / tree-verify via arguments
(see blocks.py). Returns multi-layer features for DFlash drafter conditioning
and per-layer self-KV of the pass (``kv_outs``) so verification can commit
accepted KV without recompute (SpecInfer-style gather-commit).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config.base import ModelConfig
from repro.models import kvcache
from repro.models import param as pm
from repro.models.blocks import (BlockSpec2, block_apply, block_init,
                                 block_state_init, period_spec)
from repro.models.layers import (embed, embedding_init, rmsnorm, rmsnorm_init,
                                 softcap, unembed)
from repro.distributed.sharding import constrain


# ------------------------------------------------------------------ init ---
@functools.partial(jax.jit, static_argnums=1)
def lm_init(key, cfg: ModelConfig):
    """Parameters in ``cfg.param_dtype``. Draws are made in float32 and cast
    down inside this jit, so XLA fuses each draw into its cast and a
    full-width model never holds a float32 copy of itself."""
    spec, n_periods, tail = period_spec(cfg)
    ks = pm.split(key, 4 + len(tail))
    p: Dict[str, Any] = {
        "tok": embedding_init(ks[0], cfg.vocab_size, cfg.d_model),
        "ln_f": rmsnorm_init(cfg.d_model),
    }
    if not cfg.tie_embeddings:
        p["lm_head"] = pm.dense_init(ks[1], cfg.d_model, cfg.vocab_size,
                                     scale=0.02)
    if n_periods > 0:
        period_params = {}
        for j, bs in enumerate(spec):
            keys = jax.random.split(jax.random.fold_in(ks[2], j), n_periods)
            period_params[f"p{j}"] = jax.vmap(
                lambda k: block_init(k, cfg, bs))(keys)
        p["period"] = period_params
    for i, bs in enumerate(tail):
        p[f"tail{i}"] = block_init(ks[4 + i], cfg, bs)
    return pm.cast_floating(p, cfg.param_dtype)


def init_states(cfg: ModelConfig, batch: int, max_len: int, ctx_len: int = 0,
                dtype=jnp.bfloat16, cache_impl: str = "dense",
                page_size: int = 64, pool_pages: Optional[int] = None,
                page_table=None, ext_pools=None):
    """Allocate per-layer decode states.

    cache_impl="paged": global-attention KV lives in page pools shared
    across the batch; ``page_table`` [B, max_pages] maps each row's
    logical pages to physical pool pages (default: the identity layout,
    ``pool_pages = batch * ceil(max_len/page_size)``). The table is
    replicated into every paged block state (tiny int32) so the scanned
    stack threads it with no extra forward arguments.

    ext_pools: optional ``{state_key: (k_pool, v_pool)}`` of retained
    device pool buffers (``core.state.capture_pools`` of a previous
    wave). Named entries adopt the external buffers instead of allocating
    fresh zeroed pools — no transient pool-sized allocation at wave
    turnover. Stacked-period entries ("p{j}") expect the already-stacked
    ``[n_periods, P, page, H, D]`` buffers capture harvested.
    """
    spec, n_periods, tail = period_spec(cfg)
    if cache_impl == "paged":
        pool_pages, page_table = kvcache.default_page_layout(
            batch, max_len, page_size, pool_pages, page_table)
    ext_pools = ext_pools or {}
    assert not ext_pools or cache_impl == "paged", \
        "retained pool buffers require cache_impl='paged'"
    kw = dict(cache_impl=cache_impl, page_size=page_size,
              pool_pages=pool_pages or 0, page_table=page_table)
    states: Dict[str, Any] = {}
    if n_periods > 0:
        for j, bs in enumerate(spec):
            one = block_state_init(cfg, bs, batch, max_len, ctx_len, dtype,
                                   alloc_pool=f"p{j}" not in ext_pools, **kw)
            # None pool placeholders are empty pytree nodes: tree.map
            # skips them, so no zeroed pool is ever materialized here
            states[f"p{j}"] = jax.tree.map(
                lambda a: jnp.broadcast_to(a, (n_periods,) + a.shape).copy()
                if n_periods > 1 else a[None], one)
    for i, bs in enumerate(tail):
        states[f"tail{i}"] = block_state_init(
            cfg, bs, batch, max_len, ctx_len, dtype,
            alloc_pool=f"tail{i}" not in ext_pools, **kw)
    for name, (k, v) in ext_pools.items():
        st = states.get(name)
        assert (isinstance(st, dict) and "pt" in st
                and st.get("k") is None), \
            f"ext pool {name!r} does not name a paged cache"
        assert k.shape[-4:] == (pool_pages, page_size,
                                cfg.num_kv_heads, cfg.head_dim) \
            and k.dtype == dtype, ("retained pool geometry mismatch",
                                   name, k.shape)
        st["k"], st["v"] = k, v
    states["length"] = jnp.zeros((batch,), jnp.int32)
    return states


def state_batch_axis(key: str) -> int:
    """Batch axis of a target-state leaf, by its dict key in the layout
    :func:`init_states` builds: stacked-period entries ("p0", "p1", ...)
    carry [n_periods, B, ...]; everything else ("tailN", "length") is
    batch-leading. Single source of truth for code that indexes or
    repeats state rows (EngineState.adopt_row, StateReplayVerifier)."""
    return 1 if key.startswith("p") else 0


# --------------------------------------------------------------- forward ---
def forward(params, tokens, cfg: ModelConfig, *, states=None, cache_len=None,
            positions=None, write_kv: bool = False, extra_mask=None,
            ctx=None, attn_impl: str = "auto", kv_chunk: int = 1024,
            want_features: bool = False, want_logits: bool = True,
            remat: Optional[bool] = None, inputs_embeds=None, snap_at=None,
            attend_cache_on_write: bool = False):
    """tokens: [B,T] int32 (or ``inputs_embeds`` [B,T,d]).

    snap_at: [B] — replay-commit mode: states advance by exactly snap_at
    tokens per example (recurrent snapshots + dropped KV writes).
    Returns dict(logits, states, features, kv_outs, hidden).
    """
    spec, n_periods, tail = period_spec(cfg)
    dtype = jnp.dtype(cfg.dtype)
    if inputs_embeds is None:
        x = embed(params["tok"], tokens, dtype)
    else:
        x = inputs_embeds.astype(dtype)
    x = constrain(x, ("batch", "act_seq", "embed"))
    b, t = x.shape[:2]
    if states is not None and cache_len is None:
        cache_len = states["length"]
    if cache_len is None:
        cache_len = jnp.zeros((), jnp.int32)
    if positions is None:
        cl = jnp.asarray(cache_len)
        ar = jnp.arange(t, dtype=jnp.int32)
        positions = cl[:, None] + ar[None, :] if cl.ndim else cl + ar
    remat = cfg.remat if remat is None else remat

    def run_period(x, period_params, period_state):
        new_state = {}
        kv_outs = {}
        for j, bs in enumerate(spec):
            st = period_state.get(f"p{j}") if period_state else None
            x, ns, kv = block_apply(
                period_params[f"p{j}"], x, cfg, bs, state=st,
                cache_len=cache_len, positions=positions, write_kv=write_kv,
                extra_mask=extra_mask, ctx=ctx, attn_impl=attn_impl,
                kv_chunk=kv_chunk, snap_at=snap_at,
                attend_cache_on_write=attend_cache_on_write)
            if ns is not None:
                new_state[f"p{j}"] = ns
            kv_outs[f"p{j}"] = kv
        return x, new_state, kv_outs

    if remat:
        run_period = jax.checkpoint(
            run_period,
            policy=(jax.checkpoint_policies.checkpoint_dots
                    if cfg.remat_policy == "dots" else None))

    hiddens = []
    all_kv = {}
    new_states: Dict[str, Any] = {}

    if n_periods > 0:
        pparams = params["period"]
        pstates = ({k: states[k] for k in pparams} if states is not None
                   else None)

        def body(x, xs):
            pp, ps = xs
            x, ns, kv = run_period(x, pp, ps)
            return x, (ns, kv, x)

        if states is None:
            def body_nostate(x, pp):
                x, ns, kv = run_period(x, pp, None)
                return x, (kv, x)

            x, (kv_y, hid_y) = jax.lax.scan(body_nostate, x, pparams)
        else:
            x, (ns_y, kv_y, hid_y) = jax.lax.scan(body, x, (pparams, pstates))
            new_states.update(ns_y)
        all_kv["period"] = kv_y
        hiddens.append(hid_y)     # [n_periods, B, T, d]

    for i, bs in enumerate(tail):
        st = states.get(f"tail{i}") if states is not None else None
        x, ns, kv = block_apply(
            params[f"tail{i}"], x, cfg, bs, state=st, cache_len=cache_len,
            positions=positions, write_kv=write_kv, extra_mask=extra_mask,
            ctx=ctx, attn_impl=attn_impl, kv_chunk=kv_chunk, snap_at=snap_at,
                attend_cache_on_write=attend_cache_on_write)
        if ns is not None:
            new_states[f"tail{i}"] = ns
        all_kv[f"tail{i}"] = kv
        hiddens.append(x[None])

    x = rmsnorm(params["ln_f"], x, cfg.norm_eps)

    features = None
    if want_features:
        stacked = jnp.concatenate(hiddens, axis=0)   # [L', B, T, d]
        m = min(cfg_feature_layers(cfg), stacked.shape[0])
        feats = stacked[-m:]
        features = jnp.moveaxis(feats, 0, 2).reshape(b, t, m * cfg.d_model)

    logits = None
    if want_logits:
        head = (params["tok"]["embedding"].T if cfg.tie_embeddings
                else params["lm_head"])
        logits = unembed(head, x)
        logits = softcap(logits, cfg.logit_softcap)
        logits = constrain(logits, ("batch", None, "vocab"))

    if states is not None:
        out_states = dict(states)
        out_states.update(new_states)
        if write_kv:
            new_len = cache_len + (t if snap_at is None else snap_at)
            out_states["length"] = jnp.broadcast_to(
                new_len, states["length"].shape).astype(jnp.int32)
        result_states = out_states
    else:
        result_states = None

    return {"logits": logits, "states": result_states, "features": features,
            "kv_outs": all_kv, "hidden": x}


def cfg_feature_layers(cfg) -> int:
    return 3


def feature_dim(cfg: ModelConfig) -> int:
    """Width of the drafter-conditioning features ``forward`` emits:
    min(3, available period/tail hiddens) * d_model."""
    _, n_periods, tail = period_spec(cfg)
    avail = (n_periods if n_periods > 0 else 0) + len(tail)
    return min(cfg_feature_layers(cfg), max(avail, 1)) * cfg.d_model


# ------------------------------------------------------------ loss/train ---
def cross_entropy(logits, labels, mask=None, z_loss: float = 1e-4):
    """logits [B,T,V] (any float), labels [B,T] int32."""
    lf = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(lf, axis=-1)
    ll = jnp.take_along_axis(lf, labels[..., None], axis=-1)[..., 0]
    nll = lse - ll
    if z_loss:
        nll = nll + z_loss * jnp.square(lse)
    if mask is not None:
        nll = nll * mask
        denom = jnp.maximum(mask.sum(), 1.0)
    else:
        denom = nll.size
    return nll.sum() / denom


def loss_fn(params, batch, cfg: ModelConfig, *, attn_impl="auto",
            kv_chunk=1024, loss_seq_chunk: Optional[int] = None, ctx=None):
    """batch: dict(tokens [B,S], labels [B,S], mask [B,S])."""
    out = forward(params, batch["tokens"], cfg, attn_impl=attn_impl,
                  kv_chunk=kv_chunk, ctx=ctx,
                  want_logits=loss_seq_chunk is None)
    if loss_seq_chunk is None:
        return cross_entropy(out["logits"], batch["labels"],
                             batch.get("mask"))
    # chunked CE over sequence: never materialize [B,S,V] logits
    h = out["hidden"]
    head = (params["tok"]["embedding"].T if cfg.tie_embeddings
            else params["lm_head"])
    b, s, d = h.shape
    c = loss_seq_chunk
    assert s % c == 0
    hc = h.reshape(b, s // c, c, d).swapaxes(0, 1)
    lc = batch["labels"].reshape(b, s // c, c).swapaxes(0, 1)
    mc = (batch["mask"].reshape(b, s // c, c).swapaxes(0, 1)
          if batch.get("mask") is not None else
          jnp.ones((s // c, b, c), jnp.float32))

    def chunk_loss(carry, inp):
        hj, lj, mj = inp
        logits = softcap(unembed(head, hj), cfg.logit_softcap)
        lf = logits.astype(jnp.float32)
        lse = jax.scipy.special.logsumexp(lf, axis=-1)
        ll = jnp.take_along_axis(lf, lj[..., None], axis=-1)[..., 0]
        nll = (lse - ll + 1e-4 * jnp.square(lse)) * mj
        return (carry[0] + nll.sum(), carry[1] + mj.sum()), None

    (tot, cnt), _ = jax.lax.scan(
        jax.checkpoint(chunk_loss), (jnp.zeros(()), jnp.zeros(())),
        (hc, lc, mc))
    return tot / jnp.maximum(cnt, 1.0)


# -------------------------------------------------------------- KV commit --
def commit_kv(states, kv_outs, cfg: ModelConfig, path_idx, n_commit):
    """Write the accepted path's KV into the caches (per-example ragged).

    kv_outs: pytree from ``forward`` over T_tree tokens.
    path_idx: [B, P] int32 — per-example tree-node indices of the best path
        (P = max_depth+1 including the anchor at entry 0).
    n_commit: [B] int32 — tokens to commit per example (anchor + accepted =
        n_acc + 1). Entries beyond n_commit are NOT written (dropped), so
        rolling caches stay intact.
    """
    spec, n_periods, tail = period_spec(cfg)
    length = states["length"]                      # [B] (or scalar)
    length = jnp.asarray(length)
    b, p = path_idx.shape
    if length.ndim == 0:
        length = jnp.broadcast_to(length, (b,))
    valid = jnp.arange(p)[None, :] < n_commit[:, None]        # [B, P]
    new_states = dict(states)

    def write(state, kv, rolling):
        if kv is None:
            return state
        k, v = kv                                  # [(n,) B, T_tree, H, D]
        st = dict(state)
        paged = kvcache.is_paged(st)
        stacked = k.ndim == 5
        tree_ax = 2 if stacked else 1
        idx_g = path_idx
        if stacked:
            idx_g = jnp.broadcast_to(path_idx[None], (k.shape[0], b, p))
        k_path = jnp.take_along_axis(
            k, idx_g[..., None, None], axis=tree_ax)
        v_path = jnp.take_along_axis(
            v, idx_g[..., None, None], axis=tree_ax)
        # write positions: per-example length + 0..P-1 (mod cap if rolling);
        # invalid entries pushed out of bounds -> dropped by scatter
        wpos = length[:, None] + jnp.arange(p)[None, :]
        if paged:
            # page-wise commit: only the tail page(s) covering
            # [length, length+n_commit) are written; the page table is
            # untouched (allocation is fixed for the request's lifetime,
            # so masked rows trivially freeze their tables)
            st["k"] = kvcache.pool_scatter(st["k"], st["pt"], k_path, wpos,
                                           valid=valid)
            st["v"] = kvcache.pool_scatter(st["v"], st["pt"], v_path, wpos,
                                           valid=valid)
            return st
        cap = st["k"].shape[-3]
        if rolling:
            wpos = jnp.mod(wpos, cap)
        wpos = jnp.where(valid, wpos, cap + 1)
        bidx = jnp.broadcast_to(jnp.arange(b)[:, None], (b, p))
        if stacked:
            st["k"] = st["k"].at[:, bidx, wpos].set(
                k_path.astype(st["k"].dtype), mode="drop")
            st["v"] = st["v"].at[:, bidx, wpos].set(
                v_path.astype(st["v"].dtype), mode="drop")
        else:
            st["k"] = st["k"].at[bidx, wpos].set(
                k_path.astype(st["k"].dtype), mode="drop")
            st["v"] = st["v"].at[bidx, wpos].set(
                v_path.astype(st["v"].dtype), mode="drop")
        return st

    if n_periods > 0:
        kv_y = kv_outs.get("period", {})
        for j, bs in enumerate(spec):
            if bs.kind in ("global", "local") and kv_y.get(f"p{j}") is not None:
                new_states[f"p{j}"] = write(states[f"p{j}"], kv_y[f"p{j}"],
                                            rolling=(bs.kind == "local"))
    for i, bs in enumerate(tail):
        kv = kv_outs.get(f"tail{i}")
        if bs.kind in ("global", "local") and kv is not None:
            new_states[f"tail{i}"] = write(states[f"tail{i}"], kv,
                                           rolling=(bs.kind == "local"))
    new_states["length"] = length + n_commit
    return new_states
