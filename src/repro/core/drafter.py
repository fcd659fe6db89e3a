"""Block-diffusion drafters (DFlash first draft + VP-Drafter second draft).

Architecture (paper §2 "DFlash", §3.4): a lightweight transformer whose input
is a gamma-token block ([anchor, MASK, ..., MASK] for DFlash; [anchor,
prefix..., MASK...] for the VP-Drafter). Every layer's attention consumes

    K/V = [ W_k/v^l( proj(target multi-layer features) ) ;  W_k/v^l(block) ]

i.e. target hidden features are FC-projected once and *injected into the key
and value projections of every drafter layer* (the "KV injection"); mask
tokens attend bidirectionally within the block and to all injected context.

The projected per-layer context K/V are cached across decoding cycles (the
"feature cache", the drafter analogue of a KV cache) — one entry per
committed target position.

The same module runs the EAGLE-style autoregressive baseline by switching
``causal=True`` (chain drafting, one token per inner step).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import kvcache as kvc
from repro.models import param as pm
from repro.models.attention import attend
from repro.models.layers import apply_rope, dense, rmsnorm, rmsnorm_init
from repro.models.mlp import mlp, mlp_init
from repro.distributed.sharding import constrain


@dataclasses.dataclass(frozen=True)
class DrafterConfig:
    d_model: int = 256
    num_layers: int = 2
    num_heads: int = 4
    num_kv_heads: int = 4
    d_ff: int = 1024
    vocab_size: int = 512
    target_feature_dim: int = 768      # feature_layers * target d_model
    gamma: int = 16
    rope_theta: float = 10000.0
    dtype: str = "bfloat16"
    param_dtype: str = "float32"
    norm_eps: float = 1e-6
    causal: bool = False               # True => EAGLE-style AR drafter
    # Feature-cache read path, mirroring ModelConfig.attn_impl (jit-static
    # via SpecBundle aux_data): "pallas" reads paged feature pools through
    # the cascade kernel per layer instead of one dense pool_view gather.
    # kv_seq-sharded paged pools go through the shard_map read hook
    # (spdecode.sharded_paged_cache_attend) with read_impl=attn_impl —
    # each shard reads only its local pool slice either way, so sharded
    # engines draft without the per-cycle dense GSPMD gather. Dense
    # caches keep the plain gather/chunked path.
    attn_impl: str = "gather"

    def __post_init__(self):
        assert self.attn_impl in ("gather", "pallas"), (
            f"attn_impl={self.attn_impl!r} not in ('gather', 'pallas')")

    @property
    def head_dim(self) -> int:
        return self.d_model // self.num_heads

    @property
    def mask_token(self) -> int:
        return self.vocab_size         # embedding table has vocab+1 rows


@functools.partial(jax.jit, static_argnums=1)
def drafter_init(key, dcfg: DrafterConfig):
    """Parameters in ``dcfg.param_dtype``, drawn in float32 and cast down
    inside this jit (see ``lm.lm_init``)."""
    ks = pm.split(key, 4 + dcfg.num_layers)
    hq, hkv, dh = dcfg.num_heads, dcfg.num_kv_heads, dcfg.head_dim
    d = dcfg.d_model
    p = {
        "tok": {"embedding": pm.trunc_normal(
            ks[0], (dcfg.vocab_size + 1, d), stddev=0.02)},
        "feat_proj": pm.dense_init(ks[1], dcfg.target_feature_dim, d),
        "ln_f": rmsnorm_init(d),
        "head": pm.dense_init(ks[2], d, dcfg.vocab_size, scale=0.02),
    }
    for i in range(dcfg.num_layers):
        kk = pm.split(ks[4 + i], 6)
        p[f"layer{i}"] = {
            "ln1": rmsnorm_init(d),
            "wq": pm.dense_init(kk[0], d, hq * dh),
            "wk": pm.dense_init(kk[1], d, hkv * dh),
            "wv": pm.dense_init(kk[2], d, hkv * dh),
            "wo": pm.dense_init(kk[3], hq * dh, d, scale=(hq * dh) ** -0.5),
            "ln2": rmsnorm_init(d),
            "mlp": mlp_init(kk[4], d, dcfg.d_ff, gated=True),
        }
    return pm.cast_floating(p, dcfg.param_dtype)


# ----------------------------------------------------------- feature cache --
def init_feat_cache(dcfg: DrafterConfig, batch: int, max_len: int,
                    dtype=jnp.bfloat16, cache_impl: str = "dense",
                    page_size: int = 64, pool_pages=None, page_table=None,
                    ext_pool=None):
    """Dense: k/v [L, B, S_max, Hkv, Dh]. Paged: stacked page pools
    [L, P, page, Hkv, Dh] plus the wave's shared page table ``pt``
    [B, max_pages] (same page-id space as the target KV pools, so one
    host allocation covers every cache of a row). ``ext_pool`` (paged):
    retained ``(k, v)`` device buffers from a previous wave adopted in
    place of fresh zeroed pools (borrowed-pool wave turnover)."""
    l, hkv, dh = dcfg.num_layers, dcfg.num_kv_heads, dcfg.head_dim
    if cache_impl == "paged":
        pool_pages, page_table = kvc.default_page_layout(
            batch, max_len, page_size, pool_pages, page_table)
        if ext_pool is not None:
            k, v = ext_pool
            assert k.shape == (l, pool_pages, page_size, hkv, dh) \
                and k.dtype == dtype, ("retained feature-pool geometry "
                                       "mismatch", k.shape)
        else:
            k = kvc.init_pool(pool_pages, page_size, hkv, dh, dtype,
                              lead=(l,))
            v = kvc.init_pool(pool_pages, page_size, hkv, dh, dtype,
                              lead=(l,))
        return {
            "k": k,
            "v": v,
            # copy=True: every paged cache holds its own table buffer so
            # the whole state can be donated (no twice-donated aliases)
            "pt": jnp.array(page_table, jnp.int32, copy=True),
            "length": jnp.zeros((batch,), jnp.int32),
        }
    return {
        "k": jnp.zeros((l, batch, max_len, hkv, dh), dtype),
        "v": jnp.zeros((l, batch, max_len, hkv, dh), dtype),
        "length": jnp.zeros((batch,), jnp.int32),
    }


def project_features(p, dcfg: DrafterConfig, target_features, positions):
    """target_features: [B,T,Fd]; positions: [B,T] absolute.

    Returns per-layer context (k, v): ([L,B,T,Hkv,Dh], [L,B,T,Hkv,Dh]).
    """
    b, t, _ = target_features.shape
    hkv, dh = dcfg.num_kv_heads, dcfg.head_dim
    f = dense(p["feat_proj"], target_features.astype(jnp.dtype(dcfg.dtype)))
    ks, vs = [], []
    for i in range(dcfg.num_layers):
        lp = p[f"layer{i}"]
        k = dense(lp["wk"], f).reshape(b, t, hkv, dh)
        v = dense(lp["wv"], f).reshape(b, t, hkv, dh)
        k = apply_rope(k, positions, dcfg.rope_theta)
        ks.append(k)
        vs.append(v)
    return jnp.stack(ks), jnp.stack(vs)


def extend_feat_cache(p, dcfg, cache, target_features, positions, n_new):
    """Append features of newly committed tokens (per-example ragged).

    target_features: [B,P,Fd] gathered along the accepted path (padded);
    positions: [B,P] their absolute positions; n_new: [B] valid counts.
    """
    k_new, v_new = project_features(p, dcfg, target_features, positions)
    b, pl = positions.shape
    valid = jnp.arange(pl)[None, :] < n_new[:, None]
    out = dict(cache)
    if kvc.is_paged(cache):
        out["k"] = kvc.pool_scatter(cache["k"], cache["pt"], k_new,
                                    positions, valid=valid)
        out["v"] = kvc.pool_scatter(cache["v"], cache["pt"], v_new,
                                    positions, valid=valid)
    else:
        cap = cache["k"].shape[2]
        wpos = jnp.where(valid, positions, cap + 1)
        bidx = jnp.broadcast_to(jnp.arange(b)[:, None], (b, pl))
        out["k"] = cache["k"].at[:, bidx, wpos].set(
            k_new.astype(cache["k"].dtype), mode="drop")
        out["v"] = cache["v"].at[:, bidx, wpos].set(
            v_new.astype(cache["v"].dtype), mode="drop")
    out["length"] = cache["length"] + n_new
    return out


# ----------------------------------------------------------------- forward --
def drafter_forward(p, dcfg: DrafterConfig, block_tokens, feat_cache,
                    positions=None, block_mask=None, attn_impl: str = "auto",
                    kv_chunk: int = 1024):
    """block_tokens: [B,T] (mask token = dcfg.mask_token).

    positions: [B,T] absolute positions of block slots (default: feat_len+i).
    block_mask: optional [T,T] or [B,T,T] intra-block mask; default
        bidirectional (diffusion) or causal when dcfg.causal.
    Returns logits [B,T,V].
    """
    b, t = block_tokens.shape
    dtype = jnp.dtype(dcfg.dtype)
    hq, hkv, dh = dcfg.num_heads, dcfg.num_kv_heads, dcfg.head_dim
    feat_len = feat_cache["length"]
    if positions is None:
        positions = feat_len[:, None] + jnp.arange(t)[None, :]
    x = p["tok"]["embedding"].astype(dtype)[block_tokens]
    x = constrain(x, ("batch", None, "embed"))

    if block_mask is None and dcfg.causal:
        block_mask = jnp.tril(jnp.ones((t, t), dtype=bool))
    elif block_mask is None:
        block_mask = jnp.ones((t, t), dtype=bool)

    from repro.distributed import spdecode as _sp
    paged = kvc.is_paged(feat_cache)
    axis = _sp.kv_seq_axis()
    # Kernelized paged read (dcfg.attn_impl, jit-static): every layer calls
    # the cascade kernel on its pool slice + the shared page table — no
    # dense-sized pool_view gather per cycle. Block slots sit at positions
    # >= feat_len, so the kernel's causal kpos<=q_abs clamp is subsumed by
    # its kpos<feat_len mask and both paths attend identically.
    use_pallas = (paged and dcfg.attn_impl == "pallas" and axis is None)
    # kv_seq-sharded feature pools: the same shard_map hook the verify
    # read uses (per-shard local pool reads — gather of the LOCAL slice or
    # the pos_stride/pos_offset kernel — merged by the fp32 LSE psum), so
    # sharded engines draft without a per-cycle dense GSPMD gather.
    use_sharded = (paged and axis is not None
                   and feat_cache["k"].shape[-3] % _sp.kv_seq_shards() == 0)
    if paged and not (use_pallas or use_sharded):
        # logical per-row view gathered once for all drafter layers;
        # garbage beyond feat_len is masked below exactly like the dense
        # cache's zero padding, so both layouts attend identically
        ctx_k = kvc.pool_view(feat_cache["k"], feat_cache["pt"])
        ctx_v = kvc.pool_view(feat_cache["v"], feat_cache["pt"])
    elif paged:
        ctx_k, ctx_v = feat_cache["k"], feat_cache["v"]   # [L,P,page,Hkv,Dh]
    else:
        ctx_k, ctx_v = feat_cache["k"], feat_cache["v"]
    cap = (kvc.logical_len(feat_cache) if (use_pallas or use_sharded)
           else ctx_k.shape[2])
    tq = t
    if block_mask.ndim == 2:
        blk = jnp.broadcast_to(block_mask[None], (b, tq, t))
    else:
        blk = block_mask
    full_mask = None
    if not (use_pallas or use_sharded):
        # context visibility: feature entries < feat_len (per-example)
        ctx_ok = (jnp.arange(cap)[None, None, :]
                  < feat_len[:, None, None])                 # [B,1,cap]
        ctx_ok = jnp.broadcast_to(ctx_ok, (b, tq, cap))
        full_mask = jnp.concatenate([ctx_ok, blk], axis=-1)

    spdecode = _sp
    use_sp = False
    if axis is not None and not paged:
        from repro.distributed.sharding import active_mesh
        n_shards = dict(zip(active_mesh().axis_names,
                            active_mesh().devices.shape))[axis]
        use_sp = cap % n_shards == 0 and cap // n_shards >= 128

    for i in range(dcfg.num_layers):
        lp = p[f"layer{i}"]
        h = rmsnorm(lp["ln1"], x, dcfg.norm_eps)
        q = dense(lp["wq"], h).reshape(b, t, hq, dh)
        k = dense(lp["wk"], h).reshape(b, t, hkv, dh)
        v = dense(lp["wv"], h).reshape(b, t, hkv, dh)
        q = apply_rope(q, positions, dcfg.rope_theta)
        k = apply_rope(k, positions, dcfg.rope_theta)
        if use_pallas:
            from repro.kernels import ops as kops
            y = kops.cascade_attention_paged(
                q, ctx_k[i].astype(k.dtype), ctx_v[i].astype(v.dtype),
                feat_cache["pt"], k, v, cache_len=feat_len,
                q_abs=positions, tree_mask=blk, layout="BTHD")
        elif use_sharded:
            y = spdecode.sharded_paged_cache_attend(
                q, ctx_k[i].astype(k.dtype), ctx_v[i].astype(v.dtype),
                feat_cache["pt"], k, v, cache_len=feat_len,
                q_abs=positions, attn_softcap=None, blk_mask=blk,
                page_size=feat_cache["k"].shape[-3], kv_chunk=kv_chunk,
                read_impl=dcfg.attn_impl)
        elif use_sp:
            y = spdecode.sharded_cache_attend(
                q, ctx_k[i].astype(k.dtype),
                ctx_v[i].astype(v.dtype), k, v,
                cache_len=feat_len, q_abs=positions, window=None,
                attn_softcap=None, blk_mask=blk, rolling=False,
                kv_chunk=kv_chunk)
        else:
            kk = jnp.concatenate([ctx_k[i].astype(k.dtype), k], axis=1)
            vv = jnp.concatenate([ctx_v[i].astype(v.dtype), v], axis=1)
            y = attend(q, kk, vv, causal=False, extra_mask=full_mask,
                       impl=attn_impl, kv_chunk=kv_chunk)
        x = x + dense(lp["wo"], y.reshape(b, t, hq * dh))
        h = rmsnorm(lp["ln2"], x, dcfg.norm_eps)
        x = x + mlp(lp["mlp"], h)
    x = rmsnorm(p["ln_f"], x, dcfg.norm_eps)
    return dense(p["head"], x)


def dflash_block(anchor, gamma: int, mask_token: int):
    """[B] -> [B, gamma]: [anchor, MASK, ..., MASK]."""
    b = anchor.shape[0]
    blk = jnp.full((b, gamma), mask_token, jnp.int32)
    return blk.at[:, 0].set(anchor)


def vp_blocks(anchor, trunk_tokens, fork_idx, mask_token: int):
    """Second-draft inputs (paper step iii).

    anchor: [B]; trunk_tokens: [B, gamma-1] (or per-branch [B, K, gamma-1]
    for third-level drafts); fork_idx: [B, K].
    Returns [B, K, gamma]: branch b keeps anchor + first fork_b prefix tokens
    visible and re-masks the rest.
    """
    k = fork_idx.shape[1]
    g1 = trunk_tokens.shape[-1]
    slots = jnp.arange(g1 + 1)[None, None, :]             # [1,1,gamma]
    if trunk_tokens.ndim == 2:
        trunk_tokens = jnp.broadcast_to(
            trunk_tokens[:, None, :], (trunk_tokens.shape[0], k, g1))
    b = trunk_tokens.shape[0]
    full = jnp.concatenate(
        [jnp.broadcast_to(anchor[:, None, None], (b, k, 1)), trunk_tokens],
        axis=2)                                            # [B,K,gamma]
    visible = slots <= fork_idx[:, :, None]               # anchor + prefix
    return jnp.where(visible, full, mask_token).astype(jnp.int32)


def ar_chain_draft(p, dcfg: DrafterConfig, anchor, feat_cache, steps: int,
                   temperature: float = 0.0, key=None):
    """EAGLE-style baseline: draft ``steps`` tokens autoregressively.

    Runs ``steps`` causal forwards over the growing block (small gamma, so
    recompute beats cache bookkeeping). Returns (tokens [B,steps],
    logits [B,steps,V]).
    """
    b = anchor.shape[0]
    g = steps + 1
    blk = jnp.full((b, g), 0, jnp.int32).at[:, 0].set(anchor)

    def step(carry, i):
        blk, key = carry
        logits = drafter_forward(p, dcfg, blk, feat_cache,
                                 block_mask=jnp.tril(jnp.ones((g, g), bool)))
        li = logits[jnp.arange(b), i]                     # [B,V]
        if temperature > 0:
            key, sub = jax.random.split(key)
            tok = jax.random.categorical(sub, li / temperature)
        else:
            tok = jnp.argmax(li, axis=-1)
        blk = blk.at[:, i + 1].set(tok.astype(jnp.int32))
        return (blk, key), li

    key = key if key is not None else jax.random.PRNGKey(0)
    (blk, _), logit_seq = jax.lax.scan(step, (blk, key), jnp.arange(steps))
    logits = jnp.moveaxis(logit_seq, 0, 1)                # [B,steps,V]
    return blk[:, 1:], logits
