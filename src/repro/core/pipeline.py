"""D2SD decode engine: strategy/backend composition + generation loops.

Architecture (post API-redesign)
--------------------------------
One decode cycle is the composition of three pluggable pieces over a typed
:class:`~repro.core.state.EngineState` pytree:

1. **DraftStrategy** (``core/strategies.py``) — registry-dispatched on
   ``SpecConfig.mode``; turns ``(bundle, state, key)`` into a candidate
   :class:`~repro.core.tree.Tree` plus per-node proposal distributions.
   The paper modes (d2sd / dflash / naive_k / dflash_second / eagle,
   §3.3 + Tables 5-7) are the built-in registrations; a new drafter
   variant registers a class and needs no engine change.
2. **VerifierBackend** (``core/verify.py``) — selected from target
   ``ModelConfig`` capabilities: cascade tree-attention verify for
   pure-attention targets, branch-batched state-replay verify for
   SSM/hybrid targets (DESIGN §5.1).
3. **Commit** — :func:`decode_cycle` itself only wires draft -> verify ->
   feature-cache extension and emits the accepted tokens.

Generation loops: :func:`generate` is the legacy host loop (numpy sync per
cycle, per-example ragged copy-out, calibration stats);
:func:`generate_ondevice` runs the *entire* loop inside a single
``jax.lax.while_loop`` with a padded on-device output buffer — no host
round-trip per cycle — and is the serving fast path. Both produce
token-identical output for the same keys.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.config.base import ModelConfig, SpecConfig
from repro.core import strategies as strat_lib
from repro.core import verify as verify_lib
from repro.core.state import EngineState, engine_init, prefill  # noqa: F401
from repro.core.verify import uses_tree_attention  # noqa: F401 (back-compat)
from repro.core import drafter as dr


@dataclasses.dataclass(frozen=True)
class SpecBundle:
    target_cfg: ModelConfig
    d1_cfg: dr.DrafterConfig
    d2_cfg: dr.DrafterConfig
    spec: SpecConfig
    target_params: Any
    d1_params: Any
    d2_params: Any


jax.tree_util.register_pytree_node(
    SpecBundle,
    lambda s: ((s.target_params, s.d1_params, s.d2_params),
               (s.target_cfg, s.d1_cfg, s.d2_cfg, s.spec)),
    lambda aux, ch: SpecBundle(aux[0], aux[1], aux[2], aux[3], *ch),
)


def with_attn_impl(bundle: SpecBundle, impl: str) -> SpecBundle:
    """Bundle with the KV/feature-cache read path set to ``impl``
    ("gather" | "pallas") on the target AND both drafters.

    Configs live in SpecBundle aux_data, so the returned bundle is a
    distinct jit-cache key — every decode trace retraces with the selected
    read path (``ModelConfig.attn_impl`` / ``DrafterConfig.attn_impl``).
    Token-identical by construction; used by benches/tests for A/B.
    """
    return SpecBundle(
        dataclasses.replace(bundle.target_cfg, attn_impl=impl),
        dataclasses.replace(bundle.d1_cfg, attn_impl=impl),
        dataclasses.replace(bundle.d2_cfg, attn_impl=impl),
        bundle.spec, bundle.target_params, bundle.d1_params,
        bundle.d2_params)


# -------------------------------------------------------------- the cycle --
def decode_cycle(bundle: SpecBundle, state: EngineState, key,
                 collect_stats: bool = True, shard_tag=None):
    """One full speculative decoding cycle.

    ``shard_tag`` (static, ``sharding.mesh_tag()``): cache-splitter only —
    under an active mesh the trace differs (sharding constraints + the
    shard_map cascade-verify hook in ``models/blocks.py``), which jit's
    aval-keyed cache cannot see; the serving engine passes its captured
    tag so sharded and single-device engines coexist in one process.

    Rows with ``state.active == False`` are masked end to end: their draft
    tree degenerates to the root, the verifier commits zero tokens (no KV
    or feature-cache writes, length frozen), the anchor is carried over
    unchanged, and ``n_out`` is 0. The batched draft/verify FLOPs still
    run for masked rows (static shapes) — the win is that a finished
    request parks in its slot with zero state mutation, so the slot can
    be re-prefilled in place and stats stay clean.

    Returns (state', out) with out = dict(tokens [B, D+1], n_out [B],
    n_acc [B], plus calibration stats when collect_stats).
    """
    strategy = strat_lib.get_strategy(bundle.spec.mode)
    backend = verify_lib.select_backend(bundle.target_cfg)
    k_draft, k_verify = jax.random.split(key)
    active = state.active

    draft = strategy.draft(bundle, state, k_draft)
    # inactive rows (finished requests / idle serving slots) degenerate to
    # a root-only tree: nothing is accepted, nothing is committed below
    draft = strat_lib.mask_inactive(draft, active)
    with jax.named_scope("d2sd.verify"):
        vo = backend.verify(bundle, state, draft.tree, draft.dprobs,
                            draft.max_children, k_verify)
    with jax.named_scope("d2sd.commit"):
        return _commit(bundle, state, draft, vo, collect_stats)


def _commit(bundle, state, draft, vo, collect_stats):
    """Feature-cache extension and output assembly of :func:`decode_cycle`
    after the verify."""
    active = state.active
    res = vo.res
    tree = draft.tree

    # ---------------- feature-cache extension ----------------
    n_acc = jnp.where(active, res["n_acc"], 0)
    n_commit = jnp.where(active, res["n_acc"] + 1, 0)
    fpos = (state.length[:, None]
            + jnp.arange(res["path"].shape[1])[None, :])
    state2 = state.replace(
        target=vo.target,
        d1_feat=dr.extend_feat_cache(
            bundle.d1_params, bundle.d1_cfg, state.d1_feat, vo.path_feats,
            fpos, n_commit),
        d2_feat=dr.extend_feat_cache(
            bundle.d2_params, bundle.d2_cfg, state.d2_feat, vo.path_feats,
            fpos, n_commit),
        anchor=jnp.where(active, res["bonus"],
                         state.anchor).astype(jnp.int32))

    # ---------------- outputs ----------------
    path_tokens = jnp.take_along_axis(tree.tokens, res["path"], axis=1)
    d_idx = jnp.arange(res["path"].shape[1])[None, :]
    out_tok = jnp.where(d_idx < n_acc[:, None],
                        jnp.roll(path_tokens, -1, axis=1), 0)
    # slot d: accepted draft d+1 => path_tokens[d+1]; slot n_acc: bonus
    out_tok = jnp.where((d_idx == n_acc[:, None]) & active[:, None],
                        res["bonus"][:, None], out_tok)
    out = {"tokens": out_tok, "n_out": n_commit, "n_acc": n_acc}
    if collect_stats and draft.conf is not None:
        # calibration: trunk confidences vs trunk-node acceptance (greedy ok)
        g = bundle.spec.gamma
        trunk_ok = (res["ok"][:, 1:g] if res.get("ok") is not None else None)
        out["conf"] = draft.conf
        out["trunk_ok"] = trunk_ok
    return state2, out


# -------------------------------------------------------------- generate ---
# Module-level jit: SpecBundle's aux (configs) is hashable, so repeated
# generate() calls with the same shapes hit the trace cache instead of
# re-tracing a fresh closure per call.
_cycle_jit = functools.partial(
    jax.jit, static_argnames=("collect_stats", "shard_tag"))(decode_cycle)


def generate(bundle: SpecBundle, prompts, max_new: int, key=None, ctx=None,
             max_len: Optional[int] = None, collect_stats: bool = True,
             early_exit: bool = True, cache_impl: str = "dense",
             page_size: int = 64):
    """Generate up to ``max_new`` tokens for prompts [B, P] (host loop over
    jitted cycles). Returns dict(tokens [B, max_new], n_cycles, alpha, stats).

    early_exit: mask rows that already reached ``max_new`` so they stop
    committing tokens / mutating caches (per-example ``EngineState.active``);
    token output is identical either way — only finished rows' wasted
    commits (and their dilution of ``alpha``) change.

    cache_impl: "dense" | "paged" KV storage (identity page layout here —
    the serving engine owns real page allocation). Token output is
    identical across impls: the paged logical view matches the dense cache
    at every committed position and garbage beyond it is masked the same.

    Back-compat wrapper: use :func:`generate_ondevice` when you do not need
    per-cycle calibration stats — it avoids the per-cycle host sync.
    """
    import numpy as np

    b, p = prompts.shape
    g = bundle.spec.gamma
    key = key if key is not None else jax.random.PRNGKey(0)
    max_len = max_len or (p + max_new + 2 * g + 8)
    state = engine_init(bundle, b, max_len, cache_impl=cache_impl,
                        page_size=page_size)
    kpre, key = jax.random.split(key)
    state = prefill(bundle, state, prompts, key=kpre, ctx=ctx,
                    temperature=bundle.spec.temperature)
    first = np.asarray(state.anchor)

    from repro.distributed import sharding as sh_lib

    def cycle(s, k):
        return _cycle_jit(bundle, s, k, collect_stats=collect_stats,
                          shard_tag=sh_lib.mesh_tag())

    out_buf = np.zeros((b, max_new + g + 1), np.int32)
    out_buf[:, 0] = first
    filled = np.ones((b,), np.int64)
    n_cycles = 0
    act_cycles = 0
    stats = {"n_acc": [], "n_out": [], "conf": [], "trunk_ok": []}
    while filled.min() < max_new:
        below = filled < max_new
        act_cycles += int(below.sum()) if early_exit else b
        if early_exit:
            state = state.replace(active=jnp.asarray(below))
        key, sub = jax.random.split(key)
        state, out = cycle(state, sub)
        toks = np.asarray(out["tokens"])
        n_out = np.asarray(out["n_out"])
        for i in range(b):
            m = min(int(n_out[i]), out_buf.shape[1] - int(filled[i]))
            if m > 0:
                out_buf[i, filled[i]: filled[i] + m] = toks[i, :m]
        filled = np.minimum(filled + n_out, out_buf.shape[1])
        n_cycles += 1
        stats["n_acc"].append(np.asarray(out["n_acc"]))
        stats["n_out"].append(n_out)
        if collect_stats and "conf" in out:
            # calibration rows only for rows that were still generating:
            # a masked row's tree is invalidated, so its trunk_ok would be
            # forced-False against a real conf and skew the curve
            conf = np.asarray(out["conf"])
            stats["conf"].append(conf[below] if early_exit else conf)
            if out["trunk_ok"] is not None:
                tok = np.asarray(out["trunk_ok"])
                stats["trunk_ok"].append(tok[below] if early_exit else tok)
        if n_cycles > max_new + 8:
            break
    # alpha over rows that were still generating (masked rows commit 0 and
    # are excluded from the denominator; without early_exit this reduces to
    # the legacy mean over all row-cycles)
    alpha = (float(np.concatenate(stats["n_out"]).sum()) / act_cycles
             if act_cycles else 0.0)
    return {"tokens": out_buf[:, :max_new], "n_cycles": n_cycles,
            "alpha": alpha, "stats": stats}


@functools.partial(jax.jit,
                   static_argnames=("max_new", "max_len", "early_exit",
                                    "cache_impl", "page_size", "shard_tag"))
def _ondevice_loop(bundle: SpecBundle, prompts, key, max_new: int,
                   max_len: int, early_exit: bool = True,
                   cache_impl: str = "dense", page_size: int = 64,
                   shard_tag=None):
    """Prefill + full decode loop inside one ``lax.while_loop``.

    With ``early_exit`` the per-example ``EngineState.active`` mask is
    refreshed from ``filled < max_new`` every iteration: finished rows
    draft a degenerate root-only tree, commit nothing, and skip every
    KV / feature-cache write while the ``cond`` stays shape-stable.

    Returns (buf [B, max_new+g+1], n_cycles [], total_out [],
    act_row_cycles []) — all on device; the caller slices / casts.
    """
    b, _ = prompts.shape
    cap = buf_width = max_new + bundle.spec.gamma + 1
    cycle_cap = max_new + 9          # mirrors the host loop's bailout

    state = engine_init(bundle, b, max_len, cache_impl=cache_impl,
                        page_size=page_size)
    kpre, key = jax.random.split(key)
    state = prefill(bundle, state, prompts, key=kpre,
                    temperature=bundle.spec.temperature)
    buf = jnp.zeros((b, buf_width), jnp.int32).at[:, 0].set(state.anchor)
    filled = jnp.ones((b,), jnp.int32)

    def cond(carry):
        _, _, filled, _, n_cycles, _, _ = carry
        return (filled.min() < max_new) & (n_cycles < cycle_cap)

    def body(carry):
        state, buf, filled, key, n_cycles, total, act = carry
        below = filled < max_new
        if early_exit:
            state = state.replace(active=below)
        act = act + (below.sum(dtype=jnp.int32) if early_exit
                     else jnp.int32(b))
        key, sub = jax.random.split(key)
        state, out = decode_cycle(bundle, state, sub, collect_stats=False)
        t = out["tokens"].shape[1]
        idx = filled[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]
        valid = jnp.arange(t)[None, :] < out["n_out"][:, None]
        # out-of-budget / invalid slots scatter to index cap -> dropped
        wpos = jnp.where(valid, jnp.minimum(idx, cap), cap)
        bidx = jnp.broadcast_to(jnp.arange(b)[:, None], (b, t))
        buf = buf.at[bidx, wpos].set(out["tokens"], mode="drop")
        filled = jnp.minimum(filled + out["n_out"], buf_width)
        return (state, buf, filled, key, n_cycles + 1,
                total + out["n_out"].sum(), act)

    carry = (state, buf, filled, key, jnp.zeros((), jnp.int32),
             jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32))
    _, buf, _, _, n_cycles, total, act = jax.lax.while_loop(cond, body,
                                                            carry)
    return buf, n_cycles, total, act


def generate_ondevice(bundle: SpecBundle, prompts, max_new: int, key=None,
                      max_len: Optional[int] = None,
                      early_exit: bool = True, cache_impl: str = "dense",
                      page_size: int = 64):
    """On-device generation: the whole decode loop runs inside a single
    ``jax.lax.while_loop`` with a padded output buffer — zero host syncs
    between cycles. Token-identical to :func:`generate` for the same key
    (same prefill/cycle key schedule, same commit rule); calibration stats
    are not collected on this path.

    early_exit: per-example masking of finished rows inside the loop (see
    :func:`_ondevice_loop`). Token output is identical with or without it
    for the same key; ``alpha`` is reported over active row-cycles only.

    Returns dict(tokens [B, max_new] device array, n_cycles, alpha).
    """
    b, p = prompts.shape
    g = bundle.spec.gamma
    key = key if key is not None else jax.random.PRNGKey(0)
    max_len = max_len or (p + max_new + 2 * g + 8)
    from repro.distributed import sharding as sh_lib
    buf, n_cycles, total, act = _ondevice_loop(bundle, prompts, key,
                                               max_new, max_len,
                                               early_exit=early_exit,
                                               cache_impl=cache_impl,
                                               page_size=page_size,
                                               shard_tag=sh_lib.mesh_tag())
    n = int(n_cycles)
    act = int(act)
    alpha = float(total) / act if act else 0.0
    return {"tokens": buf[:, :max_new], "n_cycles": n, "alpha": alpha}
