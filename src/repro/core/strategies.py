"""Pluggable draft strategies (paper §3.3 modes as registry entries).

A :class:`DraftStrategy` turns ``(bundle, state, key)`` into a
:class:`DraftResult` — a candidate :class:`~repro.core.tree.Tree` plus the
per-node proposal distributions the verifier needs for lossless sampling.
Each paper mode is one registered class; ``decode_cycle`` dispatches on
``SpecConfig.mode`` through :func:`get_strategy` with no branching of its
own, so a new drafter variant is a one-file plugin:

    @register_strategy("my_mode")
    class MyStrategy(DraftStrategy):
        def draft(self, bundle, state, key):
            ...
            return DraftResult(tree=tree, dprobs=q, conf=conf,
                               max_children=2)

Strategies also expose static cost metadata (``n_draft_passes`` /
``n_tree_nodes``) used by the roofline speedup model in benchmarks.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Type

import jax
import jax.numpy as jnp

from repro.config.base import SpecConfig
from repro.core import confidence as conf_lib
from repro.core import drafter as dr
from repro.core import tree as tree_lib
from repro.core.state import EngineState


@dataclasses.dataclass(frozen=True)
class DraftResult:
    """Output of one draft phase.

    tree:         candidate prefix tree rooted at the anchor.
    dprobs:       [B, N, V] per-node proposal categoricals q_n for sampling
                  verification (None under greedy decoding, temp == 0).
    conf:         [B, gamma-1] trunk confidences (Eq. 3) for calibration
                  stats; None for strategies without a diffusion trunk.
    max_children: static sibling bound for the verifier's child scan.
    """
    tree: tree_lib.Tree
    dprobs: Optional[jnp.ndarray]
    conf: Optional[jnp.ndarray]
    max_children: int


class DraftStrategy:
    """Protocol for draft-phase plugins. Subclass and register by name."""

    name: str = "?"

    def draft(self, bundle, state: EngineState, key) -> DraftResult:
        raise NotImplementedError

    # ---- static cost metadata (roofline model, benchmarks/common.py) ----
    def n_draft_passes(self, spec: SpecConfig) -> int:
        raise NotImplementedError

    def n_tree_nodes(self, spec: SpecConfig) -> int:
        raise NotImplementedError


_REGISTRY: Dict[str, Type[DraftStrategy]] = {}


def register_strategy(name: str):
    """Class decorator: ``@register_strategy("d2sd")``."""
    def deco(cls: Type[DraftStrategy]) -> Type[DraftStrategy]:
        # First registration names the class; aliases must not rename it
        # (strategy.name feeds logging/metrics).
        if cls.__dict__.get("name", "?") == "?":
            cls.name = name
        _REGISTRY[name] = cls
        return cls
    return deco


def get_strategy(name: str) -> DraftStrategy:
    try:
        return _REGISTRY[name]()
    except KeyError:
        raise KeyError(
            f"unknown draft strategy {name!r}; registered: "
            f"{sorted(_REGISTRY)}") from None


def registered_strategies() -> Dict[str, Type[DraftStrategy]]:
    return dict(_REGISTRY)


def mask_inactive(result: DraftResult, active) -> DraftResult:
    """Degenerate inactive rows' candidate trees to the root-only node.

    active: [B] bool. For rows with ``active=False`` every non-root node is
    invalidated (token zeroed, valid=False), so verification accepts
    nothing, the best path stays at the anchor, and the commit for that
    row is fully masked upstream (``decode_cycle`` zeroes ``n_out`` and
    keeps the anchor). Shape-stable: the node table keeps its static size,
    which is what lets the mask cross ``jit`` / ``while_loop`` boundaries.
    """
    t = result.tree
    keep = active[:, None] | (jnp.arange(t.n) == 0)[None, :]
    tree = tree_lib.Tree(tokens=jnp.where(keep, t.tokens, 0),
                         parent=t.parent, depth=t.depth,
                         valid=t.valid & keep, max_depth=t.max_depth)
    return dataclasses.replace(result, tree=tree)


# ----------------------------------------------------- shared draft steps --
@jax.named_scope("d2sd.draft1")
def first_draft(bundle, state: EngineState, key, temperature):
    """DFlash pass: returns (trunk [B,g-1], d1_logits [B,g,V])."""
    g = bundle.spec.gamma
    blk = dr.dflash_block(state.anchor, g, bundle.d1_cfg.mask_token)
    logits = dr.drafter_forward(bundle.d1_params, bundle.d1_cfg, blk,
                                state.d1_feat)
    if temperature > 0:
        trunk = jax.random.categorical(
            key, logits[:, 1:].astype(jnp.float32) / temperature)
    else:
        trunk = jnp.argmax(logits[:, 1:], axis=-1)
    return trunk.astype(jnp.int32), logits


def second_draft(params, dcfg, feat_cache, anchor, trunk, fork_idx, key,
                 temperature, feat_len):
    """VP pass, K branches in one forward via sequence-axis concatenation.

    Returns (branch_tokens [B,K,g-1], d2_logits [B,K,g,V]).
    """
    b, k = fork_idx.shape
    g = trunk.shape[-1] + 1
    vp_in = dr.vp_blocks(anchor, trunk, fork_idx, dcfg.mask_token)  # [B,K,g]
    flat = vp_in.reshape(b, k * g)
    # block-diagonal bidirectional mask (branches blind to each other)
    eye = jnp.eye(k, dtype=bool)
    bmask = jnp.repeat(jnp.repeat(eye, g, 0), g, 1)                 # [Kg,Kg]
    slots = jnp.tile(jnp.arange(g), k)[None, :]                     # [1,Kg]
    positions = feat_len[:, None] + slots
    logits = dr.drafter_forward(params, dcfg, flat, feat_cache,
                                positions=positions, block_mask=bmask)
    logits = logits.reshape(b, k, g, -1)
    if temperature > 0:
        toks = jax.random.categorical(
            key, logits[:, :, 1:].astype(jnp.float32) / temperature)
    else:
        toks = jnp.argmax(logits[:, :, 1:], axis=-1)
    return toks.astype(jnp.int32), logits


def _splice(trunk, branch_tokens, fork_idx):
    """Per-branch completed block: trunk up to fork, branch tokens after.

    trunk [B,g-1], branch_tokens [B,K,g-1], fork_idx [B,K] -> [B,K,g-1]
    flattened to the 'trunk' argument shape expected by vp_blocks per branch.
    Used only to build third-level visible prefixes.
    """
    slot = jnp.arange(1, trunk.shape[1] + 1)[None, None, :]
    use_trunk = slot <= fork_idx[:, :, None]
    return jnp.where(use_trunk, trunk[:, None, :], branch_tokens)


def comb_draft_probs(tree, d1_logits, d2_logits, g, temp):
    """Assemble per-node drafter categoricals q_n [B,N,V] for sampling
    verification. Trunk slots from d1; branch slots from d2 (or d1 resample
    dist for naive_k, d2_logits=None)."""
    b, n = tree.tokens.shape
    v = d1_logits.shape[-1]
    q1 = jax.nn.softmax(d1_logits.astype(jnp.float32) / temp, axis=-1)
    slot = jnp.clip(tree.depth, 0, g - 1)                      # [B,N]
    q_trunk = jnp.take_along_axis(q1, slot[..., None], axis=1)
    if d2_logits is None:
        return q_trunk
    node = jnp.arange(n)
    k = d2_logits.shape[1]
    bidx = jnp.clip((node - g) // (g - 1), 0, k - 1)
    q2 = jax.nn.softmax(d2_logits.astype(jnp.float32) / temp, axis=-1)
    q2_flat = q2.reshape(b, k * g, v)
    sel = bidx[None, :] * g + slot                             # [B,N]
    q_branch = jnp.take_along_axis(q2_flat, sel[..., None], axis=1)
    is_trunk = (node < g)[None, :, None]
    return jnp.where(is_trunk, q_trunk, q_branch)


# ------------------------------------------------------------ strategies ---
@register_strategy("dflash")
class DFlashStrategy(DraftStrategy):
    """Single-chain first-draft baseline (Table 1 rows "DFlash")."""

    def draft(self, bundle, state, key):
        spec = bundle.spec
        temp = spec.temperature
        k1, _ = jax.random.split(key)
        trunk, d1_logits = first_draft(bundle, state, k1, temp)
        conf = conf_lib.confidences(d1_logits[:, 1:],
                                    trunk if temp > 0 else None)
        tree = tree_lib.chain_tree(state.anchor, trunk)
        dprobs = (comb_draft_probs(tree, d1_logits, None, spec.gamma, temp)
                  if temp > 0 else None)
        return DraftResult(tree=tree, dprobs=dprobs, conf=conf,
                           max_children=1)

    def n_draft_passes(self, spec):
        return 1

    def n_tree_nodes(self, spec):
        return spec.gamma


@register_strategy("eagle")
class EagleStrategy(DraftStrategy):
    """Autoregressive chain drafter baseline (EAGLE-style)."""

    def draft(self, bundle, state, key):
        spec = bundle.spec
        g, temp = spec.gamma, spec.temperature
        k1, _ = jax.random.split(key)
        trunk, chain_logits = dr.ar_chain_draft(
            bundle.d1_params, bundle.d1_cfg, state.anchor, state.d1_feat,
            steps=g - 1, temperature=temp, key=k1)
        tree = tree_lib.chain_tree(state.anchor, trunk)
        dprobs = None
        if temp > 0:
            q = jax.nn.softmax(chain_logits.astype(jnp.float32) / temp,
                               axis=-1)
            dprobs = jnp.concatenate([q[:, :1] * 0, q], axis=1)
        return DraftResult(tree=tree, dprobs=dprobs, conf=None,
                           max_children=1)

    def n_draft_passes(self, spec):
        return spec.gamma - 1

    def n_tree_nodes(self, spec):
        return spec.gamma


@register_strategy("naive_k")
class NaiveKStrategy(DraftStrategy):
    """Trunk + K T=1 multinomial resamples of the SAME d1 pass (Table 5)."""

    def draft(self, bundle, state, key):
        spec = bundle.spec
        g, kbr, temp = spec.gamma, spec.top_k_branches, spec.temperature
        b = state.batch
        k1, k2 = jax.random.split(key)
        trunk, d1_logits = first_draft(bundle, state, k1, temp)
        conf = conf_lib.confidences(d1_logits[:, 1:],
                                    trunk if temp > 0 else None)
        resampled = jax.random.categorical(
            k2, d1_logits[:, None, 1:, :].astype(jnp.float32)
            / max(temp, 1.0), shape=(b, kbr, g - 1))
        fork_idx = jnp.zeros((b, kbr), jnp.int32)
        tree = tree_lib.comb_tree(state.anchor, trunk,
                                  resampled.astype(jnp.int32), fork_idx, g)
        dprobs = (comb_draft_probs(tree, d1_logits, None, g, temp)
                  if temp > 0 else None)
        return DraftResult(tree=tree, dprobs=dprobs, conf=conf,
                           max_children=kbr + 1)

    def n_draft_passes(self, spec):
        return 1

    def n_tree_nodes(self, spec):
        return spec.gamma + spec.top_k_branches * (spec.gamma - 1)


@register_strategy("d2sd")
class D2SDStrategy(DraftStrategy):
    """Full dual-diffusion pipeline: DFlash trunk -> Eq. 5 top-K forks ->
    batched VP second draft (+ optional third level, Table 7)."""

    def draft(self, bundle, state, key):
        spec = bundle.spec
        g, kbr, temp = spec.gamma, spec.top_k_branches, spec.temperature
        b = state.batch
        k1, k3, k4 = jax.random.split(key, 3)
        trunk, d1_logits = first_draft(bundle, state, k1, temp)
        with jax.named_scope("d2sd.select"):
            conf = conf_lib.confidences(d1_logits[:, 1:],
                                        trunk if temp > 0 else None)
            r = conf_lib.boundary_posterior(conf)
            _, fork_idx = conf_lib.topk_prefixes(r, kbr)           # [B, K]
        with jax.named_scope("d2sd.draft2"):
            branch_tokens, d2_logits = second_draft(
                bundle.d2_params, bundle.d2_cfg, state.d2_feat,
                state.anchor, trunk, fork_idx, k3, temp,
                state.d2_feat["length"])
            tree = tree_lib.comb_tree(state.anchor, trunk, branch_tokens,
                                      fork_idx, g)
            max_children = kbr + 1
            if spec.third_level:
                conf2 = conf_lib.confidences(
                    d2_logits[:, :, 1:].reshape(b * kbr, g - 1, -1),
                    branch_tokens.reshape(b * kbr, g - 1) if temp > 0
                    else None).reshape(b, kbr, g - 1)
                # only suffix slots (> fork) are third-level candidates
                slot = jnp.arange(1, g)[None, None, :]
                c2 = jnp.where(slot > fork_idx[:, :, None] + 1, conf2, 1.0)
                r2 = conf_lib.boundary_posterior(
                    c2.reshape(b * kbr, g - 1)).reshape(b, kbr, g - 1)
                # r2[..., i] = P(prefix of length i accepted); fork slot = i
                fork3 = jnp.argmax(r2, axis=-1).astype(jnp.int32)
                fork3 = jnp.clip(jnp.maximum(fork3, fork_idx + 1), 0, g - 2)
                # visible prefix for third branches = trunk up to fork_b +
                # branch b tokens up to fork3_b
                third_tokens, _ = second_draft(
                    bundle.d2_params, bundle.d2_cfg, state.d2_feat,
                    state.anchor, _splice(trunk, branch_tokens, fork_idx),
                    fork3, k4, temp, state.d2_feat["length"])
                tree = tree_lib.extend_third_level(
                    tree, third_tokens, fork_idx, fork3, g)
                max_children += 1
            dprobs = (comb_draft_probs(tree, d1_logits, d2_logits, g, temp)
                      if temp > 0 else None)
        return DraftResult(tree=tree, dprobs=dprobs, conf=conf,
                           max_children=max_children)

    def n_draft_passes(self, spec):
        return 3 if spec.third_level else 2

    def n_tree_nodes(self, spec):
        base = spec.gamma + spec.top_k_branches * (spec.gamma - 1)
        if spec.third_level:
            base += spec.top_k_branches * (spec.gamma - 1)
        return base


@register_strategy("dflash_second")
class DFlashSecondStrategy(D2SDStrategy):
    """Table 6 ablation: d2sd pipeline with drafter-1 weights reused as the
    second drafter (wire bundle.d2_params = d1 params; the draft phase is
    identical to d2sd)."""
