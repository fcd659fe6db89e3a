"""Logical-axis sharding: name-based rules mapping logical axes to mesh axes.

Usage::

    rules = LOGICAL_RULES  # or a customized dict
    with use_sharding(mesh, rules):
        y = constrain(y, ("batch", "seq", "embed"))

Outside a ``use_sharding`` context (or without a mesh) ``constrain`` is a
no-op, so model code is mesh-agnostic: smoke tests run on 1 CPU device, the
dry-run runs on 512 host devices, production on real pods.

Parameter shardings are derived from *parameter path names* via
``param_pspec`` — every weight in the model zoo follows the naming scheme
below, so rules are robust without threading metadata through init.
"""
from __future__ import annotations

import contextlib
import re
import threading
from typing import Dict, Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# logical axis -> mesh axis (or tuple of mesh axes, or None = replicate)
# "fsdp" entries are only active when the rules enable them.
LOGICAL_RULES: Dict[str, object] = {
    "batch": ("pod", "data"),     # data parallel over pod+data
    "seq": None,                  # activations: sequence replicated by default
    "act_seq": "model",           # sequence-parallel activations between blocks
    "embed": None,                # model dim of activations
    "vocab": "model",             # embedding/lm-head vocab dim
    "embed_fsdp": "data",         # FSDP: shard param embed dim over data
    "heads": "model",             # attention q heads
    "kv_heads": None,             # kv heads often tiny (2-8): replicate, SP the seq
    "kv_seq": "model",            # decode: KV cache sequence sharding
    "ffn": "model",               # MLP hidden
    "experts": "model",           # MoE expert dim
    "expert_ffn": None,           # within-expert ffn (set to None when EP active)
    "stage": "pod",               # pipeline stages (pod_role="pipeline")
}


class _Ctx(threading.local):
    def __init__(self):
        self.mesh: Optional[Mesh] = None
        self.rules: Dict[str, object] = dict(LOGICAL_RULES)
        self.fsdp: bool = False


_CTX = _Ctx()


@contextlib.contextmanager
def use_sharding(mesh: Optional[Mesh], rules: Optional[Dict[str, object]] = None,
                 fsdp: bool = False):
    old = (_CTX.mesh, _CTX.rules, _CTX.fsdp)
    _CTX.mesh = mesh
    _CTX.rules = dict(rules) if rules is not None else dict(LOGICAL_RULES)
    _CTX.fsdp = fsdp
    try:
        yield
    finally:
        _CTX.mesh, _CTX.rules, _CTX.fsdp = old


def active_mesh() -> Optional[Mesh]:
    return _CTX.mesh


def fsdp_enabled() -> bool:
    return _CTX.fsdp


def _mesh_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(mesh.axis_names)


def logical_to_pspec(logical: Sequence[Optional[str]],
                     mesh: Optional[Mesh] = None,
                     rules: Optional[Dict[str, object]] = None) -> P:
    """Map a tuple of logical axis names to a PartitionSpec for ``mesh``.

    Mesh axes absent from the mesh are dropped (e.g. 'pod' on a 2D mesh).
    """
    mesh = mesh or _CTX.mesh
    rules = rules or _CTX.rules
    avail = set(_mesh_axes(mesh)) if mesh is not None else set()
    out, used = [], set()
    for name in logical:
        axes = rules.get(name) if name is not None else None
        if axes is None:
            out.append(None)
            continue
        if isinstance(axes, str):
            axes = (axes,)
        keep = tuple(a for a in axes if a in avail and a not in used)
        used.update(keep)
        if not keep:
            out.append(None)
        elif len(keep) == 1:
            out.append(keep[0])
        else:
            out.append(keep)
    return P(*out)


def _axis_sizes(mesh: Mesh) -> Dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def fit_spec(spec: P, shape, mesh: Mesh) -> P:
    """Drop mesh axes from a PartitionSpec where the dim isn't divisible
    (e.g. batch=1 long-context decode on a 512-chip mesh)."""
    sizes = _axis_sizes(mesh)
    out = []
    for d, entry in enumerate(spec):
        if entry is None:
            out.append(None)
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        keep = []
        prod = 1
        for a in axes:
            if shape[d] % (prod * sizes[a]) == 0:
                keep.append(a)
                prod *= sizes[a]
        out.append(tuple(keep) if len(keep) > 1 else
                   (keep[0] if keep else None))
    return P(*out)


def constrain(x, logical: Sequence[Optional[str]]):
    """with_sharding_constraint by logical names; no-op without a mesh."""
    mesh = _CTX.mesh
    if mesh is None:
        return x
    spec = fit_spec(logical_to_pspec(logical, mesh), x.shape, mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def mesh_tag():
    """Hashable fingerprint of the active (mesh, rules, fsdp) context.

    jit caches key on avals, not on this module's threadlocal context; any
    jitted function whose TRACE depends on the active mesh (``constrain``
    calls, the shard_map decode hooks) must take this as a static argument
    so one process can hold sharded and unsharded specializations side by
    side — the sharded-vs-single-device parity tests do exactly that.
    Returns None outside a mesh context.
    """
    mesh = _CTX.mesh
    if mesh is None:
        return None
    rules = tuple(sorted(
        (k, tuple(v) if isinstance(v, (tuple, list)) else v)
        for k, v in _CTX.rules.items()))
    return (tuple(mesh.axis_names), tuple(mesh.devices.shape), rules,
            _CTX.fsdp)


def shard_put(x, logical: Sequence[Optional[str]]):
    """``constrain`` that also works on concrete arrays (eager placement).

    Inside a trace this is ``with_sharding_constraint``; on a concrete
    array it is a ``device_put`` onto the fitted NamedSharding — the eager
    half of the borrowed-pool contract (``ServingEngine`` allocates its
    engine-lifetime pool outside any trace). No-op without a mesh.
    """
    mesh = _CTX.mesh
    if mesh is None:
        return x
    if isinstance(x, jax.core.Tracer):
        return constrain(x, logical)
    spec = fit_spec(logical_to_pspec(logical, mesh), x.shape, mesh)
    return jax.device_put(x, NamedSharding(mesh, spec))


def replicate_onto(tree, mesh: Mesh):
    """Replicate over ``mesh`` every leaf of ``tree`` that lives on a single
    device; leaves already spread over several devices keep their sharding.

    A leaf's own buffer becomes the copy on its device when that device is
    in the mesh, so the device holds no second copy of the weights and the
    caller's arrays stay valid (the single-device and sharded engines of
    one process share them).
    """
    rep = NamedSharding(mesh, P())
    devs = list(mesh.devices.flat)

    def one(x):
        if not isinstance(x, jax.Array):      # host data: one plain copy
            return jax.device_put(x, rep)
        if len(x.devices()) > 1:
            return x
        (home,) = x.devices()
        shards = [x if d == home else jax.device_put(x, d) for d in devs]
        return jax.make_array_from_single_device_arrays(x.shape, rep, shards)

    return jax.tree.map(one, tree)


# ---------------------------------------------------------------------------
# Parameter sharding by path name.
#
# Naming convention (suffix of the '/'-joined path):
#   embedding            -> (vocab, embed*)
#   lm_head              -> (embed*, vocab)
#   wq / wkv-ish:
#     attn/wq            -> (embed*, heads)     [d, Hq*Dh fused]
#     attn/wk, attn/wv   -> (embed*, kv_heads)
#     attn/wo            -> (heads, embed*)
#     *bias* 1-d         -> replicated
#   mlp/w_in, mlp/w_gate -> (embed*, ffn)
#   mlp/w_out            -> (ffn, embed*)
#   moe/w_in|w_gate      -> (experts, embed, ffn)
#   moe/w_out            -> (experts, ffn, embed)
#   moe/router           -> (embed, experts-as-ffn? keep replicated cols)
#   scale / norm 1-d     -> replicated
# Scanned stacks have a leading layer axis -> None prepended.
# embed* becomes "embed_fsdp" when FSDP is on (params only).
# ---------------------------------------------------------------------------

_PARAM_RULES: Tuple[Tuple[str, Tuple[Optional[str], ...]], ...] = (
    # --- decoding state (caches) ---
    (r"(^|/)(k|v)$", ("batch", "kv_seq", "kv_heads_p", None)),
    (r"(^|/)(ck|cv)$", ("batch", None, "kv_heads_p", None)),
    (r"tm_s$", ("batch", "heads", None, None)),
    (r"(rg_h)$", ("batch", "ffn")),
    (r"(conv_buf)$", ("batch", None, "ffn")),
    (r"(tm_x_prev|cm_x_prev)$", ("batch", None)),
    (r"length$", ("batch",)),
    # --- RWKV time/channel mix (before generic wk/wv rules) ---
    (r"rwkv_tm/(wr|wk|wv|wg)$", ("p_embed", "heads")),
    (r"rwkv_tm/wo$", ("heads", "p_embed")),
    (r"rwkv_tm/w_lora_b$", (None, "heads")),
    (r"rwkv_cm/wk$", ("p_embed", "ffn")),
    (r"rwkv_cm/wv$", ("ffn", "p_embed")),
    (r"rwkv_cm/wr$", ("p_embed", "p_embed")),
    # --- RG-LRU ---
    (r"(w_branch)$", ("p_embed", "ffn")),
    (r"(wa|wx)$", ("p_embed", "ffn")),
    (r"conv_w$", (None, "ffn")),
    # --- embeddings / heads ---
    (r"embedding$", ("vocab", "p_embed")),
    (r"lm_head$", ("p_embed", "vocab")),
    (r"head$", ("p_embed", "vocab")),
    # --- attention / MLP ---
    (r"(wq|wqkv)$", ("p_embed", "heads")),
    (r"(wk|wv)$", ("p_embed", "kv_heads_p")),
    (r"wo$", ("heads", "p_embed")),
    (r"(w_in|w_gate|w_up)$", ("p_embed", "ffn")),
    (r"w_out$", ("ffn", "p_embed")),
    (r"moe_w_(in|gate)$", ("experts", "p_embed", "expert_ffn")),
    (r"moe_w_out$", ("experts", "expert_ffn", "p_embed")),
    (r"router$", ("p_embed", None)),
    (r"feat_proj$", ("p_embed", "p_embed")),
)

# parameter-only logical axes
_PARAM_AXES = {
    "p_embed": lambda: "embed_fsdp" if _CTX.fsdp else None,
    "kv_heads_p": lambda: "kv_heads",
}


def _resolve_param_axes(axes: Sequence[Optional[str]]) -> Tuple[Optional[str], ...]:
    out = []
    for a in axes:
        if a in _PARAM_AXES:
            out.append(_PARAM_AXES[a]())
        else:
            out.append(a)
    return tuple(out)


def param_logical_axes(path: str, ndim: int) -> Tuple[Optional[str], ...]:
    """Logical axes for a parameter/state leaf given its '/'-joined path and
    rank. Optimizer-state suffixes map onto the base parameter's axes:
    Adafactor row stats (/vr) drop the last axis, column stats (/vc) drop the
    second-to-last; int8 moments (/q) inherit, their scales (/s) replicate.
    """
    stat = None
    for suffix in ("/vr", "/vc", "/q", "/s"):
        if path.endswith(suffix):
            stat = suffix[1:]
            path = path[: -len(suffix)]
            break
    if stat == "s":
        return (None,) * ndim

    def base_axes(nd):
        for pat, axes in _PARAM_RULES:
            if re.search(pat, path):
                axes = _resolve_param_axes(axes)
                if nd == len(axes):
                    return axes
                if nd > len(axes):
                    return (None,) * (nd - len(axes)) + axes
                return axes[-nd:] if nd > 0 else ()
        return (None,) * nd

    if stat == "vr":
        return base_axes(ndim + 1)[:-1]
    if stat == "vc":
        ax = base_axes(ndim + 1)
        return ax[:-2] + ax[-1:]
    return base_axes(ndim)


def param_pspec(path: str, ndim: int, mesh: Optional[Mesh] = None) -> P:
    return logical_to_pspec(param_logical_axes(path, ndim), mesh)


def tree_paths(tree) -> Dict[str, object]:
    """Flatten a pytree into {'/'.join(path): leaf}."""
    flat = {}
    for kp, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        parts = []
        for k in kp:
            if hasattr(k, "key"):
                parts.append(str(k.key))
            elif hasattr(k, "idx"):
                parts.append(str(k.idx))
            else:
                parts.append(str(k))
        flat["/".join(parts)] = leaf
    return flat


def params_shardings(params, mesh: Optional[Mesh] = None):
    """NamedSharding pytree for a parameter/state pytree (path-name rules,
    divisibility-fitted). Works for params, optimizer states, caches."""
    mesh = mesh or _CTX.mesh
    assert mesh is not None, "params_shardings needs a mesh"

    def one(kp, leaf):
        parts = []
        for k in kp:
            if hasattr(k, "key"):
                parts.append(str(k.key))
            elif hasattr(k, "idx"):
                parts.append(str(k.idx))
        path = "/".join(parts)
        spec = param_pspec(path, leaf.ndim, mesh)
        return NamedSharding(mesh, fit_spec(spec, leaf.shape, mesh))

    return jax.tree_util.tree_map_with_path(one, params)


def constrain_params(params):
    """Apply parameter sharding constraints inside jit (by path rules)."""
    mesh = _CTX.mesh
    if mesh is None:
        return params

    def one(kp, leaf):
        parts = [str(k.key) for k in kp if hasattr(k, "key")]
        spec = fit_spec(param_pspec("/".join(parts), leaf.ndim, mesh),
                        leaf.shape, mesh)
        return jax.lax.with_sharding_constraint(leaf, NamedSharding(mesh, spec))

    return jax.tree_util.tree_map_with_path(one, params)
