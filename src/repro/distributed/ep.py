"""Expert parallelism via shard_map: the production MoE path (§Perf).

Observation: in our TP layout the token activations are REPLICATED across
the ``model`` axis (they are sharded over data/pod only). So expert
parallelism needs **no all_to_all at all**: every model-rank already holds
every token; it computes only the experts it owns (capacity-gathered
locally), emits a partial combine, and ONE psum([T_loc, d]) per layer merges
expert contributions. Communication per MoE layer drops from
"all-gather the expert weights" (5.8 GB/layer for grok-1 serving under
naive pjit — measured in the §Perf diagnosis) to a ~14 MB activation psum.

Expert-to-rank mapping handles both regimes:
  * E %  M == 0: rank r owns experts [r*E_loc, (r+1)*E_loc)
  * M %  E == 0: experts are SPLIT along d_ff: rank r owns the
    (r % split)-th f-slice of expert r // split (SwiGLU is elementwise in
    f, so slicing f across ranks is exact; the psum sums the slices).

Differentiable (shard_map + psum), so the same path serves EP training.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.distributed import sharding as sh
from repro.models.layers import act_fn


def ep_available(cfg) -> bool:
    mesh = sh.active_mesh()
    if mesh is None or cfg.moe is None:
        return False
    ax = sh._CTX.rules.get("experts")
    if isinstance(ax, (tuple, list)):
        ax = ax[0] if ax else None
    if ax is None or ax not in mesh.axis_names:
        return False
    m = dict(zip(mesh.axis_names, mesh.devices.shape))[ax]
    e = cfg.moe.num_experts
    if m <= 1:
        return False
    return e % m == 0 or (m % e == 0 and cfg.d_ff % (m // e) == 0)


def moe_apply_ep(p, x, cfg):
    """x: [B,T,d] -> [B,T,d]; requires ep_available(cfg)."""
    mesh = sh.active_mesh()
    ax = sh._CTX.rules.get("experts")
    ax = ax[0] if isinstance(ax, (tuple, list)) else ax
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    m = sizes[ax]
    e, d, f = cfg.moe.num_experts, cfg.d_model, cfg.d_ff
    k = cfg.moe.top_k
    b, t, _ = x.shape
    x2 = x.reshape(b * t, d)

    # ---- routing (replicated weights; token-sharded activations) ----
    logits = jnp.einsum("td,de->te", x2.astype(jnp.float32),
                        p["router"].astype(jnp.float32))
    gates, idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), k)
    gates = (gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
             ).astype(x.dtype)

    # ---- expert weight relayout to a leading rank axis of size M ----
    gated = cfg.mlp_gated
    if e % m == 0:
        e_loc, split, f_loc = e // m, 1, f
        w_in = p["moe_w_in"].reshape(m, e_loc, d, f)
        w_gate = p["moe_w_gate"].reshape(m, e_loc, d, f) if gated else None
        w_out = p["moe_w_out"].reshape(m, e_loc, f, d)
    else:
        split = m // e
        e_loc, f_loc = 1, f // split
        w_in = p["moe_w_in"].reshape(e, d, split, f_loc).transpose(
            0, 2, 1, 3).reshape(m, 1, d, f_loc)
        w_gate = (p["moe_w_gate"].reshape(e, d, split, f_loc).transpose(
            0, 2, 1, 3).reshape(m, 1, d, f_loc) if gated else None)
        w_out = p["moe_w_out"].reshape(e, split, f_loc, d).reshape(
            m, 1, f_loc, d)

    batch_axes = []
    prod = 1
    for a in ("pod", "data"):
        if a in mesh.axis_names and (b * t) % (prod * sizes[a]) == 0:
            batch_axes.append(a)
            prod *= sizes[a]
    bspec = tuple(batch_axes) if len(batch_axes) > 1 else (
        batch_axes[0] if batch_axes else None)
    t_loc = (b * t) // prod
    cap = max(8, int(t_loc * k / e * cfg.moe.capacity_factor) + 1)

    wspecs = P(ax)
    cast = x.dtype

    def shard_fn(x_loc, gates_loc, idx_loc, wi, wg, wo):
        rank = jax.lax.axis_index(ax)
        wi = wi[0]                       # shard_map keeps rank dim as size 1
        wo = wo[0]
        wg = wg[0] if gated else None
        y = jnp.zeros_like(x_loc)
        y = jax.lax.pcast(y, (ax,), to="varying")
        flat_idx = idx_loc.reshape(-1)                       # [T_loc*k]
        flat_gate = gates_loc.reshape(-1)
        src = jnp.repeat(jnp.arange(t_loc), k)
        for j in range(e_loc):
            e_mine = (rank * e_loc + j) if split == 1 else rank // split
            sel = flat_idx == e_mine                         # [T_loc*k]
            pos = jnp.cumsum(sel.astype(jnp.int32)) - 1
            ok = sel & (pos < cap)
            wpos = jnp.where(ok, pos, cap)
            xin0 = jax.lax.pcast(jnp.zeros((cap + 1, d), cast), (ax,),
                                 to="varying")
            xin = xin0.at[wpos].add(
                jnp.where(ok[:, None], x_loc[src], 0))[:cap]
            h = xin @ wi[j].astype(cast)
            if gated:
                h = act_fn(cfg.mlp_act)(xin @ wg[j].astype(cast)) * h
            else:
                h = act_fn(cfg.mlp_act)(h)
            xout = h @ wo[j].astype(cast)                    # [cap, d]
            picked = jnp.where(ok[:, None],
                               xout[jnp.clip(wpos, 0, cap - 1)], 0)
            y = y.at[src].add(picked * flat_gate[:, None])
        return jax.lax.psum(y, ax)

    y2 = jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P(bspec), P(bspec), P(bspec), wspecs, wspecs
                  if gated else P(), wspecs),
        out_specs=P(bspec),
        check_vma=True,
    )(x2, gates, idx,
      jax.lax.with_sharding_constraint(
          w_in, jax.sharding.NamedSharding(mesh, P(ax))),
      (jax.lax.with_sharding_constraint(
          w_gate, jax.sharding.NamedSharding(mesh, P(ax)))
       if gated else jnp.zeros((), x.dtype)),
      jax.lax.with_sharding_constraint(
          w_out, jax.sharding.NamedSharding(mesh, P(ax))))
    return y2.reshape(b, t, d)
