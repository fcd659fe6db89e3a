"""GPipe-style pipeline parallelism over the ``pod`` axis.

For multi-pod meshes the pod axis can act as pure extra data parallelism
(default) or as pipeline stages (MeshConfig.pod_role="pipeline"): the layer
stack is split into S contiguous stages, microbatches stream through with
``collective_permute`` hops between stage owners, and the bubble fraction is
(S-1)/(M+S-1) for M microbatches.

Implementation: shard_map over the pod axis; each pod holds its stage's
parameters (leading stage axis sharded over pod); a lax.fori over
M + S - 1 ticks runs the classic schedule; activations hop via ppermute.
Compute/communication overlap: the ppermute of tick t runs concurrently
with the next tick's stage compute (double-buffered carry).
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.distributed import sharding as sh


def pipeline_apply(stage_fn: Callable, stage_params, x_microbatches,
                   axis: str = "pod"):
    """Run microbatches through pipeline stages owned by pod ranks.

    stage_fn(params_slice, x) -> y       (one stage's computation)
    stage_params: pytree with leading axis [S, ...] sharded over ``axis``.
    x_microbatches: [M, mb, ...] (replicated over ``axis``).
    Returns [M, mb, ...] outputs of the final stage.
    """
    mesh = sh.active_mesh()
    assert mesh is not None
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    s = sizes[axis]
    m = x_microbatches.shape[0]

    def shard_fn(params, xs):
        params = jax.tree.map(lambda a: a[0], params)   # my stage's slice
        rank = jax.lax.axis_index(axis)
        n_ticks = m + s - 1
        buf = jnp.zeros_like(xs[0])
        buf = jax.lax.pcast(buf, (axis,), to="varying")
        outs = jax.lax.pcast(jnp.zeros_like(xs), (axis,), to="varying")

        def tick(t, carry):
            buf, outs = carry
            # stage 0 injects microbatch t (when in range)
            mb_idx = jnp.clip(t, 0, m - 1)
            inject = jnp.where(rank == 0,
                               jnp.where(t < m, 1.0, 0.0), 0.0)
            x_in = jnp.where(inject > 0, xs[mb_idx], buf)
            y = stage_fn(params, x_in)
            # last stage records output of microbatch t - (s-1)
            out_idx = jnp.clip(t - (s - 1), 0, m - 1)
            record = (rank == s - 1) & (t >= s - 1)
            outs = jax.lax.cond(
                record,
                lambda o: o.at[out_idx].set(y),
                lambda o: o, outs)
            # hop activations forward one stage
            buf = jax.lax.ppermute(
                y, axis, [(i, (i + 1) % s) for i in range(s)])
            return buf, outs

        _, outs = jax.lax.fori_loop(0, n_ticks, tick, (buf, outs))
        # replicate results from the last stage to all pods
        outs = jax.lax.psum(
            jnp.where(rank == s - 1, outs, jnp.zeros_like(outs)), axis)
        return outs

    other = tuple(a for a in mesh.axis_names if a != axis)
    pspec = P(axis)
    xspec = P()
    return jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(jax.tree.map(lambda _: pspec, stage_params), xspec),
        out_specs=xspec, check_vma=True,
    )(stage_params, x_microbatches)
