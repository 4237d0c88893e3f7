"""SPMD pipeline parallelism (GPipe schedule) over the mesh 'pp' axis
(TPU-native extension; the reference's pipeline story never left the
legacy layer-placement design, SURVEY §2.4).

Shape: L IDENTICAL layers, parameters stacked on a leading [L, ...] axis
sharded over 'pp' (each of the P ranks owns L/P consecutive layers); the
batch splits into M microbatches. One lax.scan runs the classic
fill/compute/drain schedule: at every tick each rank applies its layer to
the activation arriving from the previous rank (a lax.ppermute shift
register — one ICI hop per tick), rank 0 injects fresh microbatches,
rank P-1 emits finished ones. Bubble ticks compute on don't-care data and
are masked out — the standard GPipe trade (bubble fraction
(P-1)/(M+P-1)). The scan is reverse-differentiable, so training works
out of the box.

Current scope: one layer per rank (L == P). Deeper stacks pipeline in
groups by calling gpipe_apply once per group of P layers.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .mesh import DATA_AXIS, PIPE_AXIS


def gpipe_apply(layer_fn, stacked_params, x_microbatches, mesh,
                pp_axis=PIPE_AXIS, batch_axis=DATA_AXIS):
    """Apply P stacked layers as a pipeline over `pp_axis`.

    layer_fn(params_slice, x) -> y with y.shape == x.shape
    stacked_params: pytree; every leaf has leading dim P (layer axis),
        sharded over pp_axis.
    x_microbatches: [M, mb, ...] microbatched input; the mb dim shards
        over `batch_axis` when the mesh has one (each dp group pipelines
        only its own batch shard — layers never mix rows).
    Returns [M, mb, ...]: layer P-1(...layer 0(x)).
    """
    from jax import shard_map

    nstages = int(mesh.shape[pp_axis])
    m = x_microbatches.shape[0]
    ndp = int(mesh.shape.get(batch_axis, 1))
    # shard the microbatch rows over dp only when they divide; else
    # replicate (correct, just without the dp speedup for this op)
    b_ax = batch_axis if ndp > 1 \
        and x_microbatches.shape[1] % ndp == 0 else None
    extra = (None,) * (x_microbatches.ndim - 2)
    xs_spec = P(None, b_ax, *extra)
    param_specs = jax.tree.map(lambda _: P(pp_axis), stacked_params)

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(param_specs, xs_spec),
        out_specs=P(pp_axis, None, b_ax, *extra), check_vma=False)
    def pipe(params_local, xs):
        rank = jax.lax.axis_index(pp_axis)
        p_local = jax.tree.map(lambda a: a[0], params_local)  # this stage
        perm = [(i, (i + 1) % nstages) for i in range(nstages)]
        zero = jnp.zeros_like(xs[0])

        def tick(carry, t):
            shifted = carry            # output of rank-1 from last tick
            mb_idx = jnp.clip(t, 0, m - 1)
            inp = jnp.where(rank == 0,
                            jax.lax.dynamic_index_in_dim(
                                xs, mb_idx, keepdims=False),
                            shifted)
            out = layer_fn(p_local, inp)
            # don't-care ticks (pipeline bubble) produce garbage that is
            # never emitted; zero it so NaNs can't propagate via ppermute
            active = (t >= rank) & (t < m + rank)
            out = jnp.where(active, out, zero)
            return jax.lax.ppermute(out, pp_axis, perm), out

        ticks = jnp.arange(m + nstages - 1)
        _, outs = jax.lax.scan(tick, zero, ticks)   # [T, mb, ...]
        # this rank's finished microbatch j sits at tick j + rank; only
        # rank P-1's slice is the pipeline output (selected by the caller
        # from the out_specs=P(pp_axis) leading axis)
        sel = jax.lax.dynamic_slice_in_dim(outs, rank, m, axis=0) \
            if nstages > 1 else outs[:m]
        return sel[None]               # [1, M, mb, ...] per rank

    stacked = pipe(stacked_params, x_microbatches)  # [P, M, mb, ...]
    return stacked[-1]                              # rank P-1's emissions
