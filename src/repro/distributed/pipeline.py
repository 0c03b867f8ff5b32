"""GPipe-style pipeline parallelism over a mesh axis (e.g. ``pod``).

Stages are sharded over ``axis``; each step every stage processes one
microbatch and hands its activation to the next stage via a neighbor
``ppermute`` — on the TPU torus this is the same physical pattern as the
stencil halo update, and the hand-off of step t overlaps the compute of
step t+1 exactly like ``@hide_communication``.

Schedule: plain GPipe fill-drain, M microbatches over S stages in
M + S - 1 ticks (bubble fraction (S-1)/(M+S-1)).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def gpipe(stage_fn, stage_params, microbatches, mesh, *, axis: str = "pod"):
    """Run ``y = stage_{S-1}(... stage_0(x))`` for each microbatch.

    stage_fn(params_s, x) -> y with x/y of identical shape;
    stage_params: pytree with leading axis S (sharded over ``axis``);
    microbatches: (M, ...) array.  Returns (M, ...) outputs.
    """
    S = mesh.shape[axis]
    M = microbatches.shape[0]

    def local(params_s, xs):
        # params_s: leading axis 1 (this stage's slice); xs: (M, ...) replicated
        params_local = jax.tree.map(lambda a: a[0], params_s)
        r = jax.lax.axis_index(axis)
        perm = [(i, i + 1) for i in range(S - 1)]

        def body(t, carry):
            recv, outs = carry
            x0 = xs[jnp.clip(t, 0, M - 1)]
            cur = jnp.where(r == 0, x0, recv)
            y = stage_fn(params_local, cur)
            m = t - (S - 1)
            valid = (m >= 0) & (r == S - 1)
            mc = jnp.clip(m, 0, M - 1)
            outs = outs.at[mc].set(jnp.where(valid, y, outs[mc]))
            recv = jax.lax.ppermute(y, axis, perm)
            return recv, outs

        recv0 = jax.lax.pcast(jnp.zeros_like(xs[0]), (axis,), to="varying")
        outs0 = jax.lax.pcast(jnp.zeros_like(xs), (axis,), to="varying")
        _, outs = jax.lax.fori_loop(0, M + S - 1, body, (recv0, outs0))
        # only the last stage holds real outputs; broadcast via psum of a
        # one-hot mask (cheap relative to the pipeline itself)
        outs = jax.lax.psum(jnp.where(r == S - 1, outs, 0.0), axis)
        return outs

    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(),
        check_vma=False,
    )
    return jax.jit(fn)(stage_params, microbatches)
