"""Megatron's two conjugate operators over a mesh axis, for code inside a
`shard_map` with replication checking off: each pins BOTH directions of a
psum, which a bare `lax.psum` there does not."""
from __future__ import annotations

import functools

import jax


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def tp_f(x, axis: str):
    """Megatron's `f` operator: identity forward, psum-over-tp backward.
    Placed at each sublayer input so activation COTANGENTS — partial per
    model shard after flowing back through that shard's weight slice — are
    summed back to full. With f in place, every replicated parameter's
    gradient comes out identical on all model shards and NO gradient
    collective over the model axis is needed; sharded weights' gradients
    are complete locally (the psum's own transpose broadcasts)."""
    return x


tp_f.defvjp(lambda x, axis: (x, None),
            lambda axis, _, g: (jax.lax.psum(g, axis),))


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def tp_g(x, axis: str):
    """Megatron's `g` operator: psum forward, IDENTITY backward. Under
    shard_map with replication checking off, a bare psum's transpose is
    another psum — the already-replicated output cotangent would be summed
    again, overcounting every row-parallel weight's gradient tp times
    (non-uniformly vs the column side, so even Adam diverges). Pairing
    g (here) with f (above) pins both directions explicitly."""
    return jax.lax.psum(x, axis)


tp_g.defvjp(lambda x, axis: (jax.lax.psum(x, axis), None),
            lambda axis, _, ct: (ct,))
