"""The embedding lookup, and its gradient without XLA's scatter.

The lookup is `table[ids]`: a gather of rows, fast on every platform. Its
gradient is the table-shaped sum of the cotangent rows by id, which JAX
writes as XLA's scatter-add into the table's gradient. On a v5e that
scatter is one dependent HBM read-modify-write a row: 1.3 us a row, 21 ms
a step for 16,384 rows of 2,560 into a 128 MB bfloat16 table, where the
bytes it must move take 0.26 ms (PERF.md, PR 38).

`lookup` keeps the gather and gives it a gradient of its own, chosen from
what it can observe (the platform and the shapes), with no knob, and
counted at trace time (`embed.grad.route.pallas` / `embed.grad.route.xla`):

**The kernel** `embed_grad`, on a TPU where `pallas_fits`. ONE `lax.sort`
of the ids carries their positions; the cotangent's rows are gathered
into that order, so the rows of one vocabulary block are one contiguous
run. The grid walks the blocks of `BLOCK` ids in order, and each block the
chunks of `CHUNK` sorted rows its run touches (a block no id reaches gets
one step, with no product): a step's chunk of rows and their ids arrive
by `BlockSpec`, indexed by scalar-prefetched tables, so the run is read
whole and not row by row. A step adds a one-hot (BLOCK x CHUNK) product
with its chunk into a float32 (BLOCK, d) VMEM accumulator on the MXU
(exact: a product by 0 or 1 is exact, the sum is float32), and the
block's last step writes the block once, in the table's dtype. Every
block of the output is written by the kernel, zeros included. The one-hot
goes per block: over the whole vocabulary it would be V N d 2 operations
(2.1 TFLOP, 11 ms at phi4flash's 25,008 x 2,560 and 16,384 rows), per
block it is BLOCK N d 2 in all.

**XLA's scatter-add**, the transpose of the gather, everywhere else: the
CPU, other dtypes and widths; and the plain form the kernel is tested
against (tests/test_embedding.py).

Precision: duplicates add in float32 and round once (the CPU's scatter
adds them in the table's dtype; the v5e's read as the kernel does, PERF.md
PR 38). Ids as the gather reads them: a negative id counts from the end;
an id outside the table adds nothing.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..reliability.metrics import reliability_metrics
from ..telemetry import names as tnames

KERNEL = "embed_grad"
# vocabulary rows a block: the accumulator is (BLOCK, d) float32
BLOCK = 256
# sorted rows a grid step reads
CHUNK = 256
_LANES = 128
_VMEM_LIMIT = 64 * 1024 * 1024


def pallas_fits(table, ids) -> bool:
    """The kernel's shape rule: a (V, d) table of whole 128-lane tiles,
    bfloat16 or float32, and int32 ids."""
    return (table.ndim == 2 and table.shape[1] % _LANES == 0
            and table.dtype in (jnp.bfloat16, jnp.float32)
            and ids.dtype == jnp.int32)


def lookup(table, ids):
    """table (V, d), ids (...) -> (..., d): `table[ids]`. Its gradient by
    `embed_grad` on a TPU where the shapes fit, else by XLA's scatter."""
    if pallas_fits(table, ids) and jax.devices()[0].platform == "tpu":
        return lookup_pallas(table, ids)
    reliability_metrics.inc(tnames.EMBED_GRAD_ROUTE_XLA)
    return table[ids]


def lookup_pallas(table, ids, interpret=False):
    """`lookup` with its gradient by the kernel whatever the platform
    (tests ask `interpret=True` or `pltpu.InterpretParams()`)."""
    if not pallas_fits(table, ids):
        raise ValueError(f"the kernel wants a (V, d) bfloat16 or float32 "
                         f"table with d in 128s and int32 ids, not "
                         f"{table.shape} {table.dtype}, ids {ids.dtype}")
    reliability_metrics.inc(tnames.EMBED_GRAD_ROUTE_PALLAS)
    return _lookup(table, ids, table.shape[0], interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _lookup(table, ids, vocab, interpret):
    return table[ids]


def _lookup_fwd(table, ids, vocab, interpret):
    return table[ids], ids


def _lookup_bwd(vocab, interpret, ids, g):
    # traced under the lookup's own region (`lm.embed` in the trainer)
    d = g.shape[-1]
    return _grad(ids.reshape(-1), g.reshape(-1, d), vocab, interpret), None


_lookup.defvjp(_lookup_fwd, _lookup_bwd)


def grad_plan(ids, vocab: int):
    """What the kernel walks, for ids (N,) int32 over a vocabulary of
    `vocab`: `order` (N,) the positions sorted by id, `keys` (L,) the
    sorted ids padded to whole chunks (L = N rounded up to CHUNK; padding
    and ids outside the table read past every block), and per grid step
    (G = blocks + chunks, a bound on the steps any ids need) its `block`,
    `chunk`, `live` (the block has rows) and `n_steps` (1,) the steps in
    use. A block's steps are the chunks from its run's first row to its
    last, or one where it has none; steps past `n_steps` do nothing."""
    i32 = jnp.int32
    n = ids.shape[0]
    n_blocks = -(-vocab // BLOCK)
    n_chunks = -(-n // CHUNK)
    past = n_blocks * BLOCK
    ids = jnp.where(ids < 0, ids + vocab, ids)
    ids = jnp.where((ids >= 0) & (ids < vocab), ids, past).astype(i32)
    keys, order = jax.lax.sort((ids, jnp.arange(n, dtype=i32)), num_keys=1)
    keys = jnp.concatenate([keys, jnp.full((n_chunks * CHUNK - n,), past,
                                           i32)])
    # a run's bounds by compare-and-sum: exact, one fused pass
    bounds = jnp.arange(n_blocks + 1, dtype=i32) * BLOCK
    starts = (keys[None, :] < bounds[:, None]).sum(-1, dtype=i32)
    lo, hi = starts[:-1], starts[1:]
    live = hi > lo
    steps = jnp.where(live, (hi - 1) // CHUNK - lo // CHUNK + 1, 1)
    ends = jnp.cumsum(steps).astype(i32)
    s = jnp.arange(n_blocks + n_chunks, dtype=i32)
    block = jnp.minimum((s[:, None] >= ends).sum(-1, dtype=i32),
                        n_blocks - 1)
    # the step's block's row of a small table, by a masked sum
    mine = block[:, None] == jnp.arange(n_blocks, dtype=i32)

    def of_block(a):
        return jnp.where(mine, a, 0).sum(-1, dtype=i32)
    chunk = jnp.minimum(of_block(lo // CHUNK) + s - of_block(ends - steps),
                        n_chunks - 1)
    return {"order": order, "keys": keys, "block": block, "chunk": chunk,
            "live": of_block(live.astype(i32)), "n_steps": ends[-1:]}


def _kernel(block, chunk, live, n_steps, ids_ref, rows_ref, out_ref,
            acc_ref, *, exact: bool):
    del chunk                       # the index maps read it
    s, last_step = pl.program_id(0), pl.num_programs(0) - 1
    b = block[s]

    @pl.when(s < n_steps[0])
    def _():
        first = jnp.logical_or(s == 0, block[jnp.maximum(s - 1, 0)] != b)
        last = jnp.logical_or(s == n_steps[0] - 1,
                              block[jnp.minimum(s + 1, last_step)] != b)

        @pl.when(first)
        def _():
            acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

        @pl.when(live[s] != 0)
        def _():
            here = ids_ref[0] - b * acc_ref.shape[0]            # (1, CHUNK)
            one_hot = (jax.lax.broadcasted_iota(
                jnp.int32, (acc_ref.shape[0], here.shape[1]), 0) == here)
            acc_ref[...] += jax.lax.dot(
                one_hot.astype(rows_ref.dtype), rows_ref[...],
                precision=jax.lax.Precision.HIGHEST if exact else None,
                preferred_element_type=jnp.float32)

        @pl.when(last)
        def _():
            out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def _grad(ids, g, vocab: int, interpret):
    """ids (N,), g (N, d) -> the (vocab, d) sum of g's rows by id."""
    n, d = g.shape
    plan = grad_plan(ids, vocab)
    pad = plan["keys"].shape[0] - n
    rows = g[plan["order"]]        # the gather the lookup is: no fill
    if pad:
        rows = jnp.concatenate([rows, jnp.zeros((pad, d), g.dtype)])
    n_chunks = rows.shape[0] // CHUNK
    grid = plan["block"].shape[0]

    def by_chunk(s, block, chunk, live, n_steps):
        return chunk[s], 0, 0

    def rows_map(s, block, chunk, live, n_steps):
        return chunk[s], 0

    def out_map(s, block, chunk, live, n_steps):
        return block[s], 0

    return pl.pallas_call(
        functools.partial(_kernel, exact=g.dtype == jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(grid,),
            in_specs=[pl.BlockSpec((1, 1, CHUNK), by_chunk),
                      pl.BlockSpec((CHUNK, d), rows_map)],
            out_specs=pl.BlockSpec((BLOCK, d), out_map),
            scratch_shapes=[pltpu.VMEM((BLOCK, d), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((vocab, d), g.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret, name=KERNEL,
    )(plan["block"], plan["chunk"], plan["live"], plan["n_steps"],
      plan["keys"].reshape(n_chunks, 1, CHUNK), rows)
