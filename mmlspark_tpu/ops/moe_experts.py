"""The expert layer's tile loop as one pipelined Pallas kernel a direction.

`models/dnn/moe.py` sorts a layer's (token, expert) pairs by held expert
and cuts each expert's run into tiles of `tile` rows. Its XLA form walks
the tiles in a `while`, one fusion a tile: gather the rows, fetch the
expert's weights, three (forward) or eight (backward) matrix products,
scatter-add, each waiting for the one before. Here the same walk is ONE
kernel a direction, `moe_fwd` and `moe_bwd`:

**The plan is tile-aligned** (`tile_plan`): each held expert's run is
padded by the SORT itself, with dummy pairs (pair ids past N k, weight
0), to whole tiles and to at least one, so tile t is rows t tile ..
(t + 1) tile of the sorted order, every held expert owns a tile (so the
kernel writes every gradient block and XLA zeroes none), and the pairs'
weights and token ids
arrive in sorted order with no gather. The sorted length is the dropless
bound, N k + E tile: no buffer grows with the skew.

**The grid** is (blocks of the expert width, that bound in tiles), both
sequential, the tiles innermost. The number of tiles this routing made,
each tile's expert and count of real rows, and the sorted token ids are
scalar-prefetched into SMEM. Steps past the last tile do nothing, and
their index maps name the blocks already resident, so they fetch nothing.

**Rows move by DMA.** `x` (and `dout`) stay in HBM, reshaped to (N, d /
128, 128) in their own dtype: Mosaic moves a whole (d / 128, 128) slab at
a dynamic first index, not one row of a tiled (N, d) array. Tile t + 1's
rows are started before tile t's products and waited on after them, into
the other half of a double buffer. The result rows are added into `out`
(N, d / 128, 128) float32 (backward: `dx`), which the kernel zeroes
itself at its first step, by reading the tokens' rows, adding, and
writing them back: tile t - 1's writes fly under tile t's first products,
tile t's reads under its last (one token can sit in two adjacent tiles of
different experts, so the reads wait for the writes; inside a tile a
token occurs once).

**Weights stay.** `w_gate`, `w_up`, `w_down` are blocks indexed by the
tile's expert: consecutive tiles of one expert fetch nothing. The
backward's `dw_*` are output blocks indexed the same way: accumulated in
float32 VMEM scratch across an expert's tiles (set, not added to, at its
first) and rounded into the block once, at its last. Where an
expert's blocks are more than VMEM holds (`_width_block`), the expert
width is cut into blocks and the OUTER grid axis walks them: every tile
once a block, rows moved again, nothing recomputed.

Arithmetic is the XLA loop's: bfloat16 operands (as handed in), float32
accumulation, `hidden` rounded once before `w_down`, `y * weight` and the
sum over a token's experts in float32, the backward's `dgate`, `dup`,
`hidden * weight` rounded where the loop rounds them. Only the order of
the float32 additions into `out` differs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..telemetry import names as tnames

KERNEL_FWD = "moe_fwd"
KERNEL_BWD = "moe_bwd"
_LANES = 128
_VMEM_LIMIT = 100 * 1024 * 1024
# what an expert's resident blocks (double-buffered) may take of it
_BLOCK_BUDGET = 64 * 1024 * 1024
# the sorted token ids live in SMEM (1 MiB on a v5e) beside the tile table
_SMEM_IDS = 200_000


def pallas_fits(x, w_gate, top_k: int, tile: int) -> bool:
    """The kernels' shape rule: whole 128-lane tiles of the model and the
    expert width, bfloat16 or float32, whole tiles of pairs, and a sorted
    order that SMEM holds. A bfloat16 row must be a slab Mosaic can move
    whole: d / 128 packed rows in 2s, 4s, 8s or 16s (float32: any)."""
    n, d = x.shape
    rows = d // _LANES
    return (d % _LANES == 0 and w_gate.shape[-1] % _LANES == 0
            and (x.dtype == jnp.float32 or x.dtype == jnp.bfloat16
                 and (rows % 16 == 0 or rows in (2, 4, 8)))
            and w_gate.dtype == x.dtype and (n * top_k) % tile == 0
            and n * top_k + w_gate.shape[0] * tile <= _SMEM_IDS)


def tile_plan(idx, top_p, lo: int, hi: int, tile: int):
    """The tile-aligned layout of one routing. idx (N, k) expert ids over
    all experts, top_p (N, k) their weights, [lo, hi) the experts held.
    Returns arrays over the sorted order of length L = N k + E tile
    (T = L / tile tiles): `pair` (L,) int32 pair ids (dummies count on from
    N k), `weight` (L,) float32 (0 on dummies), `tile_expert` (T,),
    `tile_valid` (T,) the real rows of a tile, `n_tiles` (1,), `counts`
    (E,). Expert e's run is its pairs, then dummies up to whole tiles (one
    tile where it has no pair); absent pairs and unused dummies follow."""
    i32 = jnp.int32
    n_held = hi - lo
    flat = idx.reshape(-1)
    held = (flat >= lo) & (flat < hi)
    key = jnp.where(held, flat - lo, n_held).astype(i32)
    experts = jnp.arange(n_held, dtype=i32)
    counts = (key[:, None] == experts).sum(0, dtype=i32)
    tiles = jnp.maximum((counts + tile - 1) // tile, 1)
    pad = tiles * tile - counts                      # 0 .. tile
    dummy_key = jnp.where(jnp.arange(tile, dtype=i32) < pad[:, None],
                          experts[:, None], n_held + 1).reshape(-1)
    length = flat.size + n_held * tile
    _, pair, weight = jax.lax.sort(
        (jnp.concatenate([key, dummy_key]), jnp.arange(length, dtype=i32),
         jnp.concatenate([top_p.reshape(-1).astype(jnp.float32),
                          jnp.zeros((n_held * tile,), jnp.float32)])),
        num_keys=1, is_stable=True)
    tile_ends = jnp.cumsum(tiles).astype(i32)
    t = jnp.arange(length // tile, dtype=i32)
    tile_expert = jnp.minimum(
        (t[:, None] >= tile_ends).sum(-1, dtype=i32), n_held - 1)
    # a compare-and-sum reads the tile's expert's row of a small table
    mine = tile_expert[:, None] == experts
    into = (t - jnp.where(mine, tile_ends - tiles, 0).sum(-1)) * tile
    tile_valid = jnp.where(
        t < tile_ends[-1],
        jnp.clip(jnp.where(mine, counts, 0).sum(-1) - into, 0, tile), 0)
    return {"pair": pair, "weight": weight, "tile_expert": tile_expert,
            "tile_valid": tile_valid.astype(i32), "n_tiles": tile_ends[-1:],
            "counts": counts}


def _width_block(d: int, f: int, itemsize: int, grads: bool) -> int:
    """The widest block of the expert width (whole lane tiles, a divisor)
    whose resident blocks fit `_BLOCK_BUDGET`: three weight matrices
    double-buffered and, in the backward, their gradients' float32
    accumulators and double-buffered output blocks too."""
    each = 3 * d * (2 * itemsize + ((4 + 2 * itemsize) if grads else 0))
    for blocks in range(1, f // _LANES + 1):
        if f % blocks == 0 and (f // blocks) % _LANES == 0 \
                and each * (f // blocks) <= _BLOCK_BUDGET:
            return f // blocks
    return _LANES


# ------------------------------------------------------------ the kernels

def _dot(a, b, contract, exact: bool):
    """a, b contracted over dimension `contract[0]` of a and `contract[1]`
    of b, float32 accumulation; `exact`: the operands are float32 and stay
    so (Mosaic's default rounds them to one bfloat16 pass, PR 21)."""
    return jax.lax.dot_general(
        a, b, (((contract[0],), (contract[1],)), ((), ())),
        precision=jax.lax.Precision.HIGHEST if exact else None,
        preferred_element_type=jnp.float32)


def _eye(tile: int):
    return (jax.lax.broadcasted_iota(jnp.int32, (tile, tile), 0)
            == jax.lax.broadcasted_iota(jnp.int32, (tile, tile), 1))


def _down_rows(row, eye):
    """(1, tile) along the lanes -> (tile, 1) down the sublanes: a masked
    sum, exact, where Mosaic has no transpose of a row."""
    return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)


def _along_lanes(col, eye):
    return jnp.sum(jnp.where(eye, col, 0.0), axis=0, keepdims=True)


def _by_eights(count, row):
    """row(r) for r in [0, count): eight rows a loop step, so that the
    scalar slots of a bundle fill (a copy's start is 5 to 6 bundles so,
    10 to 11 alone), then the rest."""
    def eight(i, carry):
        for u in range(8):
            row(i * 8 + u)
        return carry

    def one(r, carry):
        row(r)
        return carry
    jax.lax.fori_loop(0, count // 8, eight, 0)
    jax.lax.fori_loop(count // 8 * 8, count, one, 0)


def _start_rows(hbm, tok, buf, sem, first, count, put: bool = False):
    """Start one copy a row: HBM row tok[first + r] -> buf[r], or with
    `put` the other way."""
    def row(r):
        ends = hbm.at[tok[first + r]], buf.at[r]
        pltpu.make_async_copy(*(ends[::-1] if put else ends), sem).start()
    _by_eights(count, row)


def _wait_rows(hbm, buf, sem, count):
    """Wait for `count` row copies on `sem` (every row is as large)."""
    _by_eights(count, lambda r: pltpu.make_async_copy(
        hbm.at[0], buf.at[0], sem).wait())


def _fill_zeros(hbm, buf, sem, wait: bool):
    """Zero `hbm` (N, rows, 128) from `buf` (tile, rows, 128), which holds
    zeros: whole tiles of rows, then the rest; `wait`: wait for those
    copies instead of starting them."""
    tile, n = buf.shape[0], hbm.shape[0]

    def go(copy):
        copy.wait() if wait else copy.start()

    def one(i, carry):
        go(pltpu.make_async_copy(buf, hbm.at[pl.ds(i * tile, tile)], sem))
        return carry
    jax.lax.fori_loop(0, n // tile, one, 0)
    if n % tile:
        go(pltpu.make_async_copy(buf.at[pl.ds(0, n % tile)],
                                 hbm.at[pl.ds(n - n % tile, n % tile)], sem))


def _tile_rows(buf, rows: int, dtype):
    """A buffer of token slabs (tile, d / 128, 128) as the tile's (tile, d)
    matrix: slab row c of every token is lanes 128 c .. of the matrix."""
    return jnp.concatenate([buf[:, c, :] for c in range(rows)],
                           axis=1).astype(dtype)


def _add_rows(buf, rows: int, y):
    for c in range(rows):
        buf[:, c, :] = buf[:, c, :] + y[:, c * _LANES:(c + 1) * _LANES]


def _fwd_kernel(meta, tile_expert, tile_valid, tok, x_hbm, pw_ref, wg_ref,
                wu_ref, wd_ref, out_hbm, xbuf, obuf, sem, *,
                tile: int, rows: int, exact: bool):
    del tile_expert                # the index maps read it
    t, n_tiles = pl.program_id(1), meta[0]
    first_step = jnp.logical_and(pl.program_id(0) == 0, t == 0)
    cdt = wg_ref.dtype

    @pl.when(t < n_tiles)
    def _():
        slot = t % 2

        @pl.when(first_step)
        def _():
            obuf[...] = jnp.zeros(obuf.shape, jnp.float32)
            _fill_zeros(out_hbm, obuf, sem.at[3], wait=False)

        @pl.when(t == 0)
        def _():
            _start_rows(x_hbm, tok, xbuf.at[0], sem.at[0], 0, tile_valid[0])

        @pl.when(t + 1 < n_tiles)
        def _():
            _start_rows(x_hbm, tok, xbuf.at[1 - slot], sem.at[1 - slot],
                        (t + 1) * tile, tile_valid[t + 1])

        _wait_rows(x_hbm, xbuf.at[slot], sem.at[slot], tile_valid[t])
        xt = _tile_rows(xbuf.at[slot], rows, cdt)
        gate = _dot(xt, wg_ref[0], (1, 0), exact)
        up = _dot(xt, wu_ref[0], (1, 0), exact)
        hidden = (jax.nn.silu(gate) * up).astype(cdt)

        # the tile before may hold one of this tile's tokens
        @pl.when(t > 0)
        def _():
            _wait_rows(out_hbm, obuf, sem.at[3], tile_valid[t - 1])

        @pl.when(first_step)
        def _():
            _fill_zeros(out_hbm, obuf, sem.at[3], wait=True)

        _start_rows(out_hbm, tok, obuf, sem.at[2], t * tile, tile_valid[t])
        y = _dot(hidden, wd_ref[0], (1, 0), exact)
        y = y * _down_rows(pw_ref[0], _eye(tile))
        _wait_rows(out_hbm, obuf, sem.at[2], tile_valid[t])
        _add_rows(obuf, rows, y)
        _start_rows(out_hbm, tok, obuf, sem.at[3], t * tile, tile_valid[t],
                    put=True)

        @pl.when(t == n_tiles - 1)
        def _():
            _wait_rows(out_hbm, obuf, sem.at[3], tile_valid[t])


def _bwd_kernel(meta, tile_expert, tile_valid, tok, x_hbm, dy_hbm, pw_ref,
                wg_ref, wu_ref, wd_ref, dx_hbm, dpw_ref, dwg_ref, dwu_ref,
                dwd_ref, xbuf, dybuf, obuf, dwg_acc, dwu_acc, dwd_acc, sem,
                *, tile: int, rows: int, exact: bool):
    f32 = jnp.float32
    t, n_tiles = pl.program_id(1), meta[0]
    first_step = jnp.logical_and(pl.program_id(0) == 0, t == 0)
    cdt = wg_ref.dtype

    def start_both(step, slot):
        for hbm, buf, s in ((x_hbm, xbuf, 0), (dy_hbm, dybuf, 2)):
            _start_rows(hbm, tok, buf.at[slot], sem.at[s + slot],
                        step * tile, tile_valid[step])

    @pl.when(t < n_tiles)
    def _():
        slot = t % 2

        @pl.when(first_step)
        def _():
            obuf[...] = jnp.zeros(obuf.shape, f32)
            _fill_zeros(dx_hbm, obuf, sem.at[5], wait=False)

        @pl.when(t == 0)
        def _():
            # rows no copy fills meet a weight of 0: they must be finite
            xbuf[...] = jnp.zeros(xbuf.shape, xbuf.dtype)
            dybuf[...] = jnp.zeros(dybuf.shape, dybuf.dtype)
            start_both(0, 0)

        @pl.when(t + 1 < n_tiles)
        def _():
            start_both(t + 1, 1 - slot)

        _wait_rows(x_hbm, xbuf.at[slot], sem.at[slot], tile_valid[t])
        _wait_rows(dy_hbm, dybuf.at[slot], sem.at[2 + slot], tile_valid[t])
        xt = _tile_rows(xbuf.at[slot], rows, cdt)
        dy = _tile_rows(dybuf.at[slot], rows, cdt)
        wg, wu, wd = wg_ref[0], wu_ref[0], wd_ref[0]
        gate = _dot(xt, wg, (1, 0), exact)
        up = _dot(xt, wu, (1, 0), exact)
        sig = jax.nn.sigmoid(gate)
        act = gate * sig
        hidden = act * up
        dh_unweighted = _dot(dy, wd, (1, 1), exact)
        eye = _eye(tile)
        weight = _down_rows(pw_ref[0], eye)
        dpw_ref[0, 0] = _along_lanes(
            (hidden * dh_unweighted).sum(-1, keepdims=True), eye)
        dh = dh_unweighted * weight
        dwd = _dot((hidden * weight).astype(cdt), dy, (0, 0), exact)
        dup = (dh * act).astype(cdt)
        dgate = (dh * up * (sig + act * (1.0 - sig))).astype(cdt)
        dwg = _dot(xt, dgate, (0, 0), exact)
        dwu = _dot(xt, dup, (0, 0), exact)
        # an expert's gradients: float32 across its tiles, rounded once
        first = jnp.logical_or(
            t == 0, tile_expert[t] != tile_expert[jnp.maximum(t - 1, 0)])
        last = jnp.logical_or(
            t == n_tiles - 1,
            tile_expert[t] != tile_expert[jnp.minimum(t + 1, n_tiles - 1)])

        @pl.when(first)
        def _():
            dwg_acc[...], dwu_acc[...], dwd_acc[...] = dwg, dwu, dwd

        @pl.when(jnp.logical_not(first))
        def _():
            dwg_acc[...] += dwg
            dwu_acc[...] += dwu
            dwd_acc[...] += dwd

        @pl.when(last)
        def _():
            dwg_ref[0] = dwg_acc[...].astype(dwg_ref.dtype)
            dwu_ref[0] = dwu_acc[...].astype(dwu_ref.dtype)
            dwd_ref[0] = dwd_acc[...].astype(dwd_ref.dtype)

        @pl.when(t > 0)
        def _():
            _wait_rows(dx_hbm, obuf, sem.at[5], tile_valid[t - 1])

        @pl.when(first_step)
        def _():
            _fill_zeros(dx_hbm, obuf, sem.at[5], wait=True)

        _start_rows(dx_hbm, tok, obuf, sem.at[4], t * tile, tile_valid[t])
        dxt = _dot(dgate, wg, (1, 1), exact) + _dot(dup, wu, (1, 1), exact)
        _wait_rows(dx_hbm, obuf, sem.at[4], tile_valid[t])
        _add_rows(obuf, rows, dxt)
        _start_rows(dx_hbm, tok, obuf, sem.at[5], t * tile, tile_valid[t],
                    put=True)

        @pl.when(t == n_tiles - 1)
        def _():
            _wait_rows(dx_hbm, obuf, sem.at[5], tile_valid[t])


# ------------------------------------------------------------- the calls

def _slabs(a):
    """(N, d) -> (N, d / 128, 128): a row as one slab a DMA moves."""
    return a.reshape(a.shape[0], -1, _LANES)


def _prefetched(plan, n_pairs: int, top_k: int):
    """What both kernels read from SMEM, and the pairs' weights a tile."""
    tile = plan["pair"].shape[0] // plan["tile_expert"].shape[0]
    pair = plan["pair"]
    tok = jnp.where(pair < n_pairs, pair // top_k, 0).astype(jnp.int32)
    return ((plan["n_tiles"], plan["tile_expert"], plan["tile_valid"], tok),
            plan["weight"].reshape(-1, 1, tile), tile)


def _specs(d: int, block: int, tile: int):
    def last(t, meta):
        return jnp.minimum(t, meta[0] - 1)

    def w_in(j, t, meta, te, tv, tok):
        return te[last(t, meta)], 0, j

    def w_out(j, t, meta, te, tv, tok):
        return te[last(t, meta)], j, 0

    return {"any": pl.BlockSpec(memory_space=pl.ANY),
            "pw": pl.BlockSpec((1, 1, tile), lambda j, t, meta, *_:
                               (last(t, meta), 0, 0)),
            "dpw": pl.BlockSpec((1, 1, 1, tile), lambda j, t, meta, *_:
                                (j, last(t, meta), 0, 0)),
            "w_in": pl.BlockSpec((1, d, block), w_in),
            "w_out": pl.BlockSpec((1, block, d), w_out)}


def _params():
    # every row copy's ends are in range by construction (`_prefetched`
    # clips the ids; the loops stop at a tile's real rows): Mosaic's own
    # check of both ends is half of a copy's 37 bundles
    return pltpu.CompilerParams(
        dimension_semantics=("arbitrary", "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT, disable_bounds_checks=True)


@functools.partial(jax.jit, static_argnames=("top_k", "interpret"))
def _forward(x, w_gate, w_up, w_down, plan, top_k: int, interpret):
    n, d = x.shape
    f = w_gate.shape[-1]
    with jax.named_scope(tnames.LM_MOE_DISPATCH):
        scalars, pw, tile = _prefetched(plan, n * top_k, top_k)
        x3 = _slabs(x)
    rows = d // _LANES
    block = _width_block(d, f, x.dtype.itemsize, grads=False)
    spec = _specs(d, block, tile)
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, tile=tile, rows=rows,
                          exact=x.dtype == jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(f // block, pw.shape[0]),
            in_specs=[spec["any"], spec["pw"], spec["w_in"], spec["w_in"],
                      spec["w_out"]],
            out_specs=spec["any"],
            scratch_shapes=[pltpu.VMEM((2, tile, rows, _LANES), x.dtype),
                            pltpu.VMEM((tile, rows, _LANES), jnp.float32),
                            pltpu.SemaphoreType.DMA((4,))]),
        out_shape=jax.ShapeDtypeStruct(x3.shape, jnp.float32),
        compiler_params=_params(), interpret=interpret, name=KERNEL_FWD,
    )(*scalars, x3, pw, w_gate, w_up, w_down)
    return out.reshape(n, d).astype(x.dtype)


@functools.partial(jax.jit, static_argnames=("top_k", "interpret"))
def _backward(x, w_gate, w_up, w_down, plan, dout, top_k: int, interpret):
    f32 = jnp.float32
    n, d = x.shape
    n_held, _, f = w_gate.shape
    with jax.named_scope(tnames.LM_MOE_DISPATCH):
        scalars, pw, tile = _prefetched(plan, n * top_k, top_k)
        x3, dy3 = _slabs(x), _slabs(dout)
    rows = d // _LANES
    block = _width_block(d, f, x.dtype.itemsize, grads=True)
    n_blocks, n_tiles = f // block, pw.shape[0]
    spec = _specs(d, block, tile)
    slabs = pltpu.VMEM((2, tile, rows, _LANES), x.dtype)
    dx, dpw, dwg, dwu, dwd = pl.pallas_call(
        functools.partial(_bwd_kernel, tile=tile, rows=rows,
                          exact=x.dtype == f32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(n_blocks, n_tiles),
            in_specs=[spec["any"], spec["any"], spec["pw"], spec["w_in"],
                      spec["w_in"], spec["w_out"]],
            out_specs=[spec["any"], spec["dpw"], spec["w_in"], spec["w_in"],
                       spec["w_out"]],
            scratch_shapes=[slabs, slabs,
                            pltpu.VMEM((tile, rows, _LANES), f32),
                            pltpu.VMEM((d, block), f32),
                            pltpu.VMEM((d, block), f32),
                            pltpu.VMEM((block, d), f32),
                            pltpu.SemaphoreType.DMA((6,))]),
        out_shape=[jax.ShapeDtypeStruct(x3.shape, f32),
                   jax.ShapeDtypeStruct((n_blocks, n_tiles, 1, tile), f32),
                   jax.ShapeDtypeStruct(w_gate.shape, w_gate.dtype),
                   jax.ShapeDtypeStruct(w_up.shape, w_up.dtype),
                   jax.ShapeDtypeStruct(w_down.shape, w_down.dtype)],
        compiler_params=_params(), interpret=interpret, name=KERNEL_BWD,
    )(*scalars, x3, dy3, pw, w_gate, w_up, w_down)
    with jax.named_scope(tnames.LM_MOE_DISPATCH):
        # the pairs' gradient leaves in sorted order; ONE sort by pair id
        # puts it back (a scatter of N k scalars costs a millisecond)
        pair = plan["pair"]
        real = (pair < n * top_k) & (
            jnp.arange(pair.size) // tile < plan["n_tiles"][0])
        dweight = jnp.where(real, dpw.sum(0).reshape(-1), 0.0)
        _, dp = jax.lax.sort((pair, dweight), num_keys=1)
        dp = dp[:n * top_k].reshape(n, top_k)
    return dx.reshape(n, d).astype(x.dtype), dp, dwg, dwu, dwd


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def experts_pallas(x, top_p, w_gate, w_up, w_down, plan, interpret=False):
    """The routed sum of the held experts, x (N, d) -> (N, d), down the
    kernels whatever the platform (tests ask `interpret=True`). `plan`:
    `tile_plan`'s, made from the same `top_p`; the pairs' weights are read
    from it and their gradient is returned for `top_p`."""
    return _forward(x, w_gate, w_up, w_down, plan, top_p.shape[1], interpret)


def _experts_pallas_fwd(x, top_p, w_gate, w_up, w_down, plan, interpret):
    out = _forward(x, w_gate, w_up, w_down, plan, top_p.shape[1], interpret)
    return out, (x, top_p, w_gate, w_up, w_down, plan)


def _experts_pallas_bwd(interpret, res, dout):
    x, top_p, w_gate, w_up, w_down, plan = res
    dx, dp, dwg, dwu, dwd = _backward(x, w_gate, w_up, w_down, plan, dout,
                                      top_p.shape[1], interpret)
    return dx, dp.astype(top_p.dtype), dwg, dwu, dwd, None


experts_pallas.defvjp(_experts_pallas_fwd, _experts_pallas_bwd)
