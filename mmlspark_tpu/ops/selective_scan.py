"""The selective state-space scan (Mamba's recurrence).

A channel i of `Di` carries a state of N numbers, zero at the start of a
sequence; for each position t, with a_t = exp(dt_t[i] A[i, n]):

    s_t[i, n] = a_t s_{t-1}[i, n] + dt_t[i] v_t[i] B_t[n]
    y_t[i]    = sum_n s_t[i, n] C_t[n] + D[i] v_t[i]

The state is diagonal: no matrix product anywhere, one `exp` and six
multiply-adds a (position, channel, state) on the vector and transcendental
units. The (S, Di, N) states never reach HBM in either form.

Two implementations of that one function, chosen by `selective_scan` from
what it can observe (the platform and the shapes), with no knob, and
counted (`ssm.scan.route.pallas` / `ssm.scan.route.xla`):

**The Pallas kernels** (`ssm_fwd`, `ssm_bwd`), on a TPU when Di is a
multiple of 128 and N of 8. Slabs in, slab out: v, dt (B, S, Di), B, C
(B, S, N) -> y (B, S, Di). Grid (batch, chunk of `CHUNK` positions, block
of `LANES` channels), chunk and block sequential, the block innermost so
that a chunk's B and C are fetched once. In the registers a state is
(N, 128): the N states down the sublanes, 128 channels along the lanes. A
position's dt and dt v are a row of a (16, .) strip, turned down the
sublanes by a broadcast; its B and C must lie down the sublanes and be the
same in every lane, which no cheap in-kernel move gives, so the wrapper
hands them in already broadcast, (B, S, N, 128) float32 (67 MB a sequence
of 8,192: beside v, dt and y's 336 MB, and under the kernels' arithmetic).
The state a (batch, block) has reached lives in a VMEM scratch across the
chunks. The forward writes the state each chunk STARTED from ((S / CHUNK)
x N x Di float32 a sequence); the backward walks the chunks from the last
to the first, remakes a chunk's states in VMEM from that boundary, and
walks the chunk in reverse with the state's cotangent in a second scratch.
dB and dC are sums over channels: the kernel adds the blocks' (N, 128)
partial products into one (B, S, N, 128) output and the wrapper sums the
lanes. Strips of 16 rows in a `fori_loop`, the 16 positions unrolled.

**The XLA form** (`_scan_xla`): a `lax.scan` over chunks with the chunk's
states made inside it by `lax.associative_scan` and the chunk recomputed
in the backward pass; the gradient by autodiff. The fallback for every
other platform and shape, and the plain form the kernels are tested
against (tests/test_selective_scan.py).

Precision, both forms: dt, exp(dt A), the state, its cotangent and every
sum float32; v, B, C come in the caller's dtype and y leaves in v's.
The differentiated forward tags y and the boundary states
`telemetry.names.KEEP_SSM`: under a `jax.checkpoint` policy that saves
that name, as the trainer's does (`names.REMAT_RESIDUALS`), a checkpointed
mixer does not run `ssm_fwd` a second time.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..reliability.metrics import reliability_metrics
from ..telemetry import names as tnames

CHUNK = 128
# channels a grid step: two lane tiles' states, decays and cotangents stay
# in registers beside a strip's rows
LANES = 256
_TILE = 128
_STRIP = 16
_XLA_CHUNK = 64
_VMEM_LIMIT = 64 * 1024 * 1024

KERNEL_FWD = "ssm_fwd"
KERNEL_BWD = "ssm_bwd"


def selective_scan(v, dt, a, b, c, d):
    """v, dt (B, S, Di), a (Di, N) negative, b, c (B, S, N), d (Di,) ->
    y (B, S, Di) in v's dtype. S need not divide by the chunk: the tail is
    padded with positions of dt = 0, which leave the state as it is."""
    if pallas_fits(v, b) and jax.devices()[0].platform == "tpu":
        return selective_scan_pallas(v, dt, a, b, c, d)
    reliability_metrics.inc(tnames.SSM_SCAN_ROUTE_XLA)
    return _scan_xla(v, dt, a, b, c, d)


def pallas_fits(v, b) -> bool:
    """The kernels' shape rule: whole 128-lane tiles of channels, whole
    sublane tiles of states, bfloat16 or float32."""
    return (v.shape[-1] % _TILE == 0 and b.shape[-1] % 8 == 0
            and v.dtype in (jnp.bfloat16, jnp.float32))


def _scan_xla(v, dt, a, b, c, d, chunk: int = _XLA_CHUNK):
    f32 = jnp.float32
    bsz, seq, di = v.shape
    pad = (-seq) % chunk
    n = (seq + pad) // chunk
    a = a.astype(f32)

    def chunks(t):                    # (B, S, X) -> (n, B, C, X)
        t = jnp.pad(t, ((0, 0), (0, pad), (0, 0)))
        return jnp.moveaxis(t.reshape(bsz, n, chunk, t.shape[-1]), 1, 0)

    def combine(left, right):
        return left[0] * right[0], right[0] * left[1] + right[1]

    @jax.checkpoint
    def step(state, xs):              # state (B, Di, N)
        v_c, dt_c, b_c, c_c = xs
        dt32 = dt_c.astype(f32)
        decay = jnp.exp(dt32[..., None] * a)              # (B, C, Di, N)
        write = (dt32 * v_c.astype(f32))[..., None] \
            * b_c.astype(f32)[:, :, None, :]
        decay_to, written = jax.lax.associative_scan(
            combine, (decay, write), axis=1)
        states = decay_to * state[:, None] + written
        y = jnp.einsum("bcin,bcn->bci", states, c_c.astype(f32))
        return states[:, -1], y

    _, y = jax.lax.scan(step, jnp.zeros((bsz, di, a.shape[-1]), f32),
                        (chunks(v), chunks(dt), chunks(b), chunks(c)))
    y = jnp.moveaxis(y, 0, 1).reshape(bsz, n * chunk, di)[:, :seq]
    return (y + d.astype(f32) * v.astype(f32)).astype(v.dtype)


# ------------------------------------------------------------ the kernels

def _tiles(lanes: int):
    return [pl.ds(k * _TILE, _TILE) for k in range(lanes // _TILE)]


def _put_row(rows, r: int, row):
    """`rows` (8, 128) with sublane r % 8 replaced by `row` (1, 128): Mosaic
    stores no single row at a dynamic offset, so eight positions' rows are
    gathered in a register and stored as one aligned tile."""
    sub = jax.lax.broadcasted_iota(jnp.int32, rows.shape, 0)
    return jnp.where(sub == r % 8, row, rows)


def _row(strip, r: int, k: int):
    """Position r of a (16, lanes) strip over lane tile k, (1, 128)."""
    return strip[r:r + 1, k * _TILE:(k + 1) * _TILE]


def _advance(s, decay, x_r, bb):
    """s_t from s_{t-1}: decay * s + (dt v) B, (N, 128)."""
    return decay * s + x_r * bb


def _strip_rows(refs, r0):
    """Rows [r0, r0 + 16) of each (1, C, lanes) block, float32."""
    return [ref[0, pl.ds(r0, _STRIP), :].astype(jnp.float32) for ref in refs]


def _fwd_kernel(v_ref, dt_ref, bx_ref, cx_ref, a_ref, d_ref, y_ref, s0_ref,
                state, y_rows, *, chunk: int, lanes: int):
    j, blk = pl.program_id(1), pl.program_id(2)
    tiles = _tiles(lanes)

    @pl.when(j == 0)
    def _start():
        state[blk] = jnp.zeros(state.shape[1:], state.dtype)

    s0_ref[0, 0] = state[blk]
    a = [a_ref[:, t] for t in tiles]

    def strip(i, s):
        r0 = pl.multiple_of(i * _STRIP, _STRIP)
        v16, dt16 = _strip_rows((v_ref, dt_ref), r0)
        x16 = dt16 * v16
        s = list(s)
        y8 = [jnp.zeros((8, _TILE), jnp.float32) for _ in tiles]
        for r in range(_STRIP):
            bb, cc = bx_ref[0, r0 + r], cx_ref[0, r0 + r]     # (N, 128)
            for k, t in enumerate(tiles):
                s[k] = _advance(s[k], jnp.exp(_row(dt16, r, k) * a[k]),
                                _row(x16, r, k), bb)
                y8[k] = _put_row(y8[k], r, jnp.sum(s[k] * cc, axis=0,
                                                   keepdims=True))
                if r % 8 == 7:
                    y_rows[pl.ds(r0 + r - 7, 8), t] = y8[k]
        y16 = y_rows[pl.ds(r0, _STRIP), :] + d_ref[...] * v16
        y_ref[0, pl.ds(r0, _STRIP), :] = y16.astype(y_ref.dtype)
        return tuple(s)

    s = jax.lax.fori_loop(0, chunk // _STRIP, strip,
                          tuple(state[blk, :, t] for t in tiles))
    for k, t in enumerate(tiles):
        state[blk, :, t] = s[k]


def _bwd_kernel(v_ref, dt_ref, bx_ref, cx_ref, a_ref, d_ref, dy_ref, s0_ref,
                dv_ref, ddt_ref, dbx_ref, dcx_ref, da_ref,
                carried, before, dv_rows, ddt_rows, *, chunk: int,
                lanes: int):
    """A chunk's reverse pass. With g_t the cotangent of s_t (what y_t and
    every later position ask of it):
        g_t = dy_t C_t + a_{t+1} g_{t+1}
        dC_t = sum_i dy_t s_t;  dB_t = sum_i g_t x_t;  dx_t = sum_n g_t B_t
        da_t = g_t s_{t-1};  ddt_t = dx_t v_t + sum_n da_t a_t A
        dv_t = dx_t dt_t + D dy_t;  dA = sum_t da_t a_t dt_t
    `carried` holds a_{t+1} g_{t+1} across chunks, `before` the chunk's
    s_{t-1}."""
    f32 = jnp.float32
    j, blk = pl.program_id(1), pl.program_id(2)
    tiles = _tiles(lanes)
    n_tiles = len(tiles)
    n_strips = chunk // _STRIP

    @pl.when(j == 0)
    def _start():
        carried[blk] = jnp.zeros(carried.shape[1:], f32)
        da_ref[0, blk] = jnp.zeros(da_ref.shape[2:], f32)

    @pl.when(blk == 0)
    def _first_block():
        def zero(t, _):
            dbx_ref[0, t] = jnp.zeros(dbx_ref.shape[2:], f32)
            dcx_ref[0, t] = jnp.zeros(dcx_ref.shape[2:], f32)
            return _
        jax.lax.fori_loop(0, chunk, zero, 0)

    a = [a_ref[:, t] for t in tiles]

    def remake(i, s):
        r0 = pl.multiple_of(i * _STRIP, _STRIP)
        v16, dt16 = _strip_rows((v_ref, dt_ref), r0)
        x16 = dt16 * v16
        s = list(s)
        for r in range(_STRIP):
            bb = bx_ref[0, r0 + r]
            for k, t in enumerate(tiles):
                before[r0 + r, :, t] = s[k]
                s[k] = _advance(s[k], jnp.exp(_row(dt16, r, k) * a[k]),
                                _row(x16, r, k), bb)
        return tuple(s)

    jax.lax.fori_loop(0, n_strips, remake,
                      tuple(s0_ref[0, 0, :, t] for t in tiles))

    def strip(i, carry):
        r0 = pl.multiple_of((n_strips - 1 - i) * _STRIP, _STRIP)
        v16, dt16, dy16 = _strip_rows((v_ref, dt_ref, dy_ref), r0)
        x16 = dt16 * v16
        g_next, d_a = list(carry[:n_tiles]), list(carry[n_tiles:])
        dv8 = [jnp.zeros((8, _TILE), f32) for _ in tiles]
        ddt8 = [jnp.zeros((8, _TILE), f32) for _ in tiles]
        for r in reversed(range(_STRIP)):
            bb, cc = bx_ref[0, r0 + r], cx_ref[0, r0 + r]
            d_b = jnp.zeros_like(bb)
            d_c = jnp.zeros_like(cc)
            for k, t in enumerate(tiles):
                dt_r, x_r, dy_r = (_row(strip, r, k)
                                   for strip in (dt16, x16, dy16))
                decay = jnp.exp(dt_r * a[k])
                s_before = before[r0 + r, :, t]
                g = dy_r * cc + g_next[k]
                d_c = d_c + dy_r * _advance(s_before, decay, x_r, bb)
                d_b = d_b + g * x_r
                d_x = jnp.sum(g * bb, axis=0, keepdims=True)
                d_decay = g * s_before * decay
                ddt8[k] = _put_row(
                    ddt8[k], r, d_x * _row(v16, r, k)
                    + jnp.sum(d_decay * a[k], axis=0, keepdims=True))
                dv8[k] = _put_row(dv8[k], r, d_x * dt_r)
                if r % 8 == 0:
                    ddt_rows[pl.ds(r0 + r, 8), t] = ddt8[k]
                    dv_rows[pl.ds(r0 + r, 8), t] = dv8[k]
                d_a[k] = d_a[k] + d_decay * dt_r
                g_next[k] = decay * g
            dbx_ref[0, r0 + r] = dbx_ref[0, r0 + r] + d_b
            dcx_ref[0, r0 + r] = dcx_ref[0, r0 + r] + d_c
        rows = pl.ds(r0, _STRIP)
        dv_ref[0, rows, :] = (dv_rows[rows, :] + d_ref[...] * dy16
                              ).astype(dv_ref.dtype)
        ddt_ref[0, rows, :] = ddt_rows[rows, :].astype(ddt_ref.dtype)
        return tuple(g_next) + tuple(d_a)

    zeros = tuple(jnp.zeros(a[0].shape, f32) for _ in tiles)
    out = jax.lax.fori_loop(
        0, n_strips, strip,
        tuple(carried[blk, :, t] for t in tiles) + zeros)
    for k, t in enumerate(tiles):
        carried[blk, :, t] = out[k]
        da_ref[0, blk, :, t] = da_ref[0, blk, :, t] + out[n_tiles + k]


def _geometry(v, chunk: int):
    bsz, seq, di = v.shape
    lanes = next(w for w in (LANES, _TILE) if di % w == 0)
    pad = (-seq) % chunk
    return bsz, seq, di, lanes, pad, (seq + pad) // chunk


def _padded(pad: int, *arrays):
    if not pad:
        return arrays
    return tuple(jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
                 for t in arrays)


def _along_lanes(t):
    """(B, S, N) -> (B, S, N, 128) float32, a value in every lane."""
    return jnp.broadcast_to(t.astype(jnp.float32)[..., None],
                            t.shape + (_TILE,))


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT)


def _forward(v, dt, a_t, b, c, d, chunk: int, interpret: bool):
    """-> (y (B, S, Di), the state each chunk started from
    (B, chunks, N, Di) float32). a_t: (N, Di)."""
    f32 = jnp.float32
    bsz, seq, di, lanes, pad, n = _geometry(v, chunk)
    n_state = a_t.shape[0]
    v_p, dt_p, b_p, c_p = _padded(pad, v, dt.astype(f32), b, c)
    slab = pl.BlockSpec((1, chunk, lanes), lambda i, j, k: (i, j, k))
    wide = pl.BlockSpec((1, chunk, n_state, _TILE),
                        lambda i, j, k: (i, j, 0, 0))
    y, s0 = pl.pallas_call(
        functools.partial(_fwd_kernel, chunk=chunk, lanes=lanes),
        grid=(bsz, n, di // lanes),
        in_specs=[slab, slab, wide, wide,
                  pl.BlockSpec((n_state, lanes), lambda i, j, k: (0, k)),
                  pl.BlockSpec((1, lanes), lambda i, j, k: (0, k))],
        out_specs=[slab, pl.BlockSpec((1, 1, n_state, lanes),
                                      lambda i, j, k: (i, j, 0, k))],
        out_shape=[jax.ShapeDtypeStruct(v_p.shape, v.dtype),
                   jax.ShapeDtypeStruct((bsz, n, n_state, di), f32)],
        scratch_shapes=[pltpu.VMEM((di // lanes, n_state, lanes), f32),
                        pltpu.VMEM((chunk, lanes), f32)],
        compiler_params=_params(), interpret=interpret, name=KERNEL_FWD,
    )(v_p, dt_p, _along_lanes(b_p), _along_lanes(c_p), a_t.astype(f32),
      d.astype(f32)[None])
    return y[:, :seq], s0


def _backward(v, dt, a_t, b, c, d, s0, dy, chunk: int, interpret: bool):
    f32 = jnp.float32
    bsz, seq, di, lanes, pad, n = _geometry(v, chunk)
    n_state = a_t.shape[0]
    n_blk = di // lanes
    v_p, dt_p, b_p, c_p, dy_p = _padded(pad, v, dt.astype(f32), b, c, dy)
    # the grid walks the chunks from the last to the first
    slab = pl.BlockSpec((1, chunk, lanes), lambda i, j, k: (i, n - 1 - j, k))
    wide = pl.BlockSpec((1, chunk, n_state, _TILE),
                        lambda i, j, k: (i, n - 1 - j, 0, 0))
    wide_shape = jax.ShapeDtypeStruct(b_p.shape + (_TILE,), f32)
    dv, ddt, dbx, dcx, da = pl.pallas_call(
        functools.partial(_bwd_kernel, chunk=chunk, lanes=lanes),
        grid=(bsz, n, n_blk),
        in_specs=[slab, slab, wide, wide,
                  pl.BlockSpec((n_state, lanes), lambda i, j, k: (0, k)),
                  pl.BlockSpec((1, lanes), lambda i, j, k: (0, k)),
                  slab, pl.BlockSpec((1, 1, n_state, lanes),
                                     lambda i, j, k: (i, n - 1 - j, 0, k))],
        out_specs=[slab, slab, wide, wide,
                   pl.BlockSpec((1, n_blk, n_state, lanes),
                                lambda i, j, k: (i, 0, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct(v_p.shape, v.dtype),
                   jax.ShapeDtypeStruct(v_p.shape, f32),
                   wide_shape, wide_shape,
                   jax.ShapeDtypeStruct((bsz, n_blk, n_state, lanes), f32)],
        scratch_shapes=[pltpu.VMEM((n_blk, n_state, lanes), f32),
                        pltpu.VMEM((chunk, n_state, lanes), f32),
                        pltpu.VMEM((chunk, lanes), f32),
                        pltpu.VMEM((chunk, lanes), f32)],
        compiler_params=_params(), interpret=interpret, name=KERNEL_BWD,
    )(v_p, dt_p, _along_lanes(b_p), _along_lanes(c_p), a_t.astype(f32),
      d.astype(f32)[None], dy_p, s0)
    d_a = jnp.moveaxis(da.sum(0), 0, 1).reshape(n_state, di)
    d_d = jnp.einsum("bsi,bsi->i", dy.astype(f32), v.astype(f32))
    return (dv[:, :seq], ddt[:, :seq].astype(dt.dtype), d_a,
            dbx[:, :seq].sum(-1).astype(b.dtype),
            dcx[:, :seq].sum(-1).astype(c.dtype), d_d)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _scan_pallas(v, dt, a_t, b, c, d, chunk, interpret):
    return _forward(v, dt, a_t, b, c, d, chunk, interpret)[0]


def _scan_pallas_fwd(v, dt, a_t, b, c, d, chunk, interpret):
    # named inside the rule, as the flash forward's residuals are: a policy
    # that keeps them leaves the recomputed rule no use for `ssm_fwd`
    y, s0 = checkpoint_name(_forward(v, dt, a_t, b, c, d, chunk, interpret),
                            tnames.KEEP_SSM)
    return y, (v, dt, a_t, b, c, d, s0)


def _scan_pallas_bwd(chunk, interpret, res, dy):
    v, dt, a_t, b, c, d, s0 = res
    dv, ddt, d_a, d_b, d_c, d_d = _backward(v, dt, a_t, b, c, d, s0, dy,
                                            chunk, interpret)
    return (dv, ddt, d_a.astype(a_t.dtype), d_b, d_c, d_d.astype(d.dtype))


_scan_pallas.defvjp(_scan_pallas_fwd, _scan_pallas_bwd)


def selective_scan_pallas(v, dt, a, b, c, d, chunk: int = CHUNK,
                          interpret: bool = False):
    """`selective_scan` down the kernels whatever the platform (tests ask
    `interpret=True`). `chunk`: a multiple of 16."""
    if not pallas_fits(v, b) or chunk % _STRIP:
        raise ValueError(f"the kernels want channels in 128s, states in 8s "
                         f"and a chunk in 16s, not {v.shape}, {b.shape}, "
                         f"{chunk}")
    reliability_metrics.inc(tnames.SSM_SCAN_ROUTE_PALLAS)
    return _scan_pallas(v, dt, jnp.swapaxes(a, 0, 1), b, c, d, int(chunk),
                        bool(interpret))
