"""The Gated-DeltaNet mixer round its recurrence, on (B, S, H d) slabs.

Between the input projections and `out_proj` the mixer does two elementwise
stages, one before the gated delta rule (`ops/gated_delta.py`) and one
after it. Both work on the slab the projections write and the recurrence
reads, (B, S, H d) with a head a group of d lanes of a row: a per-head
reduction is a lane reduction inside a row, so no (B, S, H, d) array exists.

`gdn_prepare(qkv, taps, heads)`: qkv (B, S, 2 Hk dk + H dv), the lanes of
q, k and v side by side; taps (W, the same lanes), the depthwise causal
convolution of width W.

    c_t = sum_j taps_j x_(t - W + 1 + j)     (x = 0 before position 0)
    y = silu(c);  q = l2(y_q) dk^-0.5;  k = l2(y_k);  v = y_v
    l2(t) = t rsqrt(sum over the head of t^2 + 1e-6)

`gdn_finish(o, z, weight, dv, eps)`: the gated output norm on the
recurrence's o and the gate lanes z, both (B, S, H dv):

    out = o rsqrt(mean over the head of o^2 + eps) weight silu(z)

Each is one function with two forms, chosen by what the call can observe
(the platform, the head widths, the dtype), with no knob, and counted at
trace time as `gdn.mixer.route.pallas` / `gdn.mixer.route.xla`:

**The Pallas kernels** (`gdn_prep_fwd`, `gdn_prep_bwd`, `gdn_post_fwd`,
`gdn_post_bwd`), taken on a TPU when dk and dv are multiples of 128 and the
slabs are bfloat16 or float32. Each stage is ONE pass over HBM forward and
ONE backward, in blocks of `ROWS` positions by up to `LANES` lanes.

- `gdn_prep_fwd` runs once for each of q, k, v and reads its lanes of qkv
  through the block index (no slice is made). The W - 1 positions before a
  block come as a second, 16-row block of the same array, zeroed at
  position 0 (no padded copy is made, and a sequence never sees the end of
  the previous one of the batch).
- `gdn_prep_bwd` (`jax.custom_vjp`) reads the raw lanes and dq, dk or dv,
  recomputes the cheap forward in VMEM and writes the lanes' share of dqkv
  (the three calls fill one buffer, handed on by `input_output_aliases`)
  and of the taps' gradient, which is accumulated over the grid in float32.
  The transposed convolution needs d c of the W - 1 FOLLOWING positions:
  the grid walks the row blocks from the last to the first and a VMEM
  scratch carries the first rows of d c from one block to the one before.
- `gdn_post_fwd` / `gdn_post_bwd`: the same shape of pass over o and z; the
  backward writes do, dz and the weight's gradient (a row of float32 lanes
  accumulated over the grid, summed over the heads outside).

**The plain form** (`prepare_xla`, `finish_xla`): the same equations in
`jnp` on the slab, differentiated by `jax.grad`. It is the fallback for
every other shape and platform and what the kernels are tested against.

Rounding, both forms. Everything is float32 from the slabs read to the
slabs written, forward and backward; the only roundings are of q, k, v, of
the gated output and of the gradients written, each once, to the slabs'
dtype. `hybrid_layers.gdn_mixer` before these functions existed read
`round(o n weight) round(silu(z))`, a product of two rounded factors, but
under `jit` on a TPU XLA keeps that chain in float32 inside its fusion
and rounds the product once (`xla_allow_excess_precision`): compared on the
chip element by element, the kernels' q, k, v and gated output equal what
the old expression computed there on every element, and a kernel that made
the three roundings the source spells was measurably further from float32
(PERF.md section 6, PR 31). Autodiff of the old form rounded cotangents
to the slabs' dtype on the way; the kernels do not.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..reliability.metrics import reliability_metrics
from ..telemetry import names as tnames

# The kernels' names reach the compiled program (`%gdn_prep_fwd.N =
# custom-call`), as the recurrence's and the flash kernels' do.
KERNEL_PREP_FWD = "gdn_prep_fwd"
KERNEL_PREP_BWD = "gdn_prep_bwd"
KERNEL_POST_FWD = "gdn_post_fwd"
KERNEL_POST_BWD = "gdn_post_bwd"

_LANE = 128
# Positions and lanes a block. The passes are bound by the vector unit and
# by HBM about equally; the sweep on the chip is in PERF.md section 6.
ROWS = 256
LANES = 1024
# Rows of the block that brings the positions before a row block: a whole
# bfloat16 tile, of which the convolution reads the last W - 1.
_HALO = 16
_CARRY = 8                # rows of d c the backward hands to the block before
_STRIP = 16               # rows the kernels work at a time: a bfloat16 tile
_L2_EPS = 1e-6


def kernels_fit(dtype, dk: int, dv: int, key_lanes: int = 0) -> bool:
    """The kernels' shape rule, the recurrence's own (`pallas_fits`): whole
    128-lane heads, bfloat16 or float32; and v's lanes start on a head."""
    return (dk % _LANE == 0 and dv % _LANE == 0 and key_lanes % dv == 0
            and dtype in (jnp.bfloat16, jnp.float32))


def _on_tpu() -> bool:
    return jax.devices()[0].platform == "tpu"


def gdn_prepare(qkv, taps, heads):
    """qkv (B, S, 2 Hk dk + H dv), taps (W, the same lanes), `heads` =
    (Hk, dk, H, dv) -> q, k (B, S, Hk dk) and v (B, S, H dv) in qkv's
    dtype, as the recurrence reads them (module docstring)."""
    hk, dk, _, dv = heads
    if kernels_fit(qkv.dtype, dk, dv, 2 * hk * dk) and _on_tpu():
        return prepare_pallas(qkv, taps, heads)
    reliability_metrics.inc(tnames.GDN_MIXER_ROUTE_XLA)
    return prepare_xla(qkv, taps, heads)


def gdn_finish(o, z, weight, dv: int, eps: float):
    """o, z (B, S, H dv), weight (dv,) -> the gated, normed (B, S, H dv)
    slab `out_proj` consumes (module docstring)."""
    if kernels_fit(o.dtype, dv, dv) and o.dtype == z.dtype and _on_tpu():
        return finish_pallas(o, z, weight, dv, eps)
    reliability_metrics.inc(tnames.GDN_MIXER_ROUTE_XLA)
    return finish_xla(o, z, weight, dv, eps)


# --------------------------------------------------------- the plain form

def prepare_xla(qkv, taps, heads):
    hk, dk, _, _ = heads
    f32 = jnp.float32
    b, s, _ = qkv.shape
    width = taps.shape[0]
    padded = jnp.pad(qkv, ((0, 0), (width - 1, 0), (0, 0)))
    t32 = taps.astype(f32)
    y = jax.nn.silu(sum(padded[:, j:j + s].astype(f32) * t32[j]
                        for j in range(width)))

    def l2(t, scale):
        t = t.reshape(b, s, hk, dk)
        r = jax.lax.rsqrt((t * t).sum(-1, keepdims=True) + _L2_EPS)
        return (t * (r * scale)).reshape(b, s, hk * dk)

    n = hk * dk
    return (l2(y[..., :n], dk ** -0.5).astype(qkv.dtype),
            l2(y[..., n:2 * n], 1.0).astype(qkv.dtype),
            y[..., 2 * n:].astype(qkv.dtype))


def finish_xla(o, z, weight, dv: int, eps: float):
    f32 = jnp.float32
    o32 = o.astype(f32).reshape(o.shape[:-1] + (-1, dv))
    o32 = o32 * jax.lax.rsqrt((o32 * o32).mean(-1, keepdims=True) + eps)
    y = (o32 * weight.astype(f32)).reshape(o.shape)
    return (y * jax.nn.silu(z.astype(f32))).astype(o.dtype)


# ------------------------------------------------------------ the kernels
# A block is worked a strip at a time, `_STRIP` positions of one head: the
# whole chain from the loads to the store runs on a few vector registers,
# where the same arithmetic on a whole (rows, lanes) block spilt every
# intermediate to VMEM (33 stores a register of the block, by the
# compiler's schedule for a v5e; PERF.md section 6).

def _heads(lanes: int, head: int):
    """The lane slices of a block's heads."""
    return [slice(l0, l0 + head) for l0 in range(0, lanes, head)]


def _over_strips(rows: int, body, carry=(), reverse: bool = False):
    """`body(first row of a strip, carry) -> carry` over a block's strips,
    from the last to the first if `reverse`. A loop the compiler unrolls:
    the body is traced once a head, not once a strip (a kernel traced strip
    by strip cost the host 7 s a process)."""
    n = rows // _STRIP

    def step(i, carry):
        at = n - 1 - i if reverse else i
        return body(pl.multiple_of(at * _STRIP, _STRIP), carry)

    return jax.lax.fori_loop(0, n, step, carry, unroll=True)


def _stage(x_ref, halo_ref, xe_ref, first):
    """The block's rows in float32 below the `_CARRY` rows before them:
    row t of the block is row `_CARRY` + t of `xe_ref`, so a strip and the
    rows before it are one aligned load. `first`: the block starts its
    sequence, so what came before is zero."""
    f32 = jnp.float32
    before = halo_ref[0].astype(f32)[_HALO - _CARRY:]
    xe_ref[:_CARRY] = jnp.where(first, 0.0, before)
    xe_ref[_CARRY:] = x_ref[0].astype(f32)


def _shift(a, down: int):
    """`a` with its rows moved `down` (up if negative) and wrapped round:
    a sublane rotation and a select a register, where an unaligned load
    costs a relayout of every consumer. The caller drops the `_CARRY` spare
    rows of `a` that the wrap spoils."""
    if down == 0:
        return a
    return pltpu.roll(a, down % a.shape[0], axis=0)


def _conv_silu(xe_ref, taps, r0, lanes):
    """A strip's W shifted reads, the convolution's sum c, sigmoid(c) and
    y = silu(c)."""
    width = len(taps)
    a = xe_ref[pl.ds(r0, _CARRY + _STRIP), lanes]
    shifted = [_shift(a, width - 1 - j)[_CARRY:] for j in range(width)]
    c = sum(x * t for x, t in zip(shifted, taps))
    sig = jax.nn.sigmoid(c)
    return shifted, c, sig, c * sig


def _l2_rsqrt(y):
    return jax.lax.rsqrt(jnp.sum(y * y, axis=1, keepdims=True) + _L2_EPS)


def _prep_fwd_kernel(x_ref, halo_ref, taps_ref, out_ref, xe_ref, *,
                     head: int, scale):
    """One block of one of q, k, v: `scale` None leaves y as it is (v),
    else each head of `head` lanes is L2-normalised and scaled."""
    rows, width = x_ref.shape[1], taps_ref.shape[0]
    _stage(x_ref, halo_ref, xe_ref, pl.program_id(2) == 0)
    for lanes in _heads(x_ref.shape[2], head):
        taps = [taps_ref[j:j + 1, lanes] for j in range(width)]

        def strip(r0, carry, lanes=lanes, taps=taps):
            _, _, _, y = _conv_silu(xe_ref, taps, r0, lanes)
            if scale is not None:
                y = y * (_l2_rsqrt(y) * scale)
            out_ref[0, pl.ds(r0, _STRIP), lanes] = y.astype(out_ref.dtype)
            return carry

        _over_strips(rows, strip)


def _prep_bwd_kernel(x_ref, halo_ref, taps_ref, g_ref, *rest, head: int,
                     scale):
    """The reverse of `_prep_fwd_kernel` over one block; the grid walks the
    row blocks from the last to the first, and so do the strips here.
    rest = ([the dqkv buffer,] dx out, d taps out, xe scratch, d c
    scratch): d c of the block lies in the scratch's first rows, and below
    them the first `_CARRY` rows of the block AFTER this one, which the
    grid step before this one left at the top."""
    dx_ref, dtaps_ref, xe_ref, dc_ref = rest[-4:]
    f32 = jnp.float32
    step = pl.program_id(2)
    rows, width = x_ref.shape[1], taps_ref.shape[0]
    _stage(x_ref, halo_ref, xe_ref, step == pl.num_programs(2) - 1)
    dc_ref[rows:] = jnp.where(step == 0, 0.0, dc_ref[:_CARRY])

    @pl.when((pl.program_id(1) == 0) & (step == 0))
    def _():
        dtaps_ref[...] = jnp.zeros_like(dtaps_ref)

    for lanes in _heads(x_ref.shape[2], head):
        taps = [taps_ref[j:j + 1, lanes] for j in range(width)]

        def strip(r0, sums, lanes=lanes, taps=taps):
            shifted, c, sig, y = _conv_silu(xe_ref, taps, r0, lanes)
            dy = g_ref[0, pl.ds(r0, _STRIP), lanes].astype(f32)
            if scale is not None:
                dy = dy * scale
                r = _l2_rsqrt(y)
                along = jnp.sum(dy * y, axis=1, keepdims=True) * (r * r)
                dy = r * (dy - y * along)
            dc = dy * (sig * (1.0 + c * (1.0 - sig)))
            dc_ref[pl.ds(r0, _STRIP), lanes] = dc
            # the transposed convolution: tap j of position t + W - 1 - j
            after = dc_ref[pl.ds(r0, _STRIP + _CARRY), lanes]
            dx_ref[0, pl.ds(r0, _STRIP), lanes] = sum(
                _shift(after, j + 1 - width)[:_STRIP] * taps[j]
                for j in range(width)).astype(dx_ref.dtype)
            return tuple(s + dc * x for s, x in zip(sums, shifted))

        sums = _over_strips(rows, strip, (jnp.zeros((_STRIP, head), f32),)
                            * width, reverse=True)
        dtaps_ref[:, lanes] += jnp.concatenate(
            [jnp.sum(s, axis=0, keepdims=True) for s in sums], axis=0)


def _norm_gate(o_ref, z_ref, r0, lanes, eps: float):
    """A strip of the gated output norm, float32: normalised o, its rsqrt,
    z and sigmoid(z)."""
    f32 = jnp.float32
    o32 = o_ref[0, pl.ds(r0, _STRIP), lanes].astype(f32)
    r = jax.lax.rsqrt(jnp.mean(o32 * o32, axis=1, keepdims=True) + eps)
    zf = z_ref[0, pl.ds(r0, _STRIP), lanes].astype(f32)
    return o32 * r, r, zf, jax.nn.sigmoid(zf)


def _post_fwd_kernel(o_ref, z_ref, w_ref, out_ref, *, head: int, eps: float):
    w = w_ref[...]
    for lanes in _heads(o_ref.shape[2], head):
        def strip(r0, carry, lanes=lanes):
            n, _, zf, sig = _norm_gate(o_ref, z_ref, r0, lanes, eps)
            out_ref[0, pl.ds(r0, _STRIP), lanes] = (
                (n * w) * (zf * sig)).astype(out_ref.dtype)
            return carry

        _over_strips(o_ref.shape[1], strip)


def _post_bwd_kernel(o_ref, z_ref, w_ref, g_ref, do_ref, dz_ref, dw_ref, *,
                     head: int, eps: float):
    f32 = jnp.float32

    @pl.when((pl.program_id(1) == 0) & (pl.program_id(2) == 0))
    def _():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    w = w_ref[...]
    for lanes in _heads(o_ref.shape[2], head):
        def strip(r0, dw, lanes=lanes):
            rows = pl.ds(r0, _STRIP)
            n, r, zf, sig = _norm_gate(o_ref, z_ref, r0, lanes, eps)
            g = g_ref[0, rows, lanes].astype(f32)
            dy = g * (zf * sig)
            dz_ref[0, rows, lanes] = (g * (n * w) * (
                sig * (1.0 + zf * (1.0 - sig)))).astype(dz_ref.dtype)
            dn = dy * w
            do_ref[0, rows, lanes] = (r * (dn - n * jnp.mean(
                dn * n, axis=1, keepdims=True))).astype(do_ref.dtype)
            return dw + dy * n

        dw = _over_strips(o_ref.shape[1], strip,
                          jnp.zeros((_STRIP, head), f32))
        dw_ref[:, lanes] += jnp.sum(dw, axis=0, keepdims=True)


# ------------------------------------------------------- the kernels' calls

def _lane_block(width: int, head: int, offset: int, lanes: int):
    """Lanes a block of a part `width` lanes wide that starts at lane
    `offset` of its array: whole heads, a divisor of both, at most `lanes`
    where a head allows it."""
    for n in range(max(lanes // head, 1), 0, -1):
        if width % (n * head) == 0 and offset % (n * head) == 0:
            return n * head
    raise ValueError(f"lanes {offset} to {offset + width} are no whole "
                     f"heads of {head}")


def _row_block(seq: int, rows: int) -> int:
    """Positions a block: `rows`, or the whole of a shorter sequence in
    whole tiles."""
    return min(rows, -(-seq // _HALO) * _HALO)


def _pad_rows(t, rows: int):
    pad = (-t.shape[1]) % rows
    return jnp.pad(t, ((0, 0), (0, pad), (0, 0))) if pad else t


def _params(sequential: int):
    """Grid (lane block, batch, row block); the last `sequential` axes carry
    something from step to step."""
    return pltpu.CompilerParams(
        dimension_semantics=("parallel",) * (3 - sequential)
        + ("arbitrary",) * sequential)


def _parts(heads):
    """(lane offset in qkv, lanes, head width, scale) of q, k and v."""
    hk, dk, hv, dv = heads
    return ((0, hk * dk, dk, dk ** -0.5), (hk * dk, hk * dk, dk, 1.0),
            (2 * hk * dk, hv * dv, dv, None))


# `jax.jit` round the kernel calls, as the recurrence has it: a Pallas call
# traces its kernel every time it is bound, the step binds these once a
# layer and direction and is itself traced twice a run.
@functools.partial(jax.jit, static_argnums=(2,))
def _prepare_forward(qkv, taps, cfg):
    heads, rows, lanes, interpret = cfg
    batch, seq, _ = qkv.shape
    n = seq // rows
    per_halo = rows // _HALO
    out = []
    for offset, width, head, scale in _parts(heads):
        lb = _lane_block(width, head, offset, lanes)
        first = offset // lb
        out.append(pl.pallas_call(
            functools.partial(_prep_fwd_kernel, head=head, scale=scale),
            grid=(width // lb, batch, n),
            in_specs=[
                pl.BlockSpec((1, rows, lb),
                             lambda li, b, i: (b, i, first + li)),
                pl.BlockSpec((1, _HALO, lb), lambda li, b, i: (
                    b, jnp.maximum(i * per_halo - 1, 0), first + li)),
                pl.BlockSpec((taps.shape[0], lb),
                             lambda li, b, i: (0, first + li))],
            out_specs=pl.BlockSpec((1, rows, lb),
                                   lambda li, b, i: (b, i, li)),
            out_shape=jax.ShapeDtypeStruct((batch, seq, width), qkv.dtype),
            scratch_shapes=[pltpu.VMEM((_CARRY + rows, lb), jnp.float32)],
            compiler_params=_params(0), interpret=interpret,
            name=KERNEL_PREP_FWD)(qkv, qkv, taps))
    return tuple(out)


@functools.partial(jax.jit, static_argnums=(0,))
def _prepare_backward(cfg, qkv, taps, *cts):
    heads, rows, lanes, interpret = cfg
    batch, seq, _ = qkv.shape
    n = seq // rows
    per_halo = rows // _HALO
    dqkv, dtaps = None, []
    for (offset, width, head, scale), g in zip(_parts(heads), cts):
        lb = _lane_block(width, head, offset, lanes)
        first = offset // lb

        def at(i):
            return n - 1 - i

        def block(li, b, i):
            return b, at(i), first + li

        in_specs = [
            pl.BlockSpec((1, rows, lb), block),
            pl.BlockSpec((1, _HALO, lb), lambda li, b, i: (
                b, jnp.maximum(at(i) * per_halo - 1, 0), first + li)),
            pl.BlockSpec((taps.shape[0], lb),
                         lambda li, b, i: (0, first + li)),
            pl.BlockSpec((1, rows, lb), lambda li, b, i: (b, at(i), li))]
        args = [qkv, qkv, taps, g]
        if dqkv is not None:
            # the buffer the call before wrote its lanes of
            in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
            args.append(dqkv)
        dqkv, dt = pl.pallas_call(
            functools.partial(_prep_bwd_kernel, head=head, scale=scale),
            grid=(width // lb, batch, n),
            in_specs=in_specs,
            out_specs=[pl.BlockSpec((1, rows, lb), block),
                       pl.BlockSpec((taps.shape[0], lb),
                                    lambda li, b, i: (0, li))],
            out_shape=[jax.ShapeDtypeStruct(qkv.shape, qkv.dtype),
                       jax.ShapeDtypeStruct((taps.shape[0], width),
                                            jnp.float32)],
            scratch_shapes=[pltpu.VMEM((_CARRY + rows, lb), jnp.float32),
                            pltpu.VMEM((rows + _CARRY, lb), jnp.float32)],
            input_output_aliases={4: 0} if len(args) == 5 else {},
            compiler_params=_params(2), interpret=interpret,
            name=KERNEL_PREP_BWD)(*args)
        dtaps.append(dt)
    return dqkv, jnp.concatenate(dtaps, axis=1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _prepare(qkv, taps, cfg):
    return _prepare_forward(qkv, taps, cfg)


def _prepare_fwd(qkv, taps, cfg):
    return _prepare_forward(qkv, taps, cfg), (qkv, taps)


def _prepare_bwd(cfg, res, cts):
    return _prepare_backward(cfg, *res, *cts)


_prepare.defvjp(_prepare_fwd, _prepare_bwd)


def _post_specs(rows: int, lb: int, head: int):
    block = pl.BlockSpec((1, rows, lb), lambda li, b, i: (b, i, li))
    return block, pl.BlockSpec((1, head), lambda li, b, i: (0, 0))


@functools.partial(jax.jit, static_argnums=(3,))
def _finish_forward(o, z, w, cfg):
    head, eps, rows, lanes, interpret = cfg
    batch, seq, width = o.shape
    lb = _lane_block(width, head, 0, lanes)
    block, weight = _post_specs(rows, lb, head)
    return pl.pallas_call(
        functools.partial(_post_fwd_kernel, head=head, eps=eps),
        grid=(width // lb, batch, seq // rows),
        in_specs=[block, block, weight], out_specs=block,
        out_shape=jax.ShapeDtypeStruct(o.shape, o.dtype),
        compiler_params=_params(0), interpret=interpret,
        name=KERNEL_POST_FWD)(o, z, w)


@functools.partial(jax.jit, static_argnums=(0,))
def _finish_backward(cfg, o, z, w, g):
    head, eps, rows, lanes, interpret = cfg
    batch, seq, width = o.shape
    lb = _lane_block(width, head, 0, lanes)
    block, weight = _post_specs(rows, lb, head)
    do, dz, dw = pl.pallas_call(
        functools.partial(_post_bwd_kernel, head=head, eps=eps),
        grid=(width // lb, batch, seq // rows),
        in_specs=[block, block, weight, block],
        out_specs=[block, block,
                   pl.BlockSpec((1, lb), lambda li, b, i: (0, li))],
        out_shape=[jax.ShapeDtypeStruct(o.shape, o.dtype),
                   jax.ShapeDtypeStruct(z.shape, z.dtype),
                   jax.ShapeDtypeStruct((1, width), jnp.float32)],
        compiler_params=_params(2), interpret=interpret,
        name=KERNEL_POST_BWD)(o, z, w, g)
    return do, dz, dw.reshape(-1, head).sum(0, keepdims=True)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _finish(o, z, w, cfg):
    return _finish_forward(o, z, w, cfg)


def _finish_fwd(o, z, w, cfg):
    return _finish_forward(o, z, w, cfg), (o, z, w)


def _finish_bwd(cfg, res, g):
    return _finish_backward(cfg, *res, g)


_finish.defvjp(_finish_fwd, _finish_bwd)


def prepare_pallas(qkv, taps, heads, *, interpret: bool = False,
                   rows: int = ROWS, lanes: int = LANES):
    """`gdn_prepare` down the kernels, where `kernels_fit`. `interpret`
    runs them through the Pallas interpreter, `rows` and `lanes` size the
    blocks: both for tests off the TPU and the sweep on it."""
    hk, dk, hv, dv = heads
    if not (kernels_fit(qkv.dtype, dk, dv, 2 * hk * dk)
            and taps.shape[0] - 1 <= _CARRY
            and qkv.shape[-1] == taps.shape[-1] == 2 * hk * dk + hv * dv):
        raise ValueError(
            f"prepare_pallas wants heads in multiples of {_LANE} lanes and "
            f"bfloat16 or float32, got qkv {qkv.shape} {qkv.dtype}, taps "
            f"{taps.shape}, heads {heads}")
    reliability_metrics.inc(tnames.GDN_MIXER_ROUTE_PALLAS)
    seq = qkv.shape[1]
    rows = _row_block(seq, rows)
    out = _prepare(_pad_rows(qkv, rows), taps.astype(jnp.float32),
                   (tuple(heads), rows, lanes, bool(interpret)))
    return tuple(t[:, :seq] for t in out)


def finish_pallas(o, z, weight, dv: int, eps: float, *,
                  interpret: bool = False, rows: int = ROWS,
                  lanes: int = LANES):
    """`gdn_finish` down the kernels, where `kernels_fit`."""
    if not (kernels_fit(o.dtype, dv, dv) and o.dtype == z.dtype
            and o.shape == z.shape and weight.shape == (dv,)):
        raise ValueError(
            f"finish_pallas wants heads in multiples of {_LANE} lanes and "
            f"bfloat16 or float32, got o {o.shape} {o.dtype}, z {z.shape} "
            f"{z.dtype}, weight {weight.shape}")
    reliability_metrics.inc(tnames.GDN_MIXER_ROUTE_PALLAS)
    seq = o.shape[1]
    rows = _row_block(seq, rows)
    out = _finish(_pad_rows(o, rows), _pad_rows(z, rows),
                  weight.astype(jnp.float32)[None],
                  (dv, float(eps), rows, lanes, bool(interpret)))
    return out[:, :seq]
