"""The gated delta rule (Gated DeltaNet's recurrence) in chunked form.

Per head, with a (dk, dv) state S that is zero at the start of a sequence,
for each position t:

    S = exp(g_t) S;  r = k_t^T S;  d = beta_t (v_t - r);  S = S + k_t d^T
    o_t = q_t^T S

A scan over positions does S steps of rank-one work. The chunked form
(Yang et al., "Gated Delta Networks", 2024; the WY representation of
"Parallelizing Linear Transformers with the Delta Rule") does the same
arithmetic with matrix products. Inside a chunk of C positions that starts
from state S0, with gamma_i the running sum of g up to and including i and
D_ij = exp(gamma_i - gamma_j) for j <= i:

    A_ij = beta_i D_ij (k_i . k_j)            j < i   (strictly lower)
    T = (I + A)^-1;  W = T (beta exp(gamma) K);  U0 = T (beta V)
    U  = U0 - W S0                        the C writes of the chunk
    O  = (exp(gamma) Q) S0 + ((Q K^T) * D) U
    S' = exp(gamma_C) S0 + (K * exp(gamma_C - gamma))^T U

Every exponent is of a difference that is <= 0, so nothing overflows
however long the sequence.

Two implementations of that one function, chosen by `chunk_gated_delta_rule`
from what it can observe (the platform and the shapes), with no knob:

**The Pallas kernels** (`gated_delta_pallas`; kernels `gdn_fwd`, `gdn_bwd`),
taken on a TPU when dk and dv are multiples of 128, the chunk is 64 and the
inputs are bfloat16 or float32.

- Grid (batch, head block, chunk), the chunk axis sequential; a grid step
  does `HEADS_PER_STEP` value heads of one chunk, all of them at once as
  (H, ., .) arrays and batched products. The blocks read q, k, v as
  (B, S, H x d) slabs, the layout the mixer's projections already have (a
  head is a 128-lane slice of a row, so no transpose exists anywhere), and
  write `o` the same way; `gated_delta_slab` takes and returns those
  slabs, which is how the mixer calls (`ops/gdn_mixer.py` prepares and
  finishes them in the same layout), and the (B, S, H, d) signature is a
  reshape round it. Grouped key heads are read as they are: value
  head h takes lanes of key head h // (H / Hk), and the backward sums dq and
  dk over the group in float32 before it rounds them.
- The recurrent state, (heads, dk, dv) float32, lives in a VMEM scratch
  across the chunk axis. D, K K^T, A, T, W, U, Q K^T are made and consumed
  in VMEM and never reach HBM. Only the running sum gamma is made outside (a
  cumsum over 64 positions of a (B, S, H) array); it and beta come in along
  the lanes, (1, C) a head, and the kernel turns them down the rows itself.
- T = (I + A)^-1 is float32 blocked forward substitution, two heads side
  by side in the 128 lanes of a register (`_pair_inverses`): a block of 16
  rows takes the rows above it by one float32 product, then 15 rank-one
  updates on the vector unit, each column brought to the lanes of its own
  half by one lane gather. Those gathers go through the cross-lane unit,
  which is what bounds the kernels (PERF.md section 6, with what the plain
  substitution and the product form (I - A)(I + A^2)...(I + A^32) read).
- The backward (`jax.custom_vjp`) is a hand-written reverse pass: the grid
  walks the chunks from the last to the first and the same VMEM scratch
  carries dS. The differentiated forward writes out, a chunk and head, the
  (dk, dv) float32 state the chunk started from and its T (64 x 64
  float32), 805 MB a layer at 2 x 8192 x 32 heads, transient under the
  trainer's `jax.checkpoint`. The plain forward writes neither, and
  `optimize_remat` makes the step's first forward that one (a Pallas call's
  unused outputs are not dropped by themselves). From those and q, k, v,
  gamma, beta the backward recomputes the chunk's cheap quantities and
  never inverts.

**The XLA form** (`_chunk_gated_delta_rule_xla`): everything that does not
depend on S0 for all chunks at once, the three products with the state in a
`lax.scan` over chunks, the backward by `jax.grad`. It is the fallback for
every other shape and platform, and the plain form the kernels are tested
against. Off the TPU the kernels run only where a test asks
`gated_delta_pallas(..., interpret=True)`: the XLA form is faster there.
Either way `gdn.scan.route.pallas` / `gdn.scan.route.xla` count the choice.

Precision, both forms: decays, the inverse, the state, dS and every
accumulation float32; the products take their operands in the inputs' dtype
(bfloat16 in the trainer's mixed precision, float32 in tests, where the
kernels ask for `Precision.HIGHEST`). The kernels round where autodiff of
the XLA form rounds (a cotangent becomes a product's operand in the inputs'
dtype) and nowhere else: U0, dU, dW and dT stay float32 in VMEM until they
are an operand, and dA = -T^T dT T^T is two float32 products.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..reliability.metrics import reliability_metrics
from ..telemetry import names as tnames

CHUNK = 64
# Heads a grid step, worked together as (H, ., .) arrays: one head's
# dependent chain (substitution, then products) hides in the others', and
# the kernels' size to trace and lower does not grow with H. Compiled for a
# v5e, scheduled bundles a head and chunk, forward / backward: 8 heads 491 /
# 708, 16 heads 424 / 742; on the chip in PERF.md section 6.
HEADS_PER_STEP = 16
_LANES = 128
# The kernels work every head of a step at once, so a step's intermediates
# ((H, 64, 128) float32 arrays, 32 KB a head each) live in VMEM together:
# more than the 16 MB a kernel gets unasked, far less than the 128 MB a v5e
# core has.
_VMEM_LIMIT = 96 * 1024 * 1024
# Rows a block of the substitution (`_pair_inverses`). 8 schedules no
# shorter than 16 (fewer lane gathers, more float32 products).
_BLOCK = 16

# The kernels' names reach the compiled program (`%gdn_fwd.N = custom-call`,
# `op_name` ending `.../gdn_fwd/pallas_call`), as the flash kernels' do.
KERNEL_FWD = "gdn_fwd"
KERNEL_BWD = "gdn_bwd"


def chunk_gated_delta_rule(q, k, v, g, beta, chunk: int = CHUNK):
    """q, k (S, Hk, dk), v (S, H, dv), g and beta (S, H) -> o (S, H, dv);
    or all five with a leading batch axis.

    q and k as the layer prepares them (L2-normalised, q scaled); Hk
    divides H and value head h reads key head h // (H / Hk), so grouped key
    heads come as they are, not repeated. g <= 0 is the log decay. S need
    not divide by `chunk`: the tail is padded with positions that write
    nothing. Takes the Pallas kernels on a TPU where the shapes fit them,
    else the XLA form (module docstring); either way the choice is
    counted."""
    if pallas_fits(q, v, chunk) and jax.devices()[0].platform == "tpu":
        return gated_delta_pallas(q, k, v, g, beta)
    reliability_metrics.inc(tnames.GDN_SCAN_ROUTE_XLA)
    rep = v.shape[-2] // q.shape[-2]
    if rep > 1:
        q, k = jnp.repeat(q, rep, axis=-2), jnp.repeat(k, rep, axis=-2)
    form = functools.partial(_chunk_gated_delta_rule_xla, chunk=chunk)
    return (jax.vmap(form) if q.ndim == 4 else form)(q, k, v, g, beta)


def gated_delta_slab(q, k, v, g, beta, dk: int, dv: int):
    """`chunk_gated_delta_rule` on the layout the mixer keeps end to end:
    q, k (B, S, Hk dk), v (B, S, H dv), a head a group of lanes of a row,
    g and beta (B, S, H) -> o (B, S, H dv). The kernels read and write
    these slabs as they are; the XLA form sees them as (B, S, H, d)."""
    if _fits(dk, dv, q.shape[-1] // dk, v.shape[-1] // dv, q.dtype,
             v.dtype) and jax.devices()[0].platform == "tpu":
        return _pallas_slab(q, k, v, g, beta, dk, dv)

    def heads(t, d):
        return t.reshape(t.shape[:2] + (-1, d))

    return chunk_gated_delta_rule(
        heads(q, dk), heads(k, dk), heads(v, dv), g, beta).reshape(v.shape)


def pallas_fits(q, v, chunk: int = CHUNK) -> bool:
    """The kernels' shape rule: whole 128-lane heads, the chunk they were
    written for, bfloat16 or float32."""
    return chunk == CHUNK and _fits(q.shape[-1], v.shape[-1], q.shape[-2],
                                    v.shape[-2], q.dtype, v.dtype)


def _fits(dk, dv, key_heads, heads, q_dtype, v_dtype) -> bool:
    return (dk % _LANES == 0 and dv % _LANES == 0 and q_dtype == v_dtype
            and heads % key_heads == 0
            and q_dtype in (jnp.bfloat16, jnp.float32))


def _chunk_gated_delta_rule_xla(q, k, v, g, beta, chunk: int = CHUNK):
    seq, heads, dk = q.shape
    dv = v.shape[-1]
    cdt = q.dtype
    f32 = jnp.float32
    pad = (-seq) % chunk
    n = (seq + pad) // chunk

    def chunks(t):                    # (S, H, ...) -> (H, n, C, ...)
        t = jnp.pad(t, ((0, pad),) + ((0, 0),) * (t.ndim - 1))
        t = t.reshape((n, chunk) + t.shape[1:])
        return jnp.moveaxis(t, 2, 0)

    qc, kc, vc = chunks(q), chunks(k), chunks(v)
    gc, bc = chunks(g.astype(f32)), chunks(beta.astype(f32))
    gamma = jnp.cumsum(gc, axis=-1)                       # (H, n, C)
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    diff = gamma[..., :, None] - gamma[..., None, :]
    decay = jnp.where(lower, jnp.exp(jnp.where(lower, diff, 0.0)), 0.0)
    kk = jnp.einsum("hnid,hnjd->hnij", kc, kc, preferred_element_type=f32)
    a = bc[..., :, None] * decay * kk
    a = jnp.where(jnp.tril(lower, -1), a, 0.0) + jnp.eye(chunk, dtype=f32)
    eg = jnp.exp(gamma)
    # (I + A)^-1 itself (C x C a chunk), then two products on the MXU: a
    # solve against the (C, dk + dv) right-hand side would hold it in f32
    inv = jax.lax.linalg.triangular_solve(
        a, jnp.broadcast_to(jnp.eye(chunk, dtype=f32), a.shape),
        left_side=True, lower=True, unit_diagonal=True).astype(cdt)
    w = jnp.einsum("hnij,hnjd->hnid", inv,
                   ((bc * eg)[..., None] * kc.astype(f32)).astype(cdt),
                   preferred_element_type=f32).astype(cdt)
    u0 = jnp.einsum("hnij,hnjd->hnid", inv,
                    (bc[..., None] * vc.astype(f32)).astype(cdt),
                    preferred_element_type=f32).astype(cdt)
    q_in = (eg[..., None] * qc.astype(f32)).astype(cdt)
    qk = jnp.einsum("hnid,hnjd->hnij", qc, kc, preferred_element_type=f32)
    qk = (qk * decay).astype(cdt)
    k_out = (jnp.exp(gamma[..., -1:] - gamma)[..., None]
             * kc.astype(f32)).astype(cdt)
    g_end = eg[..., -1]                                   # (H, n)

    def step(state, xs):
        w_c, u0_c, q_c, qk_c, k_c, g_c = xs
        s_in = state.astype(cdt)
        u = u0_c - jnp.einsum("hik,hkv->hiv", w_c, s_in,
                              preferred_element_type=f32)
        u_in = u.astype(cdt)
        o = jnp.einsum("hik,hkv->hiv", q_c, s_in,
                       preferred_element_type=f32) \
            + jnp.einsum("hij,hjv->hiv", qk_c, u_in,
                         preferred_element_type=f32)
        state = g_c[:, None, None] * state + jnp.einsum(
            "hik,hiv->hkv", k_c, u_in, preferred_element_type=f32)
        return state, o.astype(cdt)

    per_chunk = tuple(jnp.moveaxis(t, 1, 0)
                      for t in (w, u0, q_in, qk, k_out, g_end))
    _, o = jax.lax.scan(step, jnp.zeros((heads, dk, dv), f32), per_chunk)
    o = jnp.moveaxis(o, 1, 0).reshape(heads, n * chunk, dv)   # (H, S', dv)
    return jnp.moveaxis(o, 0, 1)[:seq]


# ------------------------------------------------------------ the kernels

def _dot(a, b, contract, exact: bool):
    """In-kernel product of every head at once, (H, ., .) x (H, ., .), with
    float32 accumulation, contracting dimension `contract[0]` of a with
    `contract[1]` of b. `exact`: the operands are float32 and must stay so
    (Mosaic's default rounds them to one bfloat16 pass, as in the flash
    kernels, PR 21)."""
    return jax.lax.dot_general(
        a, b, (((contract[0],), (contract[1],)), ((0,), (0,))),
        precision=jax.lax.Precision.HIGHEST if exact else None,
        preferred_element_type=jnp.float32)


def _heads(ref, n: int, width: int):
    """A step's (1, C, n width) block of a slab as (n, C, width): head h is
    lanes h width .. (h + 1) width of every row."""
    return jnp.stack([ref[0, :, h * width:(h + 1) * width]
                      for h in range(n)])


def _side_by_side(x):
    """(H, R, C) -> (H / 2, R, 2 C): heads 2p and 2p + 1 in the two halves
    of the lanes (an odd last head beside zeros)."""
    if x.shape[0] % 2:
        x = jnp.concatenate([x, jnp.zeros_like(x[:1])], axis=0)
    x = x.reshape((x.shape[0] // 2, 2) + x.shape[1:])
    return jnp.concatenate([x[:, 0], x[:, 1]], axis=2)


def _pair_inverses(a):
    """(I + a)^-1 for each strictly lower (C, C) float32 a of (H, C, C), by
    blocked forward substitution, two heads side by side in the 128 lanes
    of a vector register and every pair in step with the others. A block of
    `_BLOCK` rows first takes what the rows above it contribute, one
    float32 product against the block-diagonal of the pair's finished
    rows; then column j of the block updates the rows below j, the column
    brought to every lane of its own half by one lane gather a register."""
    heads, c, _ = a.shape
    f32 = jnp.float32
    nb = _BLOCK
    a2 = _side_by_side(a)                                 # (P, C, 2C)
    pairs = a2.shape[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, (nb, 2 * c), 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (nb, 2 * c), 0)
    left = lane < c
    col_of = jnp.where(left, lane, lane - c)
    # Mosaic gathers along the lanes of 2-D arrays: the pairs' blocks on
    # top of each other, (P nb, 2C)
    flat_left = jax.lax.broadcasted_iota(
        jnp.int32, (pairs * nb, 2 * c), 1) < c
    tops, bottoms, strips = [], [], []
    for bi, r0 in enumerate(range(0, c, nb)):
        a_rows = a2[:, r0:r0 + nb, :]
        x = jnp.where(col_of == row + r0, 1.0, 0.0).astype(f32)
        if bi:
            zeros = jnp.zeros((pairs, c - r0, 2 * c), f32)
            x = x - _dot(a_rows, jnp.concatenate(
                tops + [zeros] + bottoms + [zeros], axis=1), (2, 1), True)
        else:
            x = jnp.broadcast_to(x, a_rows.shape)
        flat = a_rows.reshape(pairs * nb, 2 * c)
        base = jnp.where(flat_left, r0, c + r0)
        for j in range(nb - 1):
            column = jnp.take_along_axis(flat, base + j, axis=1)
            x = x - column.reshape(a_rows.shape) * x[:, j:j + 1, :]
        strips.append(x)
        tops.append(jnp.where(left, x, 0.0))
        bottoms.append(jnp.where(left, 0.0, x))
    t2 = jnp.concatenate(strips, axis=1)                  # (P, C, 2C)
    return jnp.stack([t2[:, :, :c], t2[:, :, c:]], axis=1).reshape(
        2 * pairs, c, c)[:heads]


def _chunk_parts(refs, heads: int, rep: int, dk: int, dv: int, t_ref=None):
    """What a chunk's forward and backward share, every head of the step at
    once as (H, ., .) arrays, from the step's blocks `refs` = (q, k, v,
    gamma, beta); T = (I + A)^-1 computed, or read from `t_ref` where the
    forward kept it."""
    q_ref, k_ref, v_ref, g_ref, b_ref = refs
    f32 = jnp.float32
    c = q_ref.shape[1]
    cdt = q_ref.dtype
    exact = cdt == f32

    def per_value_head(x):            # (Hk, ...) -> (H, ...)
        return x if rep == 1 else jnp.stack(
            [x[h // rep] for h in range(heads)])

    qh = _heads(q_ref, heads // rep, dk)
    kh = _heads(k_ref, heads // rep, dk)
    q, k, v = per_value_head(qh), per_value_head(kh), _heads(v_ref, heads, dv)
    # gamma and beta along the lanes, (H, 1, C)
    gr = jnp.stack([g_ref[0, 0, 0, h:h + 1, :] for h in range(heads)])
    br = jnp.stack([b_ref[0, 0, 0, h:h + 1, :] for h in range(heads)])
    row = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    lower, strict, eye = row >= col, row > col, row == col
    # the same two vectors down the rows, (H, C, 1): a masked sum along the
    # lanes, exact, where a transpose of a (1, C) array is none Mosaic has
    gc = jnp.sum(jnp.where(eye, gr, 0.0), axis=2, keepdims=True)
    b = jnp.sum(jnp.where(eye, br, 0.0), axis=2, keepdims=True)
    d = jnp.where(lower, jnp.exp(jnp.where(lower, gc - gr, 0.0)), 0.0)
    # K K^T and Q K^T once a key head
    kk = per_value_head(_dot(kh, kh, (2, 2), exact))
    qk = per_value_head(_dot(qh, kh, (2, 2), exact))
    if t_ref is None:
        t = _pair_inverses(jnp.where(strict, b * d * kk, 0.0))
    else:
        t = t_ref[0, 0]
    tc = t.astype(cdt)
    eg = jnp.exp(gc)
    g_end = gc[:, c - 1:c, :]                             # (H, 1, 1)
    # Mosaic broadcasts along one axis at a time (and folds two broadcasts
    # into one): the chunk's whole decay as a (1, dv) row by a masked sum
    # down the rows, which then scales a (dk, dv) state
    last = jax.lax.broadcasted_iota(jnp.int32, (c, 1), 0) == c - 1
    e_end = jnp.sum(jnp.where(last, jnp.broadcast_to(eg, v.shape), 0.0),
                    axis=1, keepdims=True)                # (H, 1, dv)
    eo = jnp.exp(g_end - gc)
    kf, vf, qf = k.astype(f32), v.astype(f32), q.astype(f32)
    kb = (b * eg * kf).astype(cdt)
    vb = (b * vf).astype(cdt)
    return dict(
        q=q, k=k, exact=exact, strict=strict, eye=eye, last=last, b=b, d=d,
        kk=kk, qk=qk, t=t, tc=tc, eg=eg, e_end=e_end, eo=eo, kf=kf, vf=vf,
        qf=qf, kb=kb, vb=vb, w=_dot(tc, kb, (2, 1), exact).astype(cdt),
        u0=_dot(tc, vb, (2, 1), exact), qg=(eg * qf).astype(cdt),
        p=(qk * d).astype(cdt), ko=(eo * kf).astype(cdt))


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, o_ref, *rest,
                heads: int, rep: int, dk: int, dv: int):
    """One chunk of `heads` value heads (`rep` of them share a key head):
    rest = ([states out, inverses out,] state scratch)."""
    s_ref = rest[-1]

    @pl.when(pl.program_id(2) == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    m = _chunk_parts((q_ref, k_ref, v_ref, g_ref, b_ref), heads, rep, dk, dv)
    exact, cdt = m["exact"], q_ref.dtype
    s = s_ref[...]
    if len(rest) == 3:
        rest[0][0, 0] = s
        rest[1][0, 0] = m["t"]
    sc = s.astype(cdt)
    uc = (m["u0"] - _dot(m["w"], sc, (2, 1), exact)).astype(cdt)
    o = _dot(m["qg"], sc, (2, 1), exact) + _dot(m["p"], uc, (2, 1), exact)
    for h in range(heads):
        o_ref[0, :, h * dv:(h + 1) * dv] = o[h].astype(o_ref.dtype)
    s_ref[...] = m["e_end"] * s + _dot(m["ko"], uc, (1, 1), exact)


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, st_ref, tt_ref, do_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, db_ref, ds_ref, *,
                heads: int, rep: int, dk: int, dv: int):
    """The reverse pass over one chunk of `heads` heads; the grid walks the
    chunks from the last to the first and `ds_ref` carries dS. dq and dk of
    the `rep` heads that share a key head are summed in float32 here."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        ds_ref[...] = jnp.zeros_like(ds_ref)

    def rows(x):                      # (H, C, .) -> (H, C, 1)
        return jnp.sum(x, axis=2, keepdims=True)

    def columns(x):                   # (H, C, .) -> (H, 1, .)
        return jnp.sum(x, axis=1, keepdims=True)

    m = _chunk_parts((q_ref, k_ref, v_ref, g_ref, b_ref), heads, rep, dk, dv,
                     tt_ref)
    q, k, exact, b, d, t, tc, eg, eo, eye = (m[n] for n in (
        "q", "k", "exact", "b", "d", "t", "tc", "eg", "eo", "eye"))
    cdt = q.dtype
    c = q.shape[1]
    s = st_ref[0, 0]
    sc = s.astype(cdt)
    g_out = ds_ref[...]                                   # dS', float32
    g_op = g_out.astype(cdt)
    do = _heads(do_ref, heads, dv)
    uc = (m["u0"] - _dot(m["w"], sc, (2, 1), exact)).astype(cdt)
    # the chunk's writes U, then what they were made of
    du = _dot(m["p"], do, (1, 1), exact) + _dot(m["ko"], g_op, (2, 1), exact)
    du_op = du.astype(cdt)
    dqg = _dot(do, sc, (2, 2), exact)
    dp = _dot(do, uc, (2, 2), exact)
    dko = _dot(uc, g_op, (2, 2), exact)
    dw_op = (-_dot(du_op, sc, (2, 2), exact)).astype(cdt)
    ds_ref[...] = m["e_end"] * g_out + _dot(m["qg"], do, (1, 1), exact) \
        - _dot(m["w"], du_op, (1, 1), exact)
    dt = _dot(du_op, m["vb"], (2, 2), exact) \
        + _dot(dw_op, m["kb"], (2, 2), exact)
    dvb = _dot(tc, du_op, (1, 1), exact)
    dkb = _dot(tc, dw_op, (1, 1), exact)
    # T = (I + A)^-1: dA = -T^T dT T^T, float32 throughout, a pair of heads
    # side by side: two 128-wide products, the off-diagonal blocks of the
    # first dropped in between
    t2 = _side_by_side(t)                                 # (P, C, 2C)
    xx = _dot(t2, _side_by_side(dt), (1, 1), True)        # (P, 2C, 2C)
    own = (jax.lax.broadcasted_iota(jnp.int32, xx.shape[1:], 0) < c) == (
        jax.lax.broadcasted_iota(jnp.int32, xx.shape[1:], 1) < c)
    xx = jnp.where(own, xx, 0.0)
    da = -_dot(xx, t2, (2, 2), True)                      # (P, 2C, C)
    da = da.reshape(2 * da.shape[0], c, c)[:heads]
    f = jnp.where(m["strict"], da * d, 0.0)
    fk = f * m["kk"]
    dqk = dp * d
    e = b * fk + dqk * m["qk"]                    # dD * D, lower triangle
    t_k = rows(dkb * m["kf"])
    r_q = rows(dqg * (eg * m["qf"]))
    r_o = rows(dko * (eo * m["kf"]))
    dg_end = m["e_end"][:, :, :1] * columns(rows(g_out * s)) + columns(r_o)
    dgc = rows(e) + r_q + b * eg * t_k - r_o \
        + jnp.where(m["last"], dg_end, 0.0)
    # back along the lanes, (H, 1, C)
    dg = columns(jnp.where(eye, dgc, 0.0)) - columns(e)
    db = columns(jnp.where(eye, rows(fk) + eg * t_k + rows(dvb * m["vf"]),
                           0.0))
    dkk_op = (b * f).astype(cdt)
    dqk_op = dqk.astype(cdt)
    dq = eg * dqg + _dot(dqk_op, k, (2, 1), exact)
    dk_ = b * eg * dkb + eo * dko + _dot(dkk_op, k, (2, 1), exact) \
        + _dot(dkk_op, k, (1, 1), exact) + _dot(dqk_op, q, (1, 1), exact)
    dv_ = b * dvb
    for h in range(heads):
        dg_ref[0, 0, 0, h:h + 1, :] = dg[h]
        db_ref[0, 0, 0, h:h + 1, :] = db[h]
        dv_ref[0, :, h * dv:(h + 1) * dv] = dv_[h].astype(dv_ref.dtype)
    for kh in range(heads // rep):
        group = range(kh * rep, (kh + 1) * rep)
        dq_ref[0, :, kh * dk:(kh + 1) * dk] = sum(
            dq[h] for h in group).astype(dq_ref.dtype)
        dk_ref[0, :, kh * dk:(kh + 1) * dk] = sum(
            dk_[h] for h in group).astype(dk_ref.dtype)


def _specs(hb: int, rep: int, dk: int, dv: int, n: int, reverse: bool):
    """Block specs over the grid (batch, head block, chunk): the slabs of
    q / k (B, S, Hk dk) and of v (B, S, H dv), gamma and beta along the
    lanes (B, H/hb, n, hb, C), the states (B, n, H, dk, dv). `reverse`:
    the grid's chunk axis walks from the last chunk to the first."""
    def at(ci):
        return n - 1 - ci if reverse else ci
    qk = pl.BlockSpec((1, CHUNK, hb // rep * dk),
                      lambda b, hi, ci: (b, at(ci), hi))
    vo = pl.BlockSpec((1, CHUNK, hb * dv), lambda b, hi, ci: (b, at(ci), hi))
    row = pl.BlockSpec((1, 1, 1, hb, CHUNK),
                       lambda b, hi, ci: (b, hi, at(ci), 0, 0))
    state = pl.BlockSpec((1, 1, hb, dk, dv),
                         lambda b, hi, ci: (b, at(ci), hi, 0, 0))
    inv = pl.BlockSpec((1, 1, hb, CHUNK, CHUNK),
                       lambda b, hi, ci: (b, at(ci), hi, 0, 0))
    return qk, vo, row, state, inv


def _compiler_params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT)


# `jax.jit` round each kernel call: a Pallas call traces its kernel every time
# it is bound, and the step binds each of the three (forward, forward that
# keeps, backward) once a layer and is itself traced twice a run; as jitted
# functions they are traced once a process (`setup_s`: PERF.md section 6).
@functools.partial(jax.jit, static_argnums=(5, 6))
def _forward(q, k, v, gr, br, cfg, keep_states: bool):
    hb, rep, dk, dv, interpret = cfg
    batch, seq, _ = q.shape
    n, nh = seq // CHUNK, gr.shape[1]
    qk, vo, row, state, inv = _specs(hb, rep, dk, dv, n, False)
    out_shape = [jax.ShapeDtypeStruct(v.shape, v.dtype)]
    out_specs = [vo]
    if keep_states:
        out_shape += [
            jax.ShapeDtypeStruct((batch, n, nh * hb, dk, dv), jnp.float32),
            jax.ShapeDtypeStruct((batch, n, nh * hb, CHUNK, CHUNK),
                                 jnp.float32)]
        out_specs += [state, inv]
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, heads=hb, rep=rep, dk=dk, dv=dv),
        grid=(batch, nh, n),
        in_specs=[qk, qk, vo, row, row],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((hb, dk, dv), jnp.float32)],
        compiler_params=_compiler_params(), interpret=interpret,
        name=KERNEL_FWD)(q, k, v, gr, br)
    return out if keep_states else out[0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _gdn(q, k, v, gr, br, cfg):
    return _forward(q, k, v, gr, br, cfg, False)


def _gdn_fwd(q, k, v, gr, br, cfg):
    o, states, inverses = _forward(q, k, v, gr, br, cfg, True)
    return o, (q, k, v, gr, br, states, inverses)


def _gdn_bwd(cfg, res, do):
    return _backward(cfg, *res, do)


@functools.partial(jax.jit, static_argnums=(0,))
def _backward(cfg, q, k, v, gr, br, states, inverses, do):
    hb, rep, dk, dv, interpret = cfg
    batch, seq, _ = q.shape
    n, nh = seq // CHUNK, gr.shape[1]
    qk, vo, row, state, inv = _specs(hb, rep, dk, dv, n, True)
    shape = jax.ShapeDtypeStruct
    return tuple(pl.pallas_call(
        functools.partial(_bwd_kernel, heads=hb, rep=rep, dk=dk, dv=dv),
        grid=(batch, nh, n),
        in_specs=[qk, qk, vo, row, row, state, inv, vo],
        out_specs=[qk, qk, vo, row, row],
        out_shape=[shape(q.shape, q.dtype), shape(k.shape, k.dtype),
                   shape(v.shape, v.dtype), shape(gr.shape, jnp.float32),
                   shape(br.shape, jnp.float32)],
        scratch_shapes=[pltpu.VMEM((hb, dk, dv), jnp.float32)],
        compiler_params=_compiler_params(), interpret=interpret,
        name=KERNEL_BWD)(q, k, v, gr, br, states, inverses, do))


_gdn.defvjp(_gdn_fwd, _gdn_bwd, optimize_remat=True)


def gated_delta_pallas(q, k, v, g, beta, *, interpret: bool = False,
                       heads_per_step: int = HEADS_PER_STEP):
    """The kernels' own entry point: `chunk_gated_delta_rule`'s arguments
    (with or without the batch axis) where `pallas_fits`. `interpret` runs
    the kernels through the Pallas interpreter, for tests off the TPU."""
    if not pallas_fits(q, v):
        raise ValueError(
            f"gated_delta_pallas wants dk and dv in multiples of {_LANES} "
            f"and bfloat16 or float32, got q {q.shape} {q.dtype}, "
            f"v {v.shape} {v.dtype}")
    if q.ndim == 3:
        return gated_delta_pallas(
            q[None], k[None], v[None], g[None], beta[None],
            interpret=interpret, heads_per_step=heads_per_step)[0]
    batch, seq, _, dk = q.shape

    def slab(t):                      # (B, S, H, d) -> (B, S, H d)
        return t.reshape(batch, seq, -1)

    return _pallas_slab(slab(q), slab(k), slab(v), g, beta, dk, v.shape[-1],
                        interpret, heads_per_step).reshape(v.shape)


def _pallas_slab(q, k, v, g, beta, dk: int, dv: int, interpret: bool = False,
                 heads_per_step: int = HEADS_PER_STEP):
    """The kernels on slabs q, k (B, S, Hk dk), v (B, S, H dv) with g, beta
    (B, S, H) -> o (B, S, H dv)."""
    reliability_metrics.inc(tnames.GDN_SCAN_ROUTE_PALLAS)
    batch, seq, _ = v.shape
    heads = v.shape[-1] // dv
    rep = heads // (q.shape[-1] // dk)
    f32 = jnp.float32
    # heads a step: whole groups of the `rep` heads that share a key head
    hb = max([d for d in range(rep, max(heads_per_step, rep) + 1, rep)
              if heads % d == 0])
    nh = heads // hb
    pad = (-seq) % CHUNK
    n = (seq + pad) // CHUNK

    def whole_chunks(t):              # (B, S, ...) -> (B, S', ...)
        return jnp.pad(t, ((0, 0), (0, pad), (0, 0))) if pad else t

    def along_lanes(t):               # (B, S, H) -> (B, H/hb, n, hb, C)
        t = whole_chunks(t.astype(f32))
        return jnp.transpose(t.reshape(batch, n, CHUNK, nh, hb),
                             (0, 3, 1, 4, 2))

    o = _gdn(whole_chunks(q), whole_chunks(k), whole_chunks(v),
             jnp.cumsum(along_lanes(g), axis=-1), along_lanes(beta),
             (hb, rep, dk, dv, bool(interpret)))
    return o[:, :seq]
