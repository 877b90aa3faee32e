"""Pallas TPU kernel family for GBDT histograms: scatter-add as MXU matmuls.

XLA lowers the (node, feature, bin) scatter-add to a serialized scatter —
~4s/tree at 1M x 32 on v5e. This family reformulates it:

    hist[n, f, b] = sum_rows stat[row] * [node(row)==n] * [bin(row,f)==b]
                  = (node_onehot * stat).T @ bin_onehot_f        per feature

i.e. a (T, 3m).T @ (T, B) matmul per (feature, row-tile) — systolic-array
work instead of scatter, with both one-hots materialized only in VMEM. All
three statistics (grad, hess, count) ride one matmul by stacking them into
the 3m columns.

Layout honors TPU tiling (sublane x lane = 8 x 128): bins arrive transposed
(F_pad, n) with F padded to a multiple of FEATURE_BLOCK; each grid cell
(fb, t) owns a (FEATURE_BLOCK features x TILE_ROWS rows) stripe and its
(FEATURE_BLOCK, m, B) output block, accumulated across row tiles (init at
t == 0). Row-aligned stats are (1, n) so the block (1, TILE_ROWS) matches
the full sublane dim.

Valid for m = 2^level nodes up to M_MAX (VMEM-bounded 3m matmul columns);
deeper levels fall back to the XLA scatter path (histogram.py routes).

PRECISION CONTRACT: grad/hess operands are rounded to bfloat16 before the
MXU matmul (~0.4% per-value; accumulation stays f32), so TPU training can
pick different splits than the XLA/CPU scatter path near gain ties. The
precomputed one-hot planes (round 6) are exact {0,1} int8 and change
nothing about this contract. Where bit-reproducibility across backends
matters more than speed, set MMLSPARK_TPU_HIST=xla.

ROUTING (round 6). The family is one parametric kernel: factor the joint
key k = node * B + bin over radix digits (hi, lo) with k = hi * LO + lo.
LO = B degenerates to the DIRECT kernel (hi one-hot == node one-hot, lo
one-hot == bin one-hot); smaller LO trades the (B, T) bin-one-hot build
for a (mB/LO, T) hi build plus a 3 x (mB/LO) x T stat lift. The per-
(feature, tile) VPU-unit model is ~(2*LO + 5*mB/LO), minimized near
LO = sqrt(2.5*mB) — hardware-friendly LO comes from the table below.

    measured on v5e, 1M x 128 x 256, ms/call (rounds 4-5, 10-rep steady;
    an older kernel, JAX and device path — history, not a current figure):
    m        1      2      4      8      16     (32+)
    direct   26.8   26.8   26.8   26.8   26.8   26.8
    joint64  12.0   11.7   13.6   25.6   42.4
    joint128 16.5   17.2   21.8   17.8   23.1

    routing table (kernel_route): per (m, B) -> LO, None = direct
    B >= 128 (from the table above): m <= 4 -> 64;  m in (4, 16] -> 128;
                                     else direct
    64 <= B < 128 (analytic):        m <= 2 -> 16;  m in (2, 4]  -> 32;
                                     else direct
    B < 64: direct (the bin one-hot is already small next to the lift).

    COMPILES ON THE CHIP (PR 21: TPU v5 lite, jax 0.9.0, libtpu 0.0.34).
    chip_smoke.py's kernel phase compiles every row this table selects
    for B in {64, 255} x m in {1..64} with Mosaic and holds each inside
    the bf16 contract below against the XLA scatter — including the
    narrow-lane LO=16/32 joint layouts at B=64 and the direct kernel at
    m=64, B=255 under the default scoped-VMEM limit. No row has been
    TIMED since the kernel was rewritten: which LO wins where is still the
    old measurement (B >= 128) or the analytic model (B < 128).

LEVEL-INVARIANT ONE-HOT REUSE (round 6). The lo digit of the joint key is
bin % LO whenever LO divides B — independent of the node assignment, i.e.
invariant across levels, trees, and boosting iterations. `build_hist_plan`
precomputes the lo one-hot planes ONCE per fit as (F_pad, LO, n_pad) int8
resident in HBM; `_hist_kernel_planes` streams them straight into the MXU
(one int8->bf16 convert per element instead of compare+select+convert),
leaving only the hi digit (mB/LO rows) built per level. Per-element VPU
model ~(LO + 5*mB/LO); HBM traffic grows to F*n*(1+LO) bytes per level —
this deliberately spends memory headroom to buy VPU time. Planes require
LO | B (plan_lo_bins) and exist at LO=16 only, i.e. for 64 <= B < 128: the
LO=64 plane block that B >= 128 would need — (32, 64, 4096) int8, double-
buffered — exhausts v5e's scoped VMEM at compile time (PR 21 chip run), so
those shapes take the computed joint route with or without a plan. The
LO=16 planes kernel compiles and matches the scatter on the chip. Opt-in
via MMLSPARK_TPU_HIST=planes until a chip A/B proves a win: the analytic model puts planes within ~10-20% of the computed joint
at 8M x 32 x 64 because the VPU saving is partially repaid as plane
streaming (4 GB/level at LO=16).

Measured-and-REJECTED ledger (rounds 3-6):
- separate-node factored radix (round 4, b = hi*LO + lo with a 3m-row
  outer product): beaten by the joint-key form at every m (12.4 vs 12.0
  even at m=1); kernel deleted in round 6 — the joint kernel is its
  structural successor and the routing table no longer picks it.
- row compaction (gather the ~50% live rows pre-kernel): at 1M rows the
  compaction costs 9.7 ms (nonzero) + 14.9 ms (row gather of (1M,128)
  u8) + ~9 ms per (1M,) f32 stat gather — TPU gathers run ~10 GB/s, far
  under the 6-14 ms/level the halved kernel would save.
- feature grouping (G features share one (G*rows, T)@(T, G*LO) MXU pass,
  diagonal blocks kept): 5-15% SLOWER at every (m, G) tried.
- TILE_ROWS 16384/32768: flat (not per-grid-cell-overhead-bound).
- count-plane shortcut (unit counts make the c lift == hi_oh): the
  concatenate's layout copy costs more than the saved multiplies.
- FULL precomputed (B, n) one-hot planes (round 6, analytic): per-level
  streaming is F*n*(1+B) bytes = 16.6 GB at the headline — 20 ms/level
  at measured copy bandwidth, more than the whole VPU time it saves, and
  the resident planes (16 GB int8 at 8M x 32 x 64) do not fit v5e HBM
  next to the working set. The lo-plane form above is the viable subset.
- bit-packed planes (round 6, analytic): unpacking one bit per (lo, row)
  lane costs shift+mask+compare ~= the compare+select it replaces; the
  packing only reduces HBM traffic, which at 1.8% utilization is not the
  binding resource. Revisit only if planes win AND turn memory-bound.
- VMEM-cached one-hot reuse across a level's passes (round 6,
  structural): the 3-stat sharing already rides one matmul (the w3
  stack), sibling subtraction leaves exactly ONE kernel call per level,
  and VMEM does not persist across pallas_call invocations — there is no
  second pass left to share with inside a level.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# tile sweep on v5e (rounds 4-5): 8192/32 was ~5% faster than 4096/16. The
# VMEM worst case (m = M_MAX = 64 nodes with 255 bins: 3x(32,64,255) f32
# outputs + (255,8192) bf16 bin one-hot + (192,8192) bf16 stat rows)
# compiles and runs on v5e under the default scoped-VMEM limit (PR 21)
TILE_ROWS = 8192
FEATURE_BLOCK = 32
M_MAX = 64  # max nodes per level handled here (VMEM bound on the 3m columns)

JOINT_MIN_BINS = 64   # round 6: the routed radix family now covers B = 64
JOINT_M_MAX = 16      # beyond this the hi one-hot outgrows the saving

# precomputed-plane route: (FEATURE_BLOCK, LO, T) int8 blocks are double-
# buffered by the pallas pipeline, so the plane route halves the row tile
# to keep 2 x FEATURE_BLOCK x LO x T int8 (4 MiB at LO=16) inside VMEM
PLANES_TILE_ROWS = 4096
PLANES_M_MAX = 4      # deeper levels: the hi lift dominates, direct wins


def plan_lo_bins(n_bins: int) -> int:
    """LO digit width for the precomputed-plane route (0 = unavailable).
    Planes need LO | B so that (node*B + bin) % LO == bin % LO is level-
    invariant — non-divisible bin counts cannot take the route — and
    LO < B (LO == B is the rejected full-plane form). 64 <= B < 128 pairs
    with LO=16 (the analytic optimum at the shallow m the route covers);
    B >= 128 has no plane digit: its LO=64 plane block does not fit
    scoped VMEM (see the module docstring)."""
    if JOINT_MIN_BINS <= n_bins < 128 and n_bins % 16 == 0:
        return 16
    return 0


def kernel_route(n_nodes: int, n_bins: int, has_planes: bool = False):
    """Kernel selection per (m, B): ('direct'|'joint'|'planes', LO).

    The table at the top of this file is THE source of truth; this
    function is its executable form (pinned by tests so a silent route
    change is a visible diff). `has_planes` marks a fit that prebuilt
    level-invariant lo one-hot planes (build_hist_plan)."""
    if has_planes and n_nodes <= PLANES_M_MAX:
        lo = plan_lo_bins(n_bins)
        if lo:
            return ("planes", lo)
    if n_bins >= 128 and n_nodes <= JOINT_M_MAX:
        return ("joint", 64 if n_nodes <= 4 else 128)
    if 128 > n_bins >= JOINT_MIN_BINS and n_nodes <= 4:
        return ("joint", 16 if n_nodes <= 2 else 32)
    return ("direct", n_bins)


def _hist_kernel(bins_ref, node_ref, g_ref, h_ref, c_ref, hg_ref, hh_ref,
                 hc_ref, *, m: int, n_bins: int):
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        hg_ref[...] = jnp.zeros_like(hg_ref)
        hh_ref[...] = jnp.zeros_like(hh_ref)
        hc_ref[...] = jnp.zeros_like(hc_ref)

    node = node_ref[0, :]   # (T,) i32 node id; outside [0, m) = inactive
    g = g_ref[0, :]
    h = h_ref[0, :]
    c = c_ref[0, :]         # bagging/padding count indicator (see histogram.py)
    T = node.shape[0]

    # Build BOTH matmul operands pre-transposed — (rows, T) with the
    # contraction dim in lanes — and contract dim 1 on each side. Mosaic
    # otherwise materializes VPU transposes of the K-major (T, small)
    # operands, which dominated the kernel 4x (measured 35ms -> 8ms at
    # 1M x 32 x 64 on v5e).
    #
    # bf16 one-hots: {0,1} and the stat values round once; the MXU
    # accumulates in f32 (preferred_element_type), so per-bin sums keep f32
    # accumulation error. Halves VPU one-hot traffic and doubles MXU rate
    # vs f32 operands.
    node_oh_t = (jax.lax.broadcasted_iota(jnp.int32, (m, T), 0)
                 == node[None, :]).astype(jnp.float32)       # (m, T)
    w_t = jnp.concatenate(
        [(node_oh_t * g[None, :]).astype(jnp.bfloat16),
         (node_oh_t * h[None, :]).astype(jnp.bfloat16),
         (node_oh_t * c[None, :]).astype(jnp.bfloat16)], axis=0)  # (3m, T)

    for i in range(FEATURE_BLOCK):  # static unroll over the feature stripe
        b = bins_ref[i, :].astype(jnp.int32)  # (T,) u8 -> i32 in VMEM
        bin_oh_t = (jax.lax.broadcasted_iota(jnp.int32, (n_bins, T), 0)
                    == b[None, :]).astype(jnp.bfloat16)      # (B, T)
        res = jax.lax.dot_general(w_t, bin_oh_t, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32)  # (3m, B)
        hg_ref[i] += res[:m]
        hh_ref[i] += res[m:2 * m]
        hc_ref[i] += res[2 * m:]


def _hist_kernel_joint(bins_ref, node_ref, g_ref, h_ref, c_ref, hg_ref,
                       hh_ref, hc_ref, *, m: int, n_hi: int, lo_bins: int,
                       n_bins: int):
    """Joint-key radix kernel: k = node * n_bins + bin factored over
    (hi, lo). The stats ride as THREE rows (no node dimension); the node
    enters through the hi one-hot, so the outer-product lift costs
    3 * n_hi * T — that is what keeps the routed (m, B) points ahead of
    the direct kernel (measured/analytic table at the top of this file).
    Inactive rows carry key -1 -> hi -1, matching no hi one-hot row, so
    they vanish exactly like the direct kernel's node mask."""
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        hg_ref[...] = jnp.zeros_like(hg_ref)
        hh_ref[...] = jnp.zeros_like(hh_ref)
        hc_ref[...] = jnp.zeros_like(hc_ref)

    node = node_ref[0, :]
    g = g_ref[0, :]
    h = h_ref[0, :]
    c = c_ref[0, :]
    T = node.shape[0]
    w3 = jnp.stack([g, h, c], axis=0).astype(jnp.bfloat16)   # (3, T)
    valid = (node >= 0) & (node < m)

    for i in range(FEATURE_BLOCK):
        b = bins_ref[i, :].astype(jnp.int32)                 # (T,)
        key = jnp.where(valid, node * n_bins + b, -1)        # [0, m*B)
        hi = key // lo_bins                                  # -1 drops out
        lo = key - hi * lo_bins
        hi_oh = (jax.lax.broadcasted_iota(jnp.int32, (n_hi, T), 0)
                 == hi[None, :]).astype(jnp.bfloat16)        # (n_hi, T)
        lo_oh = (jax.lax.broadcasted_iota(jnp.int32, (lo_bins, T), 0)
                 == lo[None, :]).astype(jnp.bfloat16)        # (LO, T)
        u = (w3[:, None, :] * hi_oh[None, :, :]).reshape(3 * n_hi, T)
        res = jax.lax.dot_general(u, lo_oh, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        hg_ref[i] += res[:n_hi].reshape(n_hi, lo_bins)
        hh_ref[i] += res[n_hi:2 * n_hi].reshape(n_hi, lo_bins)
        hc_ref[i] += res[2 * n_hi:].reshape(n_hi, lo_bins)


def _hist_kernel_planes(planes_ref, bins_ref, node_ref, g_ref, h_ref, c_ref,
                        hg_ref, hh_ref, hc_ref, *, m: int, n_hi: int,
                        lo_bins: int, n_bins: int):
    """Joint-key radix with the level-invariant lo one-hot PRECOMPUTED
    (build_hist_plan): planes_ref holds (FEATURE_BLOCK, LO, T) int8 lo
    one-hots of bin % LO, streamed from HBM straight into the matmul (one
    convert per element — no compare/select rebuild per level). Only the
    hi digit hi = node*(B/LO) + bin//LO is built here; LO | B guarantees
    the key span m*B splits exactly into n_hi = m*B/LO rows (no key
    padding). Inactive rows get hi < 0 and vanish via the hi one-hot."""
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        hg_ref[...] = jnp.zeros_like(hg_ref)
        hh_ref[...] = jnp.zeros_like(hh_ref)
        hc_ref[...] = jnp.zeros_like(hc_ref)

    node = node_ref[0, :]
    g = g_ref[0, :]
    h = h_ref[0, :]
    c = c_ref[0, :]
    T = node.shape[0]
    w3 = jnp.stack([g, h, c], axis=0).astype(jnp.bfloat16)   # (3, T)
    nb_hi = n_bins // lo_bins
    valid = (node >= 0) & (node < m)
    # invalid rows: base -nb_hi keeps hi negative after adding bin//LO
    node_hi = jnp.where(valid, node * nb_hi, -nb_hi)         # (T,)

    for i in range(FEATURE_BLOCK):
        b = bins_ref[i, :].astype(jnp.int32)                 # (T,)
        hi = node_hi + b // lo_bins                          # < 0 drops out
        hi_oh = (jax.lax.broadcasted_iota(jnp.int32, (n_hi, T), 0)
                 == hi[None, :]).astype(jnp.bfloat16)        # (n_hi, T)
        lo_oh = planes_ref[i].astype(jnp.bfloat16)           # (LO, T)
        u = (w3[:, None, :] * hi_oh[None, :, :]).reshape(3 * n_hi, T)
        res = jax.lax.dot_general(u, lo_oh, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        hg_ref[i] += res[:n_hi].reshape(n_hi, lo_bins)
        hh_ref[i] += res[n_hi:2 * n_hi].reshape(n_hi, lo_bins)
        hc_ref[i] += res[2 * n_hi:].reshape(n_hi, lo_bins)


@functools.partial(jax.jit, static_argnames=("n_bins",))
def build_hist_plan(bins, n_bins: int):
    """Level-invariant histogram plan: (F_pad, LO, n_pad) int8 one-hot of
    bin % LO, built ONCE per fit (the bins never change across levels,
    trees, or boosting iterations) and resident in HBM — F*LO*n bytes
    (4 GB at 8M x 32 with LO=16). Padding matches the planes kernel's
    grid (FEATURE_BLOCK x PLANES_TILE_ROWS); padded rows one-hot lo=0
    but are dropped by the kernel's node mask. Returns None-equivalent
    (raises) when plan_lo_bins(n_bins) == 0 — callers gate on it."""
    lo = plan_lo_bins(n_bins)
    if not lo:
        raise ValueError(f"no plane digit divides n_bins={n_bins}; "
                         "the planes route needs LO | B (plan_lo_bins)")
    n, F = bins.shape
    pad_f = (-F) % FEATURE_BLOCK
    pad_n = (-n) % PLANES_TILE_ROWS
    bt = bins.T  # (F, n) u8
    if pad_f or pad_n:
        bt = jnp.pad(bt, ((0, pad_f), (0, pad_n)))
    lo_val = bt.astype(jnp.int32) % lo                       # (F_pad, n_pad)
    return (jax.lax.broadcasted_iota(
        jnp.int32, (bt.shape[0], lo, bt.shape[1]), 1)
        == lo_val[:, None, :]).astype(jnp.int8)


@functools.partial(jax.jit,
                   static_argnames=("n_nodes", "n_bins", "plane_lo",
                                    "route", "interpret"))
def pallas_hist(bins, grad, hess, node_local, active, n_nodes: int,
                n_bins: int, count_w=None, lo_planes=None, plane_lo: int = 0,
                route=None, interpret: bool = False):
    """Same contract as histogram._xla_hist: (n,F) uint8 bins + per-row stats
    -> three (n_nodes, F, n_bins) f32 histograms.

    `lo_planes`/`plane_lo`: per-fit precomputed lo one-hot planes from
    build_hist_plan — enables the 'planes' route for shallow levels.
    `route`: explicit ('direct'|'joint'|'planes', LO) override, the hook
    of tests that run one route; None = the kernel_route table."""
    n, F = bins.shape
    # uint8 end to end: the transpose stays 1 byte/element in HBM (an i32
    # operand would materialize 4x the traffic and a convert pass per level;
    # measured 1.67 -> 1.48 ms/call at 1M x 32 x 64 on v5e). XLA CSE dedupes
    # the transpose across the per-level calls in one tree.
    bins_t = bins.T  # (F, n) u8
    node = jnp.where(active, node_local, -1).astype(jnp.int32)
    cnt = (jnp.ones_like(hess) if count_w is None
           else count_w.astype(jnp.float32))

    if route is None:
        route = kernel_route(n_nodes, n_bins,
                             has_planes=(lo_planes is not None
                                         and plane_lo > 0))
    kind, lo = route
    if kind == "planes" and (lo_planes is None or plane_lo != lo):
        raise ValueError(f"planes route at LO={lo} needs matching "
                         f"build_hist_plan output (got plane_lo={plane_lo})")

    tile_rows = PLANES_TILE_ROWS if kind == "planes" else TILE_ROWS
    pad_f = (-F) % FEATURE_BLOCK
    pad_n = (-n) % tile_rows
    if pad_f or pad_n:
        bins_t = jnp.pad(bins_t, ((0, pad_f), (0, pad_n)))
        node = jnp.pad(node, (0, pad_n), constant_values=-1)
        grad = jnp.pad(grad, (0, pad_n))
        hess = jnp.pad(hess, (0, pad_n))
        cnt = jnp.pad(cnt, (0, pad_n))
    F_pad, n_pad = F + pad_f, n + pad_n
    nT = n_pad // tile_rows
    nFB = F_pad // FEATURE_BLOCK

    node2 = node[None, :]
    g2 = grad.astype(jnp.float32)[None, :]
    h2 = hess.astype(jnp.float32)[None, :]
    c2 = cnt[None, :]

    row_spec = pl.BlockSpec((1, tile_rows), lambda fb, t: (0, t))
    in_specs = [
        pl.BlockSpec((FEATURE_BLOCK, tile_rows), lambda fb, t: (fb, t)),
        row_spec, row_spec, row_spec, row_spec,
    ]
    cparams = pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"))

    if kind == "planes":
        if lo_planes.shape != (F_pad, lo, n_pad):
            raise ValueError(
                f"hist plan shape {lo_planes.shape} does not match this "
                f"call's padded ({F_pad}, {lo}, {n_pad}) — the plan must "
                f"be built from the SAME bins matrix (build_hist_plan)")
        n_hi = n_nodes * (n_bins // lo)          # LO | B: exact key span
        kernel = functools.partial(_hist_kernel_planes, m=n_nodes,
                                   n_hi=n_hi, lo_bins=lo, n_bins=n_bins)
        plane_spec = pl.BlockSpec((FEATURE_BLOCK, lo, tile_rows),
                                  lambda fb, t: (fb, 0, t))
        hg, hh, hc = pl.pallas_call(
            kernel,
            grid=(nFB, nT),
            in_specs=[plane_spec] + in_specs,
            out_specs=[pl.BlockSpec((FEATURE_BLOCK, n_hi, lo),
                                    lambda fb, t: (fb, 0, 0))] * 3,
            out_shape=[jax.ShapeDtypeStruct((F_pad, n_hi, lo),
                                            jnp.float32)] * 3,
            compiler_params=cparams,
            interpret=interpret,
        )(lo_planes, bins_t, node2, g2, h2, c2)
        merge = lambda a: a.reshape(F_pad, n_nodes, n_bins)
        hg, hh, hc = merge(hg), merge(hh), merge(hc)
        return (hg[:F].transpose(1, 0, 2), hh[:F].transpose(1, 0, 2),
                hc[:F].transpose(1, 0, 2))
    if kind == "joint":
        # joint-key radix (see routing table above): pad the combined key
        # span m*B up to a LO multiple; padded key columns are never hit
        # (no row produces them) and are sliced off below
        key_span = n_nodes * n_bins
        key_pad = key_span + ((-key_span) % lo)
        n_hi = key_pad // lo
        kernel = functools.partial(_hist_kernel_joint, m=n_nodes,
                                   n_hi=n_hi, lo_bins=lo, n_bins=n_bins)
        hg, hh, hc = pl.pallas_call(
            kernel,
            grid=(nFB, nT),
            in_specs=in_specs,
            out_specs=[pl.BlockSpec((FEATURE_BLOCK, n_hi, lo),
                                    lambda fb, t: (fb, 0, 0))] * 3,
            out_shape=[jax.ShapeDtypeStruct((F_pad, n_hi, lo),
                                            jnp.float32)] * 3,
            compiler_params=cparams,
            interpret=interpret,
        )(bins_t, node2, g2, h2, c2)
        merge = lambda a: a.reshape(F_pad, key_pad)[:, :key_span].reshape(
            F_pad, n_nodes, n_bins)
        hg, hh, hc = merge(hg), merge(hh), merge(hc)
        return (hg[:F].transpose(1, 0, 2), hh[:F].transpose(1, 0, 2),
                hc[:F].transpose(1, 0, 2))
    kernel = functools.partial(_hist_kernel, m=n_nodes, n_bins=n_bins)
    hg, hh, hc = pl.pallas_call(
        kernel,
        grid=(nFB, nT),
        in_specs=in_specs,
        out_specs=[pl.BlockSpec((FEATURE_BLOCK, n_nodes, n_bins),
                                lambda fb, t: (fb, 0, 0))] * 3,
        out_shape=[jax.ShapeDtypeStruct((F_pad, n_nodes, n_bins),
                                        jnp.float32)] * 3,
        compiler_params=cparams,
        interpret=interpret,
    )(bins_t, node2, g2, h2, c2)
    # (F_pad, m, B) -> (m, F, B)
    return (hg[:F, :, :n_bins].transpose(1, 0, 2),
            hh[:F, :, :n_bins].transpose(1, 0, 2),
            hc[:F, :, :n_bins].transpose(1, 0, 2))
