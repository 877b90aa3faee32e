"""Pallas TPU flash attention: exact attention in O(block) VMEM.

Within-chip complement of the cross-chip sequence parallelism in
parallel/ring_attention.py (SURVEY.md §5 — long context is first-class in
the TPU build; the reference has no attention ops at all). The ring handles
sequences sharded ACROSS devices; this kernel handles a long block WITHIN a
device without materializing the (S, S) score matrix in HBM:

    grid = (heads, q_blocks, k_blocks), k innermost. Each (h, qb) cell
    streams k-blocks through VMEM keeping the classic online-softmax
    carry (running max m, denominator l, unnormalized accumulator acc) in
    scratch; the normalized output is written once at the last k step.

Causal masking compares global q/k positions, so it works for any block
shape. Training: `flash_attention`'s custom VJP is a FLASH BACKWARD — two
Pallas kernels (dq over a (h, qb, kb) grid; dk/dv over (h, kb, qb))
recompute each P block from q/k and the forward's saved log-sum-exp, so
backward memory stays O(block) like the forward. `flash_attention_stats`'
VJP is ALSO flash (the same two kernels with lse := m and dsum := -dl — see
_flash_stats_bwd's shift-invariance derivation), so context-parallel ring
training is O(block) memory in both directions.

WHAT A CAUSAL CALL EXECUTES (measured on a v5e, PERF.md section 6, PR 27:
the sweep's table is there). The grid skips a cell wholly above the
diagonal, which leaves out nothing when a sequence is ONE block long: at
S = 1024 every (batch, head) is a single 1024 x 1024 cell, and until PR 27
it ran 9 matmuls (2 forward, 3 dq, 4 dk/dv) over the whole masked square
where the algorithm needs 6 over the triangle, 3.0x the operations. With
d_head = 64 every one of those matmuls has 64 as its contraction or its
output width and feeds half of the 128-wide MXU, so the shape's ceiling is
about 98 TFLOP/s and the whole-square kernels already ran at 72% to 91% of
it: there was nothing to tune, only operations to leave out. A cell ON the
diagonal of a causal self-attention call now walks its tile in four row
strips of 256 and computes each strip only up to the diagonal (10 of 16
sub-tiles, `_attend_boundary`); on a one-cell grid the forward also drops the
online-softmax carry (`single` in _flash_kernel). Per 128 cells of
1024 x 1024 x 64 bf16: forward 0.53 -> 0.36 ms, dq 0.76 -> 0.56, dk/dv
1.00 -> 0.73. What did NOT pay, same table: exact sub-tiles (unmasked
below the diagonal, masked on it: more and smaller matmuls, the forward
SLOWER than the whole square), a `fori_loop` over sub-tiles (1.3x to 2.5x
the whole square), and grid blocks of 512 or 256 (1.2x and 2.1x: a grid
cell's fixed cost). Long sequences keep 1024 blocks: per-grid-cell
overhead dominates below that (see _auto_blocks), interior cells run
unmasked and untouched, and only the diagonal cells take the strips (16k
causal forward + backward 19.2 -> 18.7 ms). Matmuls run in the input dtype.

A SLIDING WINDOW AND A VALUE WIDTH (PR 35). `flash_attention(..., window=W)`
bounds causal self-attention to the W positions up to the query's own; such
a call runs kernels of its own (`flash_fwd_win`, `flash_dq_win`,
`flash_dkv_win`, below the unwindowed ones in this file), whose grids hold
only the cells a window reaches, so a call without a window traces and
compiles to what it always did. V may be wider or narrower than q and k
(differential attention reads two value heads side by side): every kernel
takes V's, the output's and their cotangents' blocks at V's own width.

PRECISION CONTRACT. Softmax statistics and every accumulation are f32.
The matmul PRODUCTS follow the input dtype (`_mxu_dot`): bf16 q/k/v take
the MXU's single bf16 pass; f32 q/k/v ask Mosaic for its fp32 contract
precision (`Precision.HIGHEST`), because at the default Mosaic rounds f32
operands to one bf16 pass and an f32 caller would get bf16 products
unasked (f32 rows then read 2.4e-3 to 6.4e-3 on the chip, the bf16 band).
Against `reference_attention` at
`jax.default_matmul_precision("highest")` on the chip (PR 21, TPU v5 lite,
S=2048, causal, max error over max |reference|): bf16 forward 7e-4 to
2.4e-3, gradients 3e-3 to 6e-3; f32 forward 1.3e-7 to 2.4e-7, gradients
3e-7 to 5e-5 at every block size from 256 to 1024. The f32 products cost
about 3.5x the single pass (8192 x 8 x 128 causal, one run of 10 reps:
forward 5.7 vs 1.7 ms, forward+backward 23.9 vs 6.9 ms; bf16 1.3 / 5.0
ms), so a trainer that wants the MXU's rate says compute_dtype="bfloat16".
In interpret mode (off-TPU) f32 is exact to ~1e-6, as on the chip.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..reliability.metrics import reliability_metrics
from ..telemetry import names as tnames

BLOCK_Q = 256
BLOCK_K = 256
# The kernels are per-grid-cell-overhead-bound at small blocks. Measured on
# a v5e (PR 27, 128 heads x 1024 x 64 bf16 causal, the three kernels
# together): one 1024 cell a head 2.30 ms, 512 blocks 2.76 ms (3 visible
# cells of 4, so 75% of the operations in 1.2x the time), 256 blocks
# 4.73 ms. 2048+ blocks fail to compile (VMEM); the f32 BACKWARD also
# fails at 1024 (f32 operand blocks double the VMEM footprint), so the
# backward caps at 512 for f32. _auto_blocks picks these per call.
_FWD_BLOCK = 1024
_BWD_BLOCK_BF16 = 1024
_BWD_BLOCK_F32 = 512
_BWD_WIDE_HEAD = 128      # heads wider than this take the f32 cap (_bwd_cap)

# The kernels' names, which reach the compiled program: a Pallas call's HLO
# instruction is named after the innermost element of JAX's name stack, so
# a trace event reads `%flash_dq.N = ... custom-call(` (under `jax.vmap`,
# `%vmap_flash_dq_.N`) and its `op_name` ends `.../flash_dq/pallas_call`.
KERNEL_FWD = "flash_fwd"
KERNEL_DQ = "flash_dq"
KERNEL_DKV = "flash_dkv"
KERNEL_STATS_FWD = "flash_stats_fwd"

# In-cell triangular schedule: a causal cell ON the diagonal walks its
# (block, block) tile in row strips of this height and computes, of each
# strip, only the columns up to the diagonal (_attend_boundary). Measured on a
# v5e (PR 27, PERF.md section 6; 128 cells of 1024 x 1024 x 64 bf16,
# forward + dq + dk/dv): whole square 2.30 ms; strips of 512 1.78, of 256
# 1.65, of 128 1.74. Blocks of 512 take it too (one 512 cell a head,
# 128 heads: 0.89 -> 0.67 ms; S = 1536 as 3 x 3 cells of 512, 64 heads: a
# wash, 2.53 -> 2.51); blocks of 256 do not (strips of 128 on a 4 x 4 grid
# of 256-cells: 4.72 -> 4.84 ms).
_DIAG_TILE = 256


def _pick_block(seq: int) -> int:
    """Largest block in {1024, 512, 256} whose padding waste stays under
    20% of the padded length — big blocks win on grid-cell overhead for
    long sequences, but an S=1100 sequence must not pad to 2048 (the
    overhead problem they solve only exists when the grid is large)."""
    for b in (_FWD_BLOCK, _FWD_BLOCK // 2, BLOCK_Q):
        pad = (-seq) % b
        if pad * 5 <= seq + pad:
            return b
    return BLOCK_Q


def _bwd_cap(dtype, head_dim: int) -> int:
    """Largest backward block the kernels' VMEM allows: 1024 for bf16
    operands of head size up to 128, 512 for f32 operands and for heads
    wider than 128 (bf16 at 256: the dk/dv kernel wants 16.8 MB of the
    16 MB scoped limit at 1024, compiled for a described v5e, PR 28; blocks
    of 512 compile, as do 1024 x 512, which would lose the in-cell causal
    schedule that wants square blocks)."""
    return (_BWD_BLOCK_BF16 if jnp.dtype(dtype) == jnp.bfloat16
            and head_dim <= _BWD_WIDE_HEAD else _BWD_BLOCK_F32)


def _auto_blocks(seq_q: int, seq_k: int, dtype, head_dim: int = 0) -> tuple:
    """(block_q, block_k, bwd_block_q, bwd_block_k) for this shape/dtype.
    Per-dim waste-bounded block choice; the backward uses smaller blocks
    for f32 and for wide heads (VMEM)."""
    bq = _pick_block(seq_q)
    bk = _pick_block(seq_k)
    bwd_cap = _bwd_cap(dtype, head_dim)
    return bq, bk, min(bq, bwd_cap), min(bk, bwd_cap)


def _diag_tile(causal: bool, block_q: int, block_k: int, seq_q: int,
               seq_k: int, q_offset=0, k_offset=0) -> Optional[int]:
    """Strip height for the in-cell triangular schedule, or None where the
    kernel cannot see a static diagonal: the call must be causal
    self-attention (`seq_q == seq_k`: the cells that are visible and need a
    mask are then exactly the ones with qb == kb) with square blocks of at
    least two strips and STATIC zero offsets. Ring attention's traced
    `axis_index` offsets, cross-attention and non-causal calls keep the
    whole-cell masked branch."""
    static0 = all(isinstance(o, int) and o == 0 for o in (q_offset, k_offset))
    if (causal and static0 and block_q == block_k and seq_q == seq_k
            and block_q % _DIAG_TILE == 0 and block_q > _DIAG_TILE):
        return _DIAG_TILE
    return None


def _attend_boundary(tile_fn, block: int, diag_tile: Optional[int]) -> None:
    """Run `tile_fn(masked, row0, rows, cols)` over a cell that needs its
    mask. Without a static diagonal that is the whole masked tile. With one
    (`diag_tile` = t) the cell lies ON the diagonal and is walked in row
    strips: strip i holds rows [i*t, (i+1)*t) and columns [0, (i+1)*t),
    masked as a whole (the causal compare only bites in its last t columns,
    the `seq_end` one wherever keys were padded). The sub-tiles above the
    diagonal are in no strip, so they cost no matmul, no exp and no mask
    pass. One wide masked strip a slice beat every finer split on the chip
    (module docstring)."""
    if diag_tile is None:
        tile_fn(True)
        return
    for r0 in range(0, block, diag_tile):
        tile_fn(True, r0, diag_tile, r0 + diag_tile)


def _count_tiles(n_q: int, n_k: int, block: int, tile: Optional[int],
                 kernels: int = 1) -> None:
    """Trace-time record of the geometry of `kernels` kernel calls, per
    (batch, head): sub-tiles the program can execute and sub-tiles the
    in-cell schedule leaves out. Where the schedule does not engage a grid
    cell is one sub-tile and nothing is left out."""
    if tile is None:
        computed, skipped = n_q * n_k, 0
    else:
        n = block // tile
        below = n_q * (n_q - 1) // 2          # cells wholly below the diagonal
        computed = below * n * n + n_q * (n * (n + 1) // 2)
        skipped = n_q * (n * (n - 1) // 2)
    reliability_metrics.inc(tnames.FLASH_TILES_COMPUTED, kernels * computed)
    reliability_metrics.inc(tnames.FLASH_TILES_SKIPPED, kernels * skipped)


def _mxu_dot(a, b, contract, exact: bool):
    """In-kernel matmul with f32 accumulation, contracting dimension
    `contract[0]` of `a` with `contract[1]` of `b`. `exact` is "the
    kernel's q/k/v are f32": those dots ask for Precision.HIGHEST
    (Mosaic's fp32 contract precision), because at the default Mosaic
    rounds f32 operands to ONE bf16 pass and an f32 caller would silently
    get bf16 products. bf16 kernels keep the default single pass for every
    dot, the f32-cotangent ones of the stats backward included (see
    PRECISION CONTRACT in the module docstring)."""
    return jax.lax.dot_general(
        a, b, (((contract[0],), (contract[1],)), ((), ())),
        precision=jax.lax.Precision.HIGHEST if exact else None,
        preferred_element_type=jnp.float32)


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  n_k: int, block_q: int, block_k: int, seq_end,
                  causal: bool, scale: float, q_offset=0,
                  k_offset=0, m_out_ref=None, l_out_ref=None,
                  normalize: bool = True, diag_tile: Optional[int] = None):
    # q_offset/k_offset/seq_end may be static ints or traced SMEM scalars
    # (ring attention's per-device offsets come from axis_index)
    qb = pl.program_id(1)
    kb = pl.program_id(2)
    # a static diagonal on a one-step k grid (S <= block): each head's only
    # cell is the diagonal one and every strip sees all its keys at once,
    # so the carry and its scratch go unused (measured per 128 cells: 0.64
    # ms with the carry merged, 0.45 without, 0.36 written straight out;
    # PERF.md section 6, PR 27)
    single = diag_tile is not None and n_k == 1

    def _attend(masked: bool, r0: int = 0, nr: int = block_q,
                nc: int = block_k):
        # rows [r0, r0 + nr) against the first nc keys of the cell's tile,
        # the whole tile by default. Matmuls run in the INPUT dtype with
        # f32 accumulation (preferred_element_type): bf16 operands use the
        # MXU's full bf16 rate (~4x the f32 rate on v5e) and softmax/l/m
        # math stays f32.
        rows, cols = pl.ds(r0, nr), pl.ds(0, nc)
        q = q_ref[0, rows, :] * jnp.asarray(scale, q_ref.dtype)   # (nr, D)
        k = k_ref[0, cols, :]                                     # (nc, D)
        v = v_ref[0, cols, :]                                     # (nc, D)
        exact = q_ref.dtype == jnp.float32
        s = _mxu_dot(q, k, (1, 1), exact)
        if masked:
            # sublane/lane iotas broadcast in the compare: no (nr, nc)
            # iota materialization
            q_pos = q_offset + qb * block_q + r0 + jax.lax.broadcasted_iota(
                jnp.int32, (nr, 1), 0)
            k_pos = k_offset + kb * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (1, nc), 1)
            valid = k_pos < seq_end                   # padded keys drop out
            if causal:
                valid = valid & (q_pos >= k_pos)
            s = jnp.where(valid, s, -1e30)

        m_blk = jnp.max(s, axis=-1, keepdims=True)    # (nr, 1)
        if single:
            # one exact softmax over the strip's keys, written straight out
            p = jnp.exp(s - m_blk)
            l_blk = jnp.sum(p, axis=-1, keepdims=True)
            pv = _mxu_dot(p.astype(v.dtype), v, (1, 0), exact)
            o_ref[0, rows, :] = (pv / jnp.maximum(l_blk, 1e-30)
                                 ).astype(o_ref.dtype)
            m_out_ref[0, rows, :] = m_blk
            l_out_ref[0, rows, :] = l_blk
            return
        m_prev = m_ref[rows, :]
        l_prev = l_ref[rows, :]
        m_new = jnp.maximum(m_prev, m_blk)
        # NOTE: p is deliberately NOT masked with `valid` here — an extra
        # where on the (Bq, Bk) tile adds measurable inner-loop VPU work at
        # zero benefit for supported callers. The only rows affected are
        # ones that have seen NO valid key yet (m_new still -1e30, masked
        # entries contribute exp(0)=1): impossible on the normalize path
        # (causal row i always sees key 0; padding only trims the tail),
        # and on the stats path such rows are FLAGGED by m == -1e30 — the
        # ring consumer's merge weight exp(m - m_new) zeroes them. Direct
        # flash_attention_stats callers must treat m == -1e30 rows as
        # "no visible keys" rather than normalizing acc/l.
        p = jnp.exp(s - m_new)                        # (nr, nc)
        alpha = jnp.exp(m_prev - m_new)               # rescale old carry
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[rows, :] = (acc_ref[rows, :] * alpha
                            + _mxu_dot(p.astype(v.dtype), v, (1, 0), exact))
        m_ref[rows, :] = m_new
        l_ref[rows, :] = l_new

    if single:
        _attend_boundary(_attend, block_q, diag_tile)
        return

    @pl.when(kb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)

    # causal: a k-block wholly above the diagonal contributes nothing, so
    # its cell runs no matmul (DMA still streams the block, which is
    # bandwidth-trivial next to the MXU work). That skips nothing when the
    # grid has ONE cell a side (S <= block); what is above the diagonal
    # INSIDE a cell is left out by _attend_boundary
    visible = (not causal) or (k_offset + kb * block_k
                               <= q_offset + qb * block_q + block_q - 1)
    # a block needing NO mask at all: every key is < seq_end and (causal)
    # every q_pos >= k_pos. Interior blocks take the maskless branch: the
    # iota/compare/where passes over the (Bq, Bk) tile are VPU work that
    # only boundary blocks need
    full = k_offset + (kb + 1) * block_k <= seq_end
    if causal:
        full = full & (k_offset + (kb + 1) * block_k - 1
                       <= q_offset + qb * block_q)

    @pl.when(full)
    def _attend_full():
        _attend(masked=False)

    @pl.when(visible & jnp.logical_not(full))
    def _attend_masked():
        # with a static diagonal (diag_tile) these are exactly the cells ON
        # it: they walk their lower triangle, each strip's carry running on
        # through m/l/acc as it does from cell to cell
        _attend_boundary(_attend, block_q, diag_tile)

    @pl.when(kb == n_k - 1)
    def _finish():
        if normalize:
            o_ref[0] = (acc_ref[...]
                        / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)
        else:  # stats mode: unnormalized accumulator + carry for merging
            o_ref[0] = acc_ref[...].astype(o_ref.dtype)
        if m_out_ref is not None:
            m_out_ref[0] = m_ref[...]
            l_out_ref[0] = l_ref[...]


def _pad_blocks(q, k, v, block_q: int, block_k: int):
    """Pad (H, S, D) operands up to block multiples; returns the padded
    arrays + (s, sk, n_q, n_k). One implementation for both entry points so
    padding/grid logic can never diverge."""
    h, s, d = q.shape
    sk = k.shape[1]
    pad_q = (-s) % block_q
    pad_k = (-sk) % block_k
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0)))
    return q, k, v, s, sk, (s + pad_q) // block_q, (sk + pad_k) // block_k


_COMPILER_PARAMS = None


def _compiler_params():
    global _COMPILER_PARAMS
    if _COMPILER_PARAMS is None:
        _COMPILER_PARAMS = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"))
    return _COMPILER_PARAMS


def _flash_forward(q, k, v, causal: bool, scale: float, block_q: int,
                   block_k: int, interpret: bool):
    """(H, S, D) per-head layout in, (H, S, D) out. Delegates to the
    LSE-emitting variant (two (H, S, 1) extra outputs are noise next to the
    O itself) so there is exactly ONE pallas_call configuration for the
    normalized forward — the forward and its VJP can never diverge."""
    out, _ = _flash_forward_lse(q, k, v, causal, scale, block_q, block_k,
                                interpret)
    return out


def flash_attention_stats(q, k, v, q_offset, k_offset, causal: bool,
                          scale: float, block_q: Optional[int] = None,
                          block_k: Optional[int] = None,
                          interpret: Optional[bool] = None):
    """Streaming-softmax PARTIAL attention for one K/V block: returns the
    UNNORMALIZED accumulator plus the (m, l) carry, in the shapes ring
    attention merges — acc (S, H, D) f32, m/l (H, S). q_offset/k_offset are
    the blocks' global positions (causal masking across shards; traced
    values welcome — they enter the kernel through SMEM). Differentiable
    with a FLASH backward (O(block) memory — see _flash_stats_bwd; exact
    for shift-invariant consumers like the ring merge). This is what lets
    ring attention run flash WITHIN each device while `ppermute` rotates
    K/V ACROSS devices, in both training directions.

    CONTRACT (tested in test_flash_attention.py::test_stats_no_visible_key
    _contract): a q row with NO visible key in this block (causal offsets)
    returns garbage acc/l FLAGGED by m == -1e30 — consumers must fold such
    rows with zero weight (the ring merge's exp(m - m_new) does exactly
    that) instead of normalizing acc/l directly. Masking them inside the
    kernel would add inner-loop VPU work on every tile to benefit only
    this degenerate case (see the p computation note)."""
    if interpret is None:
        interpret = jax.devices()[0].platform != "tpu"
    a_bq, a_bk, _, _ = _auto_blocks(q.shape[0], k.shape[0], q.dtype)
    return _flash_stats_vjp(q, k, v,
                            jnp.asarray(q_offset, jnp.int32),
                            jnp.asarray(k_offset, jnp.int32),
                            bool(causal), float(scale),
                            int(block_q) if block_q is not None else a_bq,
                            int(block_k) if block_k is not None else a_bk,
                            bool(interpret))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _flash_stats_vjp(q, k, v, q_offset, k_offset, causal, scale, block_q,
                     block_k, interpret):
    return _flash_stats_forward(q, k, v, q_offset, k_offset, causal, scale,
                                block_q, block_k, interpret)


def _stats_xla_reference(q, k, v, q_offset, k_offset, causal, scale):
    """Dense XLA implementation of the stats contract (backward pass)."""
    s = jnp.einsum("qhd,khd->hqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    q_pos = q_offset + jnp.arange(q.shape[0])
    k_pos = k_offset + jnp.arange(k.shape[0])
    if causal:
        s = jnp.where((q_pos[:, None] >= k_pos[None, :])[None], s, -1e30)
    m = jnp.maximum(jnp.max(s, axis=-1), -1e30)             # (H, S)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum("hqk,khd->qhd", p, v.astype(jnp.float32))
    return acc, m, l


# Debug escape hatch for the shift-invariance gradient contract (see
# _flash_stats_bwd and the ops package docstring): when True, stats
# gradients route through the dense XLA reference VJP — exact for ALL
# consumers including non-shift-invariant readouts of (acc, m, l), at
# O(S^2) memory. Flip it to verify a new consumer's gradients match the
# flash path before trusting the O(block) backward.
# TRACE-TIME flag: it is read when the backward is traced, so a jitted
# function compiled before the flip keeps the flash path — flip it BEFORE
# building the jit (or call jax.clear_caches()); comparing two calls of
# one already-compiled function compares the flash path against itself.
DEBUG_STATS_EXACT_VJP = False


def _flash_stats_fwd(q, k, v, q_offset, k_offset, causal, scale, block_q,
                     block_k, interpret):
    out = _flash_stats_forward(q, k, v, q_offset, k_offset, causal, scale,
                               block_q, block_k, interpret)
    # the running max m is the only extra residual the flash backward
    # needs (it is the stats path's "lse")
    return out, (q, k, v, q_offset, k_offset, out[1])


def _flash_stats_bwd(causal, scale, block_q, block_k, interpret, res, g):
    """FLASH backward for the stats contract — O(block) memory in both
    directions (round-3 verdict item 4; the old implementation rebuilt the
    dense per-block P matrix, capping per-device sequence length exactly
    where context parallelism exists).

    Derivation: stats returns (acc, m, l) with acc_i = sum_j e^{s_ij-m_i}
    v_j, l_i = sum_j e^{s_ij-m_i}. Any SHIFT-INVARIANT consumer G — one
    with G(acc e^{-d}, m+d, l e^{-d}) = G(acc, m, l), which the ring merge
    satisfies (its weights e^{m-m_new} cancel the reference shift) — obeys
    the identity -da.acc + dm - dl*l = 0, which exactly cancels the argmax
    subgradient terms. What remains is ds_ij = p_ij (da_i.v_j + dl_i):
    the SAME recurrence as the normalized backward with lse := m and
    dsum := -dl, so both paths share the two Pallas kernels. The dm
    cotangent is consumed by that identity (non-shift-invariant consumers
    of m are outside the contract, like direct normalizers of flagged
    rows)."""
    import jax.dtypes
    q, k, v, q_offset, k_offset, m = res
    if DEBUG_STATS_EXACT_VJP:
        # exact-for-all-consumers reference path: differentiates the dense
        # stats (including the m cotangent) so a new consumer can check
        # its gradients against the flash path (ops package docstring)
        zero = np.zeros((), jax.dtypes.float0)
        _, ref_vjp = jax.vjp(
            lambda qq, kk, vv: _stats_xla_reference(
                qq, kk, vv, q_offset, k_offset, causal, scale), q, k, v)
        dq, dk, dv = ref_vjp(tuple(x.astype(jnp.float32) for x in g))
        return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
                zero, zero)
    qh = jnp.moveaxis(q, 1, 0)    # (H, S, D)
    kh = jnp.moveaxis(k, 1, 0)
    vh = jnp.moveaxis(v, 1, 0)
    d_acc, _d_m, d_l = g
    da_h = jnp.moveaxis(d_acc.astype(jnp.float32), 1, 0)      # (H, S, D)
    m3 = m[..., None]                                         # (H, S, 1)
    dsum = -d_l[..., None].astype(jnp.float32)                # (H, S, 1)
    # the backward caps its blocks by dtype (f32 operand blocks exceed
    # VMEM at 1024 — same caps as _auto_blocks)
    cap = (_BWD_BLOCK_BF16 if jnp.dtype(q.dtype) == jnp.bfloat16
           else _BWD_BLOCK_F32)
    dq, dk, dv = _flash_backward(
        qh, kh, vh, None, m3, da_h, causal, scale,
        min(block_q, cap), min(block_k, cap), interpret, dsum=dsum,
        q_offset=q_offset, k_offset=k_offset)
    zero_int = np.zeros((), jax.dtypes.float0)
    return (jnp.moveaxis(dq, 0, 1).astype(q.dtype),
            jnp.moveaxis(dk, 0, 1).astype(k.dtype),
            jnp.moveaxis(dv, 0, 1).astype(v.dtype),
            zero_int, zero_int)


_flash_stats_vjp.defvjp(_flash_stats_fwd, _flash_stats_bwd)


def _flash_stats_forward(q, k, v, q_offset, k_offset, causal, scale,
                         block_q, block_k, interpret):
    qh = jnp.moveaxis(q, 1, 0)   # (H, S, D)
    kh = jnp.moveaxis(k, 1, 0)
    vh = jnp.moveaxis(v, 1, 0)
    h, _, d = qh.shape
    qh, kh, vh, s, sk, n_q, n_k = _pad_blocks(qh, kh, vh, block_q, block_k)
    # traced offsets: the diagonal is not static, no in-cell schedule
    _count_tiles(n_q, n_k, block_q, None)

    def kernel(qoff_ref, koff_ref, q_ref, k_ref, v_ref, o_ref, m_o, l_o,
               acc_ref, m_ref, l_ref):
        qoff = qoff_ref[0]
        koff = koff_ref[0]
        _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
                      n_k=n_k, block_q=block_q, block_k=block_k,
                      seq_end=koff + sk, causal=causal, scale=scale,
                      q_offset=qoff, k_offset=koff,
                      m_out_ref=m_o, l_out_ref=l_o, normalize=False)

    qoff_arr = jnp.asarray(q_offset, jnp.int32).reshape(1)
    koff_arr = jnp.asarray(k_offset, jnp.int32).reshape(1)
    acc, m, l = pl.pallas_call(
        kernel,
        grid=(h, n_q, n_k),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.MemorySpace.SMEM),
            pl.BlockSpec(memory_space=pltpu.MemorySpace.SMEM),
            pl.BlockSpec((1, block_q, d), lambda hh, qb, kb: (hh, qb, 0)),
            pl.BlockSpec((1, block_k, d), lambda hh, qb, kb: (hh, kb, 0)),
            pl.BlockSpec((1, block_k, d), lambda hh, qb, kb: (hh, kb, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda hh, qb, kb: (hh, qb, 0)),
            pl.BlockSpec((1, block_q, 1), lambda hh, qb, kb: (hh, qb, 0)),
            pl.BlockSpec((1, block_q, 1), lambda hh, qb, kb: (hh, qb, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((h, qh.shape[1], d), jnp.float32),
            jax.ShapeDtypeStruct((h, qh.shape[1], 1), jnp.float32),
            jax.ShapeDtypeStruct((h, qh.shape[1], 1), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32),
                        pltpu.VMEM((block_q, 1), jnp.float32),
                        pltpu.VMEM((block_q, 1), jnp.float32)],
        compiler_params=_compiler_params(),
        interpret=interpret,
        name=KERNEL_STATS_FWD,
    )(qoff_arr, koff_arr, qh, kh, vh)
    # ring-merge shapes: acc (S, H, D), m/l (H, S)
    return (jnp.moveaxis(acc[:, :s], 0, 1), m[:, :s, 0], l[:, :s, 0])


def _flash_forward_lse(q, k, v, causal, scale, block_q, block_k, interpret):
    """Forward that ALSO returns the per-row log-sum-exp (H, S, 1) — the
    only extra residual the flash backward needs (FlashAttention's trick:
    P = exp(S - LSE) reconstructs the softmax block-by-block)."""
    d = q.shape[-1]
    dv = v.shape[-1]      # the values' width may differ from q's and k's
    h = q.shape[0]
    q, k, v, s, sk, n_q, n_k = _pad_blocks(q, k, v, block_q, block_k)
    tile = _diag_tile(causal, block_q, block_k, s, sk)
    _count_tiles(n_q, n_k, block_q, tile)

    def kernel(q_ref, k_ref, v_ref, o_ref, m_o, l_o, acc_ref, m_ref, l_ref):
        _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref,
                      n_k=n_k, block_q=block_q, block_k=block_k,
                      seq_end=sk, causal=causal, scale=scale,
                      m_out_ref=m_o, l_out_ref=l_o, normalize=True,
                      diag_tile=tile)

    out, m, l = pl.pallas_call(
        kernel,
        grid=(h, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda hh, qb, kb: (hh, qb, 0)),
            pl.BlockSpec((1, block_k, d), lambda hh, qb, kb: (hh, kb, 0)),
            pl.BlockSpec((1, block_k, dv), lambda hh, qb, kb: (hh, kb, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, dv), lambda hh, qb, kb: (hh, qb, 0)),
            pl.BlockSpec((1, block_q, 1), lambda hh, qb, kb: (hh, qb, 0)),
            pl.BlockSpec((1, block_q, 1), lambda hh, qb, kb: (hh, qb, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((h, q.shape[1], dv), q.dtype),
            jax.ShapeDtypeStruct((h, q.shape[1], 1), jnp.float32),
            jax.ShapeDtypeStruct((h, q.shape[1], 1), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((block_q, dv), jnp.float32),
                        pltpu.VMEM((block_q, 1), jnp.float32),
                        pltpu.VMEM((block_q, 1), jnp.float32)],
        compiler_params=_compiler_params(),
        interpret=interpret,
        name=KERNEL_FWD,
    )(q, k, v)
    lse = m + jnp.log(jnp.maximum(l, 1e-30))
    return out[:, :s], lse[:, :s]


def _bwd_common(q_ref, k_ref, v_ref, do_ref, lse_ref, dsum_ref, qb, kb, *,
                block_q: int, block_k: int, causal: bool, scale: float,
                k_end, q_offset, k_offset, masked: bool, r0: int, nr: int,
                nc: int):
    """Recompute rows [r0, r0 + nr) against the first nc keys of the cell's
    probability block (the whole (Bq, Bk) block, or one strip of
    _attend_boundary on a diagonal cell) and its dS — shared by both
    backward kernels so their masking/scaling can never diverge. Matmuls
    run in the input dtype with f32 accumulation (bf16 operands use the
    MXU's bf16 rate); `masked=False` skips the iota/compare/where passes on
    interior blocks, which only boundary blocks need. q_offset/k_offset/
    k_end may be static ints or traced SMEM scalars (the ring stats
    backward has per-device global offsets, like the forward). Returns
    (p, ds, do, q, k) with q UNSCALED, the operands the callers contract
    p and ds with."""
    rows, cols = pl.ds(r0, nr), pl.ds(0, nc)
    q = q_ref[0, rows, :]
    k = k_ref[0, cols, :]
    v = v_ref[0, cols, :]
    do = do_ref[0, rows, :]
    exact = q_ref.dtype == jnp.float32
    s = _mxu_dot(q * jnp.asarray(scale, q_ref.dtype), k, (1, 1), exact)
    if masked:
        q_pos = q_offset + qb * block_q + r0 + jax.lax.broadcasted_iota(
            jnp.int32, (nr, 1), 0)
        k_pos = k_offset + kb * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (1, nc), 1)
        valid = k_pos < k_end
        if causal:
            valid = valid & (q_pos >= k_pos)
        s = jnp.where(valid, s, -1e30)
    # padded q rows carry lse=+inf (set by the caller) -> p exactly 0
    p = jnp.exp(s - lse_ref[0, rows, :])              # (nr, nc)
    dp = _mxu_dot(do, v, (1, 1), exact)
    ds = p * (dp - dsum_ref[0, rows, :])              # (nr, nc)
    return p, ds, do, q, k


def _flash_bwd_dq_kernel(qoff_ref, koff_ref, q_ref, k_ref, v_ref, do_ref,
                         lse_ref, dsum_ref, dq_ref, acc_ref, *, n_k: int,
                         block_q: int, block_k: int, causal: bool,
                         scale: float, k_end: int,
                         diag_tile: Optional[int] = None):
    qb, kb = pl.program_id(1), pl.program_id(2)
    qoff, koff = qoff_ref[0], koff_ref[0]

    @pl.when(kb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _accum(masked: bool, r0: int = 0, nr: int = block_q,
               nc: int = block_k):
        _, ds, _, _, k = _bwd_common(
            q_ref, k_ref, v_ref, do_ref, lse_ref, dsum_ref, qb, kb,
            block_q=block_q, block_k=block_k, causal=causal, scale=scale,
            k_end=koff + k_end, q_offset=qoff, k_offset=koff, masked=masked,
            r0=r0, nr=nr, nc=nc)
        acc_ref[pl.ds(r0, nr), :] += _mxu_dot(ds.astype(k.dtype), k, (1, 0),
                                              k.dtype == jnp.float32)

    full = _bwd_full_t(qb, kb, block_q, block_k, causal, k_end, qoff, koff)
    visible = _bwd_visible_t(qb, kb, block_q, block_k, causal, qoff, koff)

    @pl.when(full)
    def _accum_full():
        _accum(masked=False)

    @pl.when(visible & jnp.logical_not(full))
    def _accum_masked():
        _attend_boundary(_accum, block_q, diag_tile)

    @pl.when(kb == n_k - 1)
    def _finish():
        dq_ref[0] = (acc_ref[...] * scale).astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(qoff_ref, koff_ref, k_ref, v_ref, q_ref, do_ref,
                          lse_ref, dsum_ref, dk_ref, dv_ref, dk_acc,
                          dv_acc, *, n_q: int, block_q: int, block_k: int,
                          causal: bool, scale: float, k_end: int,
                          diag_tile: Optional[int] = None):
    kb, qb = pl.program_id(1), pl.program_id(2)
    qoff, koff = qoff_ref[0], koff_ref[0]

    @pl.when(qb == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def _accum(masked: bool, r0: int = 0, nr: int = block_q,
               nc: int = block_k):
        p, ds, do, q, _ = _bwd_common(
            q_ref, k_ref, v_ref, do_ref, lse_ref, dsum_ref, qb, kb,
            block_q=block_q, block_k=block_k, causal=causal, scale=scale,
            k_end=koff + k_end, q_offset=qoff, k_offset=koff, masked=masked,
            r0=r0, nr=nr, nc=nc)
        exact = q.dtype == jnp.float32
        cols = pl.ds(0, nc)
        dv_acc[cols, :] += _mxu_dot(p.astype(do.dtype), do, (0, 0), exact)
        dk_acc[cols, :] += _mxu_dot(ds.astype(q.dtype), q, (0, 0), exact)

    full = _bwd_full_t(qb, kb, block_q, block_k, causal, k_end, qoff, koff)
    visible = _bwd_visible_t(qb, kb, block_q, block_k, causal, qoff, koff)

    @pl.when(full)
    def _accum_full():
        _accum(masked=False)

    @pl.when(visible & jnp.logical_not(full))
    def _accum_masked():
        _attend_boundary(_accum, block_q, diag_tile)

    @pl.when(qb == n_q - 1)
    def _finish():
        dk_ref[0] = (dk_acc[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _bwd_visible_t(qb, kb, block_q: int, block_k: int, causal: bool,
                   q_offset=0, k_offset=0):
    """Traced block-visibility for the backward grids (same geometry as the
    forward's diagonal skip; offsets are the blocks' global positions on
    the ring stats path)."""
    if not causal:
        return qb >= 0   # always true, traced
    return (k_offset + kb * block_k
            <= q_offset + qb * block_q + block_q - 1)


def _bwd_full_t(qb, kb, block_q: int, block_k: int, causal: bool,
                k_end, q_offset=0, k_offset=0):
    """Traced no-mask-needed test for the backward grids (same geometry as
    the forward's `full`): every key < k_offset + k_end and, causal,
    wholly below the diagonal."""
    full = (kb + 1) * block_k <= k_end
    if causal:
        full = full & (k_offset + (kb + 1) * block_k - 1
                       <= q_offset + qb * block_q)
    return full


def _flash_backward(q, k, v, out, lse, g, causal, scale, block_q, block_k,
                    interpret, dsum=None, q_offset=0, k_offset=0):
    """(H, S, D) flash backward: dq via a (h, qb, kb) grid, dk/dv via a
    (h, kb, qb) grid — both recompute P block-wise from q/k and the saved
    LSE, so backward memory stays O(block) like the forward (the previous
    implementation re-ran dense XLA attention: O(S^2) HBM on backward,
    which forfeited the flash advantage exactly where training needs it).

    Two parameterizations share these kernels:
    - normalized attention: lse = log-sum-exp, dsum = rowsum(dO * O)
      (computed here when dsum is None);
    - ring STATS (flash_attention_stats' VJP): lse = the running max m,
      dsum = -dl, g = d_acc — algebraically the same ds = p*(dp - dsum)
      recurrence, see _flash_stats_bwd for the derivation. q_offset/
      k_offset are the blocks' global positions (traced scalars OK)."""
    d = q.shape[-1]
    dv = v.shape[-1]
    h = q.shape[0]
    s_q = q.shape[1]
    sk = k.shape[1]
    q_p, k_p, v_p, _, _, n_q, n_k = _pad_blocks(q, k, v, block_q, block_k)
    tile = _diag_tile(causal, block_q, block_k, s_q, sk, q_offset, k_offset)
    _count_tiles(n_q, n_k, block_q, tile, kernels=2)     # dq and dk/dv
    pad_q = q_p.shape[1] - s_q
    g_p = jnp.pad(g, ((0, 0), (0, pad_q), (0, 0))) if pad_q else g
    if dsum is None:
        out_p = (jnp.pad(out, ((0, 0), (0, pad_q), (0, 0)))
                 if pad_q else out)
        # D = rowsum(dO * O); padded rows get LSE=+inf so every p block is 0
        dsum = jnp.sum(g_p.astype(jnp.float32) * out_p.astype(jnp.float32),
                       axis=-1, keepdims=True)                # (H, Sq, 1)
    elif pad_q:
        dsum = jnp.pad(dsum, ((0, 0), (0, pad_q), (0, 0)))
    lse_p = jnp.pad(lse, ((0, 0), (0, pad_q), (0, 0)),
                    constant_values=jnp.inf) if pad_q else lse
    qoff_arr = jnp.asarray(q_offset, jnp.int32).reshape(1)
    koff_arr = jnp.asarray(k_offset, jnp.int32).reshape(1)
    smem = pl.BlockSpec(memory_space=pltpu.MemorySpace.SMEM)

    row_spec_q = pl.BlockSpec((1, block_q, d), lambda hh, qb, kb: (hh, qb, 0))
    col_spec_k = pl.BlockSpec((1, block_k, d), lambda hh, qb, kb: (hh, kb, 0))
    one_spec_q = pl.BlockSpec((1, block_q, 1), lambda hh, qb, kb: (hh, qb, 0))
    # v and the output's cotangent at the values' own width (dv == d: the
    # same specs as q's and k's)
    row_spec_do = row_spec_q if dv == d else pl.BlockSpec(
        (1, block_q, dv), lambda hh, qb, kb: (hh, qb, 0))
    col_spec_v = col_spec_k if dv == d else pl.BlockSpec(
        (1, block_k, dv), lambda hh, qb, kb: (hh, kb, 0))
    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, n_k=n_k, block_q=block_q,
                          block_k=block_k, causal=causal, scale=scale,
                          k_end=sk, diag_tile=tile),
        grid=(h, n_q, n_k),
        in_specs=[smem, smem, row_spec_q, col_spec_k, col_spec_v,
                  row_spec_do, one_spec_q, one_spec_q],
        out_specs=row_spec_q,
        out_shape=jax.ShapeDtypeStruct(q_p.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=_compiler_params(),
        interpret=interpret,
        name=KERNEL_DQ,
    )(qoff_arr, koff_arr, q_p, k_p, v_p, g_p, lse_p, dsum)[:, :s_q]

    # dk/dv grid: k-blocks outer, q-blocks inner (accumulated)
    row_spec_kb = pl.BlockSpec((1, block_k, d), lambda hh, kb, qb: (hh, kb, 0))
    col_spec_qb = pl.BlockSpec((1, block_q, d), lambda hh, kb, qb: (hh, qb, 0))
    one_spec_qb = pl.BlockSpec((1, block_q, 1), lambda hh, kb, qb: (hh, qb, 0))
    row_spec_vb = row_spec_kb if dv == d else pl.BlockSpec(
        (1, block_k, dv), lambda hh, kb, qb: (hh, kb, 0))
    col_spec_dob = col_spec_qb if dv == d else pl.BlockSpec(
        (1, block_q, dv), lambda hh, kb, qb: (hh, qb, 0))
    dkv_kernel = functools.partial(
        _flash_bwd_dkv_kernel, n_q=n_q, block_q=block_q, block_k=block_k,
        causal=causal, scale=scale, k_end=sk, diag_tile=tile)
    dk, dv = pl.pallas_call(
        dkv_kernel,
        grid=(h, n_k, n_q),
        in_specs=[smem, smem, row_spec_kb, row_spec_vb, col_spec_qb,
                  col_spec_dob, one_spec_qb, one_spec_qb],
        out_specs=[row_spec_kb, row_spec_vb],
        out_shape=[jax.ShapeDtypeStruct(k_p.shape, k.dtype),
                   jax.ShapeDtypeStruct(v_p.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, dv), jnp.float32)],
        compiler_params=_compiler_params(),
        interpret=interpret,
        name=KERNEL_DKV,
    )(qoff_arr, koff_arr, k_p, v_p, q_p, g_p, lse_p, dsum)
    return dq, dk[:, :sk], dv[:, :sk]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _flash_shd(q, k, v, causal, scale, block_q, block_k, bwd_block_q,
               bwd_block_k, interpret):
    return _flash_forward(q, k, v, causal, scale, block_q, block_k,
                          interpret)


def _flash_fwd_vjp(q, k, v, causal, scale, block_q, block_k, bwd_block_q,
                   bwd_block_k, interpret):
    out, lse = _flash_forward_lse(q, k, v, causal, scale, block_q, block_k,
                                  interpret)
    # named HERE, inside the fwd rule: a checkpoint policy sees these
    # equations, and a name on the call's output would keep the output and
    # still run the kernel again for the residuals
    out, lse = checkpoint_name((out, lse), tnames.KEEP_FLASH)
    return out, (q, k, v, out, lse)   # lse: (H, S, 1)


def _flash_bwd_vjp(causal, scale, block_q, block_k, bwd_block_q,
                   bwd_block_k, interpret, res, g):
    q, k, v, out, lse = res
    return _flash_backward(q, k, v, out, lse, g, causal, scale, bwd_block_q,
                           bwd_block_k, interpret)


_flash_shd.defvjp(_flash_fwd_vjp, _flash_bwd_vjp)


# ------------------------------------------------- the sliding-window kernels
# A causal self-attention call under a static window W (key s is visible to
# query t iff t - W < s <= t) runs kernels of its own, so that a call
# without a window compiles to what it always did. Their grids hold only the
# cells a window can reach: a query block qb reads the `n_vis` key blocks
# qb - n_vis + 1 .. qb (the dk/dv grid: key block kb is read by query blocks
# kb .. kb + n_vis - 1), the index maps clamp at the sequence's ends and the
# kernel skips what lies outside. Square blocks; the distance `delta` = qb -
# kb of a grid step is one of n_vis static values, so every cell's geometry
# is static: it is walked in row strips of `_WIN_STRIP`, each strip over the
# 128-aligned columns its rows can see, masked only where an edge (the
# diagonal above, the window below) crosses it. A row with no visible key in
# a cell left of the diagonal carries garbage in its running sums until the
# diagonal cell, the last of its row, wipes it (alpha = exp(-1e30 - m) = 0):
# every row sees its own position. Padded keys lie above every real row's
# diagonal, padded rows are dropped (forward) or carry lse = +inf (backward).
KERNEL_FWD_WIN = "flash_fwd_win"
KERNEL_DQ_WIN = "flash_dq_win"
KERNEL_DKV_WIN = "flash_dkv_win"
_WIN_BLOCK = 512
_WIN_STRIP = 256


def _window_cells(block: int, window: int) -> int:
    """Key blocks a query block can see under the window, itself included."""
    return (window + block - 2) // block + 1


def _window_strips(block: int, window: int, delta: int) -> list:
    """The walk of a cell whose query block lies `delta` blocks after its
    key block: (r0, rows, c0, cols, masked) a strip, local coordinates."""
    t = min(_WIN_STRIP, block)
    shift = delta * block
    out = []
    for r0 in range(0, block, t):
        lo = max(0, r0 + shift - window + 1)       # first column row r0 sees
        hi = min(block, r0 + t + shift)            # past the last row's last
        if lo >= hi:
            continue
        lo, hi = lo // 128 * 128, min(block, -(-hi // 128) * 128)
        masked = (hi - 1 > r0 + shift                      # the diagonal
                  or lo <= r0 + t - 1 + shift - window)    # the window's edge
        out.append((r0, t, lo, hi - lo, masked))
    return out


def _count_window_tiles(n_q: int, block: int, window: int,
                        kernels: int) -> None:
    """`_count_tiles` for a windowed call, in sub-tiles of the strip's size:
    what the strips cover, and what of the causal cells they leave out."""
    t = min(_WIN_STRIP, block)
    computed = 0
    for delta in range(_window_cells(block, window)):
        cell = sum(nr * nc for _, nr, _, nc, _ in
                   _window_strips(block, window, delta))
        computed += cell * max(n_q - delta, 0)
    computed = -(-computed // (t * t))
    causal = n_q * (n_q + 1) // 2 * (block // t) ** 2
    reliability_metrics.inc(tnames.FLASH_TILES_COMPUTED, kernels * computed)
    reliability_metrics.inc(tnames.FLASH_TILES_SKIPPED,
                            kernels * max(causal - computed, 0))


def _window_mask(s, r0: int, nr: int, c0: int, nc: int, shift: int,
                 window: int):
    """-1e30 where key c0 + c is not in query r0 + r's window, the key block
    `shift` positions before the query block."""
    rel = (c0 - r0 - shift
           + jax.lax.broadcasted_iota(jnp.int32, (1, nc), 1)
           - jax.lax.broadcasted_iota(jnp.int32, (nr, 1), 0))
    return jnp.where((rel <= 0) & (rel > -window), s, -1e30)


def _win_fwd_kernel(q_ref, k_ref, v_ref, o_ref, m_o, l_o, acc_ref, m_ref,
                    l_ref, *, block: int, window: int, scale: float):
    qb, j = pl.program_id(1), pl.program_id(2)
    n_vis = _window_cells(block, window)
    exact = q_ref.dtype == jnp.float32

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)

    def attend(delta, r0, nr, c0, nc, masked):
        rows, cols = pl.ds(r0, nr), pl.ds(c0, nc)
        q = q_ref[0, rows, :] * jnp.asarray(scale, q_ref.dtype)
        v = v_ref[0, cols, :]
        s = _mxu_dot(q, k_ref[0, cols, :], (1, 1), exact)
        if masked:
            s = _window_mask(s, r0, nr, c0, nc, delta * block, window)
        m_prev, l_prev = m_ref[rows, :], l_ref[rows, :]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[rows, :] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[rows, :] = (acc_ref[rows, :] * alpha
                            + _mxu_dot(p.astype(v.dtype), v, (1, 0), exact))
        m_ref[rows, :] = m_new

    for delta in range(n_vis):
        @pl.when((j == n_vis - 1 - delta) & (qb >= delta))
        def _cell(delta=delta):
            for strip in _window_strips(block, window, delta):
                attend(delta, *strip)

    @pl.when(j == n_vis - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                    ).astype(o_ref.dtype)
        m_o[0] = m_ref[...]
        l_o[0] = l_ref[...]


def _win_bwd_strip(q_ref, k_ref, v_ref, do_ref, lse_ref, dsum_ref, delta,
                   r0, nr, c0, nc, masked, *, block, window, scale):
    """`_bwd_common` for one strip of a windowed cell -> (p, ds, do, q, k)."""
    rows, cols = pl.ds(r0, nr), pl.ds(c0, nc)
    q, k, do = q_ref[0, rows, :], k_ref[0, cols, :], do_ref[0, rows, :]
    exact = q_ref.dtype == jnp.float32
    s = _mxu_dot(q * jnp.asarray(scale, q_ref.dtype), k, (1, 1), exact)
    if masked:
        s = _window_mask(s, r0, nr, c0, nc, delta * block, window)
    p = jnp.exp(s - lse_ref[0, rows, :])
    dp = _mxu_dot(do, v_ref[0, cols, :], (1, 1), exact)
    return p, p * (dp - dsum_ref[0, rows, :]), do, q, k


def _win_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dsum_ref, dq_ref,
                   acc_ref, *, block: int, window: int, scale: float):
    qb, j = pl.program_id(1), pl.program_id(2)
    n_vis = _window_cells(block, window)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    for delta in range(n_vis):
        @pl.when((j == n_vis - 1 - delta) & (qb >= delta))
        def _cell(delta=delta):
            for strip in _window_strips(block, window, delta):
                _, ds, _, _, k = _win_bwd_strip(
                    q_ref, k_ref, v_ref, do_ref, lse_ref, dsum_ref, delta,
                    *strip, block=block, window=window, scale=scale)
                acc_ref[pl.ds(strip[0], strip[1]), :] += _mxu_dot(
                    ds.astype(k.dtype), k, (1, 0), k.dtype == jnp.float32)

    @pl.when(j == n_vis - 1)
    def _finish():
        dq_ref[0] = (acc_ref[...] * scale).astype(dq_ref.dtype)


def _win_dkv_kernel(k_ref, v_ref, q_ref, do_ref, lse_ref, dsum_ref, dk_ref,
                    dv_ref, dk_acc, dv_acc, *, n_q: int, block: int,
                    window: int, scale: float):
    kb, j = pl.program_id(1), pl.program_id(2)
    n_vis = _window_cells(block, window)

    @pl.when(j == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    for delta in range(n_vis):
        @pl.when((j == delta) & (kb + delta < n_q))
        def _cell(delta=delta):
            for strip in _window_strips(block, window, delta):
                p, ds, do, q, _ = _win_bwd_strip(
                    q_ref, k_ref, v_ref, do_ref, lse_ref, dsum_ref, delta,
                    *strip, block=block, window=window, scale=scale)
                exact = q.dtype == jnp.float32
                cols = pl.ds(strip[2], strip[3])
                dv_acc[cols, :] += _mxu_dot(p.astype(do.dtype), do, (0, 0),
                                            exact)
                dk_acc[cols, :] += _mxu_dot(ds.astype(q.dtype), q, (0, 0),
                                            exact)

    @pl.when(j == n_vis - 1)
    def _finish():
        dk_ref[0] = (dk_acc[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _win_forward_lse(q, k, v, window, scale, block, interpret):
    """(H, S, D) q, k and (H, S, Dv) v -> (out (H, S, Dv), lse (H, S, 1))."""
    h, _, d = q.shape
    dv = v.shape[-1]
    q, k, v, s, _, n_q, _ = _pad_blocks(q, k, v, block, block)
    n_vis = _window_cells(block, window)
    _count_window_tiles(n_q, block, window, 1)

    def q_map(hh, qb, j):
        return hh, qb, 0

    def k_map(hh, qb, j):
        return hh, jnp.maximum(qb - (n_vis - 1) + j, 0), 0

    one = pl.BlockSpec((1, block, 1), q_map)
    out, m, l = pl.pallas_call(
        functools.partial(_win_fwd_kernel, block=block, window=window,
                          scale=scale),
        grid=(h, n_q, n_vis),
        in_specs=[pl.BlockSpec((1, block, d), q_map),
                  pl.BlockSpec((1, block, d), k_map),
                  pl.BlockSpec((1, block, dv), k_map)],
        out_specs=[pl.BlockSpec((1, block, dv), q_map), one, one],
        out_shape=[jax.ShapeDtypeStruct((h, q.shape[1], dv), q.dtype),
                   jax.ShapeDtypeStruct((h, q.shape[1], 1), jnp.float32),
                   jax.ShapeDtypeStruct((h, q.shape[1], 1), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((block, dv), jnp.float32),
                        pltpu.VMEM((block, 1), jnp.float32),
                        pltpu.VMEM((block, 1), jnp.float32)],
        compiler_params=_compiler_params(), interpret=interpret,
        name=KERNEL_FWD_WIN,
    )(q, k, v)
    lse = m + jnp.log(jnp.maximum(l, 1e-30))
    return out[:, :s], lse[:, :s]


def _win_backward(q, k, v, out, lse, g, window, scale, block, interpret):
    h, s, d = q.shape
    dv = v.shape[-1]
    q_p, k_p, v_p, _, _, n_q, _ = _pad_blocks(q, k, v, block, block)
    n_vis = _window_cells(block, window)
    _count_window_tiles(n_q, block, window, 2)
    pad = q_p.shape[1] - s
    rows = ((0, 0), (0, pad), (0, 0))
    dsum = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                   axis=-1, keepdims=True)
    g_p, dsum = (jnp.pad(g, rows), jnp.pad(dsum, rows)) if pad else (g, dsum)
    lse_p = jnp.pad(lse, rows, constant_values=jnp.inf) if pad else lse

    def q_map(hh, qb, j):
        return hh, qb, 0

    def k_of_q(hh, qb, j):
        return hh, jnp.maximum(qb - (n_vis - 1) + j, 0), 0

    dq = pl.pallas_call(
        functools.partial(_win_dq_kernel, block=block, window=window,
                          scale=scale),
        grid=(h, n_q, n_vis),
        in_specs=[pl.BlockSpec((1, block, d), q_map),
                  pl.BlockSpec((1, block, d), k_of_q),
                  pl.BlockSpec((1, block, dv), k_of_q),
                  pl.BlockSpec((1, block, dv), q_map),
                  pl.BlockSpec((1, block, 1), q_map),
                  pl.BlockSpec((1, block, 1), q_map)],
        out_specs=pl.BlockSpec((1, block, d), q_map),
        out_shape=jax.ShapeDtypeStruct(q_p.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((block, d), jnp.float32)],
        compiler_params=_compiler_params(), interpret=interpret,
        name=KERNEL_DQ_WIN,
    )(q_p, k_p, v_p, g_p, lse_p, dsum)[:, :s]

    def k_map(hh, kb, j):
        return hh, kb, 0

    def q_of_k(hh, kb, j):
        return hh, jnp.minimum(kb + j, n_q - 1), 0

    dk, dv_out = pl.pallas_call(
        functools.partial(_win_dkv_kernel, n_q=n_q, block=block,
                          window=window, scale=scale),
        grid=(h, n_q, n_vis),
        in_specs=[pl.BlockSpec((1, block, d), k_map),
                  pl.BlockSpec((1, block, dv), k_map),
                  pl.BlockSpec((1, block, d), q_of_k),
                  pl.BlockSpec((1, block, dv), q_of_k),
                  pl.BlockSpec((1, block, 1), q_of_k),
                  pl.BlockSpec((1, block, 1), q_of_k)],
        out_specs=[pl.BlockSpec((1, block, d), k_map),
                   pl.BlockSpec((1, block, dv), k_map)],
        out_shape=[jax.ShapeDtypeStruct(k_p.shape, k.dtype),
                   jax.ShapeDtypeStruct(v_p.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((block, d), jnp.float32),
                        pltpu.VMEM((block, dv), jnp.float32)],
        compiler_params=_compiler_params(), interpret=interpret,
        name=KERNEL_DKV_WIN,
    )(k_p, v_p, q_p, g_p, lse_p, dsum)
    return dq, dk[:, :s], dv_out[:, :s]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_win(q, k, v, window, scale, block, interpret):
    return _win_forward_lse(q, k, v, window, scale, block, interpret)[0]


def _flash_win_fwd(q, k, v, window, scale, block, interpret):
    out, lse = checkpoint_name(
        _win_forward_lse(q, k, v, window, scale, block, interpret),
        tnames.KEEP_FLASH)
    return out, (q, k, v, out, lse)


def _flash_win_bwd(window, scale, block, interpret, res, g):
    q, k, v, out, lse = res
    return _win_backward(q, k, v, out, lse, g, window, scale, block,
                         interpret)


_flash_win.defvjp(_flash_win_fwd, _flash_win_bwd)


def flash_attention(q, k, v, causal: bool = False,
                    scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: Optional[bool] = None,
                    window: Optional[int] = None):
    """Exact attention without the (S, S) HBM score matrix.

    q: (S, H, D); k: (Sk, H, D); v: (Sk, H, Dv), Dv = D unless the values
    are wider or narrower than the keys. Returns (S, H, Dv), same dtype
    as q. `window` (static; causal self-attention only): key s is visible
    to query t iff t - window < s <= t; such a call runs the kernels
    `flash_fwd_win` / `flash_dq_win` / `flash_dkv_win` over the cells the
    window can reach, in square blocks of `block_q` (default 512). None,
    or a window no shorter than the sequence, is the call without one.
    block_q/block_k default to a measured-on-v5e auto choice (the largest
    of 1024 / 512 / 256 that pads the sequence by under 20%; the BACKWARD
    internally caps at 512 for f32 operands, which exceed VMEM at 1024).
    A causal self-attention call computes each diagonal cell only up to
    the diagonal (module docstring). `interpret` defaults to True off-TPU
    so tests run anywhere.
    """
    if scale is None:
        scale = 1.0 / float(np.sqrt(q.shape[-1]))
    if interpret is None:
        interpret = jax.devices()[0].platform != "tpu"
    q = jnp.asarray(q)
    if window is not None and window < q.shape[0]:
        if not causal or k.shape[0] != q.shape[0] or window < 1 \
                or block_q != block_k:
            raise ValueError(
                f"a window ({window}) bounds causal self-attention in "
                f"square blocks, not causal={causal}, {q.shape[0]} queries "
                f"over {k.shape[0]} keys, blocks {block_q} x {block_k}")
        block = int(block_q) if block_q is not None \
            else min(_pick_block(q.shape[0]), _WIN_BLOCK)
        out = _flash_win(
            jnp.moveaxis(q, 1, 0), jnp.moveaxis(jnp.asarray(k), 1, 0),
            jnp.moveaxis(jnp.asarray(v), 1, 0), int(window), float(scale),
            block, bool(interpret))
        return jnp.moveaxis(out, 0, 1)
    a_bq, a_bk, a_bwd_bq, a_bwd_bk = _auto_blocks(
        q.shape[0], k.shape[0], q.dtype, q.shape[-1])
    bq = int(block_q) if block_q is not None else a_bq
    bk = int(block_k) if block_k is not None else a_bk
    # explicit blocks pin the backward too (sweep scripts rely on that) —
    # but capped by the dtype VMEM ceiling: an f32 caller passing
    # block_q=1024 would otherwise hit the documented f32-backward VMEM
    # compile failure only at grad time (round-4 advisor)
    bwd_cap = _bwd_cap(q.dtype, q.shape[-1])
    bwd_bq = min(int(block_q), bwd_cap) if block_q is not None else a_bwd_bq
    bwd_bk = min(int(block_k), bwd_cap) if block_k is not None else a_bwd_bk
    qh = jnp.moveaxis(q, 1, 0)                # (H, S, D)
    kh = jnp.moveaxis(jnp.asarray(k), 1, 0)
    vh = jnp.moveaxis(jnp.asarray(v), 1, 0)
    out = _flash_shd(qh, kh, vh, bool(causal), float(scale), bq, bk,
                     bwd_bq, bwd_bk, bool(interpret))
    return jnp.moveaxis(out, 0, 1)
