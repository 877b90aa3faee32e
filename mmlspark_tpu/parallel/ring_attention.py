"""Sequence/context parallelism: ring attention + Ulysses all-to-all.

SURVEY.md §5 flags long-context/sequence parallelism as ABSENT in the
reference ("the TPU build's CP/SP story must be designed fresh — ring
collectives over ICI via shard_map + ppermute, not ported"). This module is
that design:

- `ring_attention`: blockwise attention over a sequence-sharded mesh axis.
  Each device holds one sequence block of Q/K/V; K/V blocks rotate around
  the ring with `lax.ppermute` while a flash-style streaming softmax
  (running max + denominator) accumulates exact attention — memory per
  device stays O(block^2) and the K/V transfer rides ICI neighbor links,
  never DCN. Causal masking uses the rotating block's global offset.
- `ulysses_attention`: the all-to-all alternative (DeepSpeed-Ulysses
  layout): `all_to_all` re-shards sequence -> heads, every device runs
  dense attention for its head subset over the FULL sequence, and a second
  `all_to_all` restores sequence sharding. Better when heads >= devices and
  block attention would underutilize the MXU.

Both are exact (not approximations) and verified against single-device
softmax attention on the virtual mesh in tests/test_ring_attention.py.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import PartitionSpec as P

from . import DATA_AXIS


def _block_attend(q, k, v, mask):
    """Scores for one (q-block, kv-block) pair + streaming-softmax stats.
    q (B, H, D), k/v (Bk, H, D), mask (B, Bk) additive. Softmax math and
    outputs are f32 regardless of input dtype (bf16 inputs keep MXU speed;
    an 8-bit-mantissa denominator would drift over long sequences)."""
    s = jnp.einsum("qhd,khd->hqk", q, k,
                   preferred_element_type=jnp.float32)      # (H, B, Bk)
    s = s + mask.astype(jnp.float32)[None, :, :]
    # finite floor: a fully-masked block row has max -inf, and
    # exp(-inf - -inf) would be NaN — clamp so its probs are exactly 0
    m = jnp.maximum(jnp.max(s, axis=-1), -1e30)             # (H, B)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)                                 # (H, B)
    o = jnp.einsum("hqk,khd->qhd", p.astype(v.dtype), v)    # (B, H, D)
    return o.astype(jnp.float32), m, l


def _ring_attention_sharded(q, k, v, axis_name: str, causal: bool,
                            scale: float, block_impl: str = "dense"):
    """Runs INSIDE shard_map: q/k/v are the local (block, H, D) shards."""
    n_dev = jax.lax.psum(1, axis_name)   # static: axis size is known at trace
    if n_dev == 1:
        # singleton axis (e.g. the 4D trainer on a 1-wide seq axis): the
        # ring degenerates to ordinary attention — route to the fused
        # normalized path instead of paying the stats kernel's separate
        # f32 accumulator, merge pass, and stats backward. Exact: one
        # block, zero offsets. Measured on v5e at the 201M/16k 4D bench:
        # this plus large-shard auto blocks below recovers most of the
        # 2.4x singleton-mesh overhead the round-4 verdict flagged.
        if block_impl == "flash":
            from ..ops.flash_attention import flash_attention
            return flash_attention(q, k, v, causal=causal, scale=scale)
        return reference_attention(q, k, v, causal=causal, scale=scale)
    my_idx = jax.lax.axis_index(axis_name)
    block = q.shape[0]
    h = q.shape[1]
    flash = block_impl == "flash"
    if not flash:
        q = q * scale  # flash scales inside its kernel

    def step(carry, i):
        k_blk, v_blk, acc, m_run, l_run = carry
        # global index of the K/V block currently held: it started at
        # (my_idx + i) ... ppermute below shifts blocks DOWN the ring, so at
        # step i we hold the block originally owned by (my_idx + i) % n_dev
        src = (my_idx + i) % n_dev
        if flash:
            # Pallas streaming kernel WITHIN the device: never materializes
            # the (block, block) score matrix; offsets carry the global
            # causal geometry across the ring. Small shards shrink the
            # kernel blocks to the shard size (8-row tile granularity) so
            # they don't pad up to 256 and waste MXU work; LARGE shards
            # take the measured auto choice (1024-wide for long blocks —
            # pinning 256 here cost ~3x on 16k shards, see the block-sweep
            # notes in ops/flash_attention.py).
            from ..ops.flash_attention import flash_attention_stats
            bq = -(-block // 8) * 8 if block < 256 else None
            o, m_blk, l_blk = flash_attention_stats(
                q, k_blk, v_blk, my_idx * block, src * block, causal, scale,
                block_q=bq, block_k=bq)
        else:
            if causal:
                q_pos = my_idx * block + jnp.arange(block)
                k_pos = src * block + jnp.arange(block)
                mask = jnp.where(q_pos[:, None] >= k_pos[None, :], 0.0,
                                 -jnp.inf)
            else:
                mask = jnp.zeros((block, block), q.dtype)
            o, m_blk, l_blk = _block_attend(q, k_blk, v_blk, mask)
        # streaming softmax merge (flash-attention accumulator)
        m_new = jnp.maximum(m_run, m_blk)
        alpha = jnp.exp(m_run - m_new)                      # rescale old
        beta = jnp.exp(m_blk - m_new)                       # rescale new
        l_new = l_run * alpha + l_blk * beta
        acc = acc * alpha.T[:, :, None] + o * beta.T[:, :, None]
        # rotate K/V to the next device (ICI neighbor exchange)
        perm = [(j, (j - 1) % n_dev) for j in range(n_dev)]
        k_nxt = jax.lax.ppermute(k_blk, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_blk, axis_name, perm)
        return (k_nxt, v_nxt, acc, m_new, l_new), None

    # f32 accumulators regardless of input dtype: both block impls return
    # f32 stats, and an 8-bit-mantissa streaming carry would drift
    acc0 = jnp.zeros(q.shape, jnp.float32)
    m0 = jnp.full((h, block), -1e30, jnp.float32)  # finite: _block_attend
    l0 = jnp.zeros((h, block), jnp.float32)
    (k, v, acc, m_run, l_run), _ = jax.lax.scan(
        step, (k, v, acc0, m0, l0), jnp.arange(n_dev))
    out = acc / jnp.maximum(l_run, 1e-30).T[:, :, None]
    return out.astype(q.dtype)


def ring_attention(q, k, v, mesh=None, axis: str = DATA_AXIS,
                   causal: bool = False, scale: Optional[float] = None,
                   block_impl: str = "dense"):
    """Exact attention over a sequence sharded across `mesh`'s `axis`.

    q/k/v: (seq, heads, dim) with seq divisible by the axis size. Returns
    (seq, heads, dim) with the same sharding. block_impl="flash" runs the
    Pallas streaming kernel inside each device (no per-device (block, block)
    score matrix) — flash WITHIN a chip, ring ACROSS chips.
    """
    from . import data_mesh
    mesh = mesh or data_mesh()
    if scale is None:
        scale = 1.0 / float(np.sqrt(q.shape[-1]))
    fn = functools.partial(_ring_attention_sharded, axis_name=axis,
                           causal=causal, scale=scale,
                           block_impl=block_impl)
    mapped = shard_map(fn, mesh=mesh,
                       in_specs=(P(axis), P(axis), P(axis)),
                       out_specs=P(axis), check_vma=False)
    return jax.jit(mapped)(q, k, v)


def _ulysses_sharded(q, k, v, axis_name: str, causal: bool, scale: float,
                     n_dev: int):
    """Runs INSIDE shard_map: sequence-sharded in, sequence-sharded out.
    all_to_all trades the sequence shard for a heads shard, so each device
    attends over the FULL sequence for heads/n_dev heads."""
    # (block, H, D) -> (block, n_dev, H/n_dev, D) -> all_to_all over axis 1
    block, h, d = q.shape

    def to_heads(x):
        x = x.reshape(block, n_dev, h // n_dev, d)
        # concat_dimension gathers the seq blocks: (seq, H/n_dev, D)
        return jax.lax.all_to_all(x, axis_name, split_axis=1,
                                  concat_axis=0, tiled=True).reshape(
            block * n_dev, h // n_dev, d)

    qh, kh, vh = to_heads(q), to_heads(k), to_heads(v)
    seq = qh.shape[0]
    if causal:
        pos = jnp.arange(seq)
        mask = jnp.where(pos[:, None] >= pos[None, :], 0.0, -jnp.inf)
    else:
        mask = jnp.zeros((seq, seq), q.dtype)
    o, _, l = _block_attend(qh * scale, kh, vh, mask)
    o = (o / jnp.maximum(l, 1e-30).T[:, :, None]).astype(q.dtype)
    # back: heads shard -> sequence shard. Splitting axis 0 sends block j to
    # device j; concatenating along the HEAD axis (2) reassembles the full
    # head dim in source (= global head group) order.
    o = o.reshape(n_dev, block, h // n_dev, d)
    o = jax.lax.all_to_all(o, axis_name, split_axis=0, concat_axis=2,
                           tiled=True)
    return o.reshape(block, h, d)


def ulysses_attention(q, k, v, mesh=None, axis: str = DATA_AXIS,
                      causal: bool = False, scale: Optional[float] = None):
    """All-to-all sequence parallelism (Ulysses layout); requires
    heads % axis_size == 0. Same contract as ring_attention."""
    from . import data_mesh
    mesh = mesh or data_mesh()
    n_dev = mesh.shape[axis]
    if q.shape[1] % n_dev:
        raise ValueError(
            f"ulysses_attention needs heads ({q.shape[1]}) divisible by the "
            f"mesh axis size ({n_dev}); use ring_attention otherwise")
    if scale is None:
        scale = 1.0 / float(np.sqrt(q.shape[-1]))
    fn = functools.partial(_ulysses_sharded, axis_name=axis, causal=causal,
                           scale=scale, n_dev=n_dev)
    mapped = shard_map(fn, mesh=mesh,
                       in_specs=(P(axis), P(axis), P(axis)),
                       out_specs=P(axis), check_vma=False)
    return jax.jit(mapped)(q, k, v)


def reference_attention(q, k, v, causal: bool = False,
                        scale: Optional[float] = None, key_mask=None):
    """Single-device attention (tests' oracle and the dense path).
    key_mask: optional (seq,) bool — False keys (e.g. padding) are excluded
    from every query's softmax."""
    if scale is None:
        scale = 1.0 / float(np.sqrt(q.shape[-1]))
    # scores/softmax in f32 even for bf16 inputs (matmuls still run at the
    # input dtype's MXU rate via preferred_element_type); output cast back
    s = jnp.einsum("qhd,khd->hqk", q * scale, k,
                   preferred_element_type=jnp.float32)
    if causal:
        n = q.shape[0]
        mask = jnp.where(jnp.arange(n)[:, None] >= jnp.arange(n)[None, :],
                         0.0, -jnp.inf)
        s = s + mask[None]
    if key_mask is not None:
        s = s + jnp.where(key_mask, 0.0, -jnp.inf)[None, None, :]
    p = jax.nn.softmax(s, axis=-1)
    # fully-masked rows (empty doc) softmax to NaN -> output 0
    return jnp.einsum("hqk,khd->qhd", jnp.nan_to_num(p).astype(v.dtype),
                      v).astype(q.dtype)
