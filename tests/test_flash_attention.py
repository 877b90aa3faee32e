"""Pallas flash attention vs dense softmax attention (exactness) and
gradient path. Runs in interpret mode on the CPU mesh."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from mmlspark_tpu.ops.flash_attention import flash_attention
from mmlspark_tpu.parallel.ring_attention import reference_attention


def _rand(s, h, d, seed=0):
    rng = np.random.default_rng(seed)
    return (rng.normal(size=(s, h, d)).astype(np.float32),
            rng.normal(size=(s, h, d)).astype(np.float32),
            rng.normal(size=(s, h, d)).astype(np.float32))


@pytest.mark.parametrize("causal", [False, True])
def test_matches_reference(causal):
    q, k, v = _rand(384, 4, 64)   # not a block multiple: exercises padding
    out = flash_attention(q, k, v, causal=causal, block_q=128, block_k=128)
    ref = reference_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                              causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_cross_attention_shapes():
    q, _, _ = _rand(96, 2, 32, seed=1)
    _, k, v = _rand(320, 2, 32, seed=2)
    out = flash_attention(q, k, v, block_q=128, block_k=128)
    ref = reference_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
    assert out.shape == (96, 2, 32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_gradients_flow():
    q, k, v = _rand(128, 2, 32)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True,
                               block_q=64, block_k=64).sum()

    def ref_loss(q, k, v):
        return reference_attention(jnp.asarray(q), jnp.asarray(k),
                                   jnp.asarray(v), causal=True).sum()

    g = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_fully_masked_rows_are_zero():
    # cross attention where causal masks out EVERYTHING for early rows is
    # impossible (row i always sees key i), so test via seq padding: keys
    # shorter than a block; padded keys must contribute nothing
    q, k, v = _rand(64, 1, 32, seed=3)
    out = flash_attention(q, k[:40], v[:40], block_q=64, block_k=64)
    ref = reference_attention(jnp.asarray(q), jnp.asarray(k[:40]),
                              jnp.asarray(v[:40]))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_stats_no_visible_key_contract():
    """flash_attention_stats' documented contract: a q row with NO visible
    key in the block (causal, q before k) is FLAGGED by m == -1e30 and its
    acc/l must be folded with zero weight, never normalized directly. This
    pins the contract so the kernel's unmasked-p fast path stays safe."""
    import numpy as np
    import jax.numpy as jnp
    from mmlspark_tpu.ops.flash_attention import flash_attention_stats

    rng = np.random.default_rng(0)
    h, s, d = 2, 128, 64
    q = jnp.asarray(rng.normal(size=(s, h, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(s, h, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(s, h, d)), jnp.float32)
    # causal with the whole k block AFTER the whole q block: no q row sees
    # any key
    acc, m, l = flash_attention_stats(q, k, v, q_offset=0, k_offset=s,
                                      causal=True, scale=1.0)
    assert np.all(np.asarray(m) <= -1e29), "empty rows must stay flagged"
    # the ring-merge fold: weight exp(m - m_new) with any finite m_new
    # zeroes these rows' contribution exactly
    w = np.exp(np.asarray(m) - 0.0)
    assert np.all(w == 0.0)
    # and a block where the LAST rows see keys but the first do not:
    # flagged rows and real rows coexist, flags are per-row
    acc2, m2, l2 = flash_attention_stats(q, k, v, q_offset=0,
                                         k_offset=s // 2, causal=True,
                                         scale=1.0)
    m2 = np.asarray(m2)  # (h, s)
    assert np.all(m2[:, : s // 2] <= -1e29)     # rows before the k block
    assert np.all(np.isfinite(m2[:, s // 2:]) & (m2[:, s // 2:] > -1e29))


def test_flash_backward_matches_dense_gradients():
    """The Pallas flash backward (dq/dk/dv kernels reconstructing P from
    the saved LSE) must match dense-attention gradients. Interpret mode
    keeps this exact (1e-6); on real TPU the difference is the bf16 MXU
    precision band shared by every matmul."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from mmlspark_tpu.ops.flash_attention import flash_attention
    from mmlspark_tpu.parallel.ring_attention import reference_attention

    rng = np.random.default_rng(0)
    for (s, sk, h, d, causal) in [(300, 300, 2, 64, True),
                                  (200, 333, 2, 64, False),
                                  (256, 256, 1, 32, True)]:
        q = jnp.asarray(rng.normal(size=(s, h, d)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(sk, h, d)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(sk, h, d)), jnp.float32)
        w = jnp.asarray(rng.normal(size=(s, h, d)), jnp.float32)
        gf = jax.grad(lambda q, k, v: (flash_attention(
            q, k, v, causal=causal) * w).sum(), argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(lambda q, k, v: (reference_attention(
            q, k, v, causal=causal) * w).sum(), argnums=(0, 1, 2))(q, k, v)
        for name, a, b in zip("qkv", gf, gr):
            rel = float(jnp.abs(a - b).max()) / (float(jnp.abs(b).max())
                                                 + 1e-9)
            assert rel < 2e-4, (s, sk, causal, name, rel)


def test_auto_block_selection():
    """_auto_blocks: long sequences get 1024-wide blocks (grid-cell
    overhead dominates below that on v5e), mid-length sequences cap the
    block so padding waste stays under 20%, and the f32 backward caps at
    512 (1024 f32 operand blocks exceed VMEM)."""
    from mmlspark_tpu.ops.flash_attention import _auto_blocks
    assert _auto_blocks(16384, 16384, jnp.bfloat16) == (1024, 1024, 1024,
                                                        1024)
    assert _auto_blocks(16384, 16384, jnp.float32) == (1024, 1024, 512, 512)
    # S=1100 at block 1024 would pad to 2048 (46% waste) -> falls to 256
    bq, bk, _, _ = _auto_blocks(1100, 1100, jnp.float32)
    assert (bq, bk) == (256, 256)
    # S=1536 is exactly 3x512: 512 wins over 256
    assert _auto_blocks(1536, 1536, jnp.float32)[0] == 512
    assert _auto_blocks(300, 300, jnp.float32)[0] == 256


def test_bf16_operands_fwd_and_grad():
    """bf16 inputs run the matmuls in bf16 (input dtype) with f32
    accumulation, at sequence lengths long enough to take the AUTO 1024
    blocks and the maskless interior fast path. Interpret mode executes
    the same program CI-side; tolerance is the bf16 rounding band."""
    rng = np.random.default_rng(5)
    s, h, d = 2048, 2, 64
    qf = rng.normal(size=(s, h, d)).astype(np.float32)
    kf = rng.normal(size=(s, h, d)).astype(np.float32)
    vf = rng.normal(size=(s, h, d)).astype(np.float32)
    q, k, v = (jnp.asarray(a, jnp.bfloat16) for a in (qf, kf, vf))
    out = flash_attention(q, k, v, causal=True)
    assert out.dtype == jnp.bfloat16
    ref = reference_attention(jnp.asarray(qf), jnp.asarray(kf),
                              jnp.asarray(vf), causal=True)
    rel = float(jnp.abs(out.astype(jnp.float32) - ref).max() /
                (jnp.abs(ref).max() + 1e-9))
    assert rel < 3e-2, rel

    # gradients through the bf16 backward kernels (ds/p down-casts)
    g = jax.grad(lambda q, k, v: flash_attention(
        q, k, v, causal=True).astype(jnp.float32).sum(),
        argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda q, k, v: reference_attention(
        q, k, v, causal=True).astype(jnp.float32).sum(),
        argnums=(0, 1, 2))(jnp.asarray(qf), jnp.asarray(kf),
                           jnp.asarray(vf))
    for name, a, b in zip("qkv", g, gr):
        assert a.dtype == jnp.bfloat16, name
        rel = float(jnp.abs(a.astype(jnp.float32) - b).max() /
                    (jnp.abs(b).max() + 1e-9))
        assert rel < 5e-2, (name, rel)


def test_stats_flash_backward_matches_dense_reference():
    """flash_attention_stats' VJP is now FLASH (O(block), lse := m,
    dsum := -dl). Against the dense XLA reference it must agree exactly
    for a SHIFT-INVARIANT consumer (the contract — the ring merge's
    weights cancel the reference shift), across causal offsets including
    partially- and fully-masked blocks."""
    from mmlspark_tpu.ops.flash_attention import (_stats_xla_reference,
                                                  flash_attention_stats)
    rng = np.random.default_rng(3)
    s, h, d = 300, 2, 64

    for q_off, k_off, causal in [(0, 0, True), (0, 0, False),
                                 (s, 0, True),      # fully visible block
                                 (128, 0, True)]:   # diagonal crosses block
        q = jnp.asarray(rng.normal(size=(s, h, d)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(s, h, d)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(s, h, d)), jnp.float32)
        w = jnp.asarray(rng.normal(size=(s, h, d)), jnp.float32)

        def consumer(acc, m, l):
            # ring-merge-shaped shift-invariant readout: weight e^{m-c}
            # rescales acc/l back to a fixed reference c=0, flagged rows
            # (m == -1e30) fold to zero weight exactly like the ring
            wgt = jnp.exp(jnp.minimum(m, 50.0))            # (H, S)
            acc_h = jnp.moveaxis(acc, 0, 1)                # (H, S, D)
            num = acc_h * wgt[..., None]
            den = l * wgt + 1e-9
            return (jnp.moveaxis(num / den[..., None], 0, 1) * w).sum()

        def loss_flash(q, k, v):
            return consumer(*flash_attention_stats(
                q, k, v, q_offset=q_off, k_offset=k_off, causal=causal,
                scale=0.125))

        def loss_dense(q, k, v):
            return consumer(*_stats_xla_reference(
                q, k, v, q_off, k_off, causal, 0.125))

        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
        for name, a, b in zip("qkv", gf, gd):
            rel = float(jnp.abs(a - b).max()) / (float(jnp.abs(b).max())
                                                 + 1e-9)
            assert rel < 2e-4, (q_off, k_off, causal, name, rel)


def test_explicit_blocks_cap_f32_backward(monkeypatch):
    """An f32 caller passing block_q=1024 must NOT pin the backward at
    1024 — that is the documented f32-backward VMEM compile failure, and
    it would surface only at grad time (round-4 advisor). The cap is the
    same dtype ceiling _auto_blocks applies."""
    import mmlspark_tpu.ops.flash_attention as fa
    seen = {}
    real = fa._flash_shd

    def spy(q, k, v, causal, scale, bq, bk, bwd_bq, bwd_bk, interpret):
        seen.update(bq=bq, bk=bk, bwd_bq=bwd_bq, bwd_bk=bwd_bk)
        return real(q, k, v, causal, scale, bq, bk, bwd_bq, bwd_bk,
                    interpret)

    monkeypatch.setattr(fa, "_flash_shd", spy)
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(64, 1, 32)), jnp.float32)
    fa.flash_attention(q, q, q, causal=True, block_q=1024, block_k=1024,
                       interpret=True)
    assert seen["bq"] == seen["bk"] == 1024       # forward stays pinned
    assert seen["bwd_bq"] == seen["bwd_bk"] == fa._BWD_BLOCK_F32
    # bf16 keeps the full pin (its backward fits VMEM at 1024)
    fa.flash_attention(q.astype(jnp.bfloat16), q.astype(jnp.bfloat16),
                       q.astype(jnp.bfloat16), causal=True, block_q=1024,
                       block_k=1024, interpret=True)
    assert seen["bwd_bq"] == 1024


def test_stats_debug_exact_vjp_path():
    """DEBUG_STATS_EXACT_VJP routes stats gradients through the dense
    reference (exact for ALL consumers) — for a shift-invariant consumer
    it must agree with the flash backward, which is how a new consumer
    verifies its own gradients before trusting the O(block) path."""
    import mmlspark_tpu.ops.flash_attention as fa
    rng = np.random.default_rng(7)
    s, h, d = 128, 2, 32
    q = jnp.asarray(rng.normal(size=(s, h, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(s, h, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(s, h, d)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(s, h, d)), jnp.float32)

    def loss(q, k, v):
        acc, m, l = fa.flash_attention_stats(q, k, v, q_offset=0, k_offset=0,
                                             causal=True, scale=0.125)
        wgt = jnp.exp(jnp.minimum(m, 50.0))
        num = jnp.moveaxis(acc, 0, 1) * wgt[..., None]
        den = l * wgt + 1e-9
        return (jnp.moveaxis(num / den[..., None], 0, 1) * w).sum()

    g_flash = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    try:
        fa.DEBUG_STATS_EXACT_VJP = True
        g_exact = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    finally:
        fa.DEBUG_STATS_EXACT_VJP = False
    for name, a, b in zip("qkv", g_flash, g_exact):
        rel = float(jnp.abs(a - b).max()) / (float(jnp.abs(b).max()) + 1e-9)
        assert rel < 2e-4, (name, rel)


def test_flash_backward_through_jit_and_composition():
    """grad-of-jit over a small transformer-block-like composition: the
    custom VJP must thread through scan/jit without shape surprises."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from mmlspark_tpu.ops.flash_attention import flash_attention

    rng = np.random.default_rng(1)
    s, h, d = 128, 2, 32
    x = jnp.asarray(rng.normal(size=(s, h * d)), jnp.float32)
    wq = jnp.asarray(rng.normal(size=(h * d, h * d)) * 0.1, jnp.float32)

    @jax.jit
    def loss(wq):
        q = (x @ wq).reshape(s, h, d)
        k = x.reshape(s, h, d)
        v = x.reshape(s, h, d)
        return flash_attention(q, k, v, causal=True).sum()

    g = jax.grad(loss)(wq)
    assert np.isfinite(np.asarray(g)).all()
    assert float(jnp.abs(g).max()) > 0


# ---------------------------------------------------------------------------
# In-cell triangular schedule (PR 27): a diagonal cell of a causal
# self-attention call computes row strips up to the diagonal only.

def _tiles_delta(fn):
    """(computed, skipped) that `fn` adds to the flash.tiles.* counters,
    which the kernels' wrappers bump while a call is TRACED."""
    from mmlspark_tpu.reliability.metrics import reliability_metrics
    from mmlspark_tpu.telemetry import names as tnames
    before = (reliability_metrics.get(tnames.FLASH_TILES_COMPUTED),
              reliability_metrics.get(tnames.FLASH_TILES_SKIPPED))
    out = fn()
    return out, (reliability_metrics.get(tnames.FLASH_TILES_COMPUTED)
                 - before[0],
                 reliability_metrics.get(tnames.FLASH_TILES_SKIPPED)
                 - before[1])


# (s, sk, block, dtype, causal, sub-tiles (computed, skipped) of forward +
# dq + dk/dv). Blocks of 512 are two strips of 256 a diagonal cell: 3
# sub-tiles computed and 1 left out, and 4 for a cell below the diagonal.
_DIAG_CASES = {
    # the schedule engages
    "one-cell": (512, 512, 512, "float32", True, (9, 3)),
    "one-cell-bf16": (512, 512, 512, "bfloat16", True, (9, 3)),
    "one-cell-padded": (400, 400, 512, "float32", True, (9, 3)),
    "two-cells": (1024, 1024, 512, "float32", True, (30, 6)),
    "two-cells-padded": (700, 700, 512, "float32", True, (30, 6)),
    "three-cells": (1536, 1536, 512, "float32", True, (63, 9)),
    "three-cells-bf16": (1536, 1536, 512, "bfloat16", True, (63, 9)),
    "auto-1024-bf16": (1024, 1024, None, "bfloat16", True, (30, 18)),
    # f32 at the auto blocks: forward one 1024 cell (10 + 6), backward 2 x 2
    # cells of 512 in each kernel (2 x 10 + 2 x 2)
    "auto-1024-f32": (1024, 1024, None, "float32", True, (30, 10)),
    # and the calls that must keep the whole-cell path
    "non-causal": (512, 512, 512, "float32", False, (3, 0)),
    "cross": (300, 512, 512, "float32", False, (3, 0)),
    "cross-causal": (512, 700, 512, "float32", True, (6, 0)),
    "blocks-of-256": (512, 512, 256, "float32", True, (12, 0)),
    "unequal-blocks": (512, 512, (512, 256), "float32", True, (6, 0)),
}


@pytest.mark.parametrize("case", sorted(_DIAG_CASES))
def test_diagonal_cell_schedule_parity(case):
    """Forward and q/k/v gradients against the dense f32 reference, with
    the flash.tiles.* counters saying whether the in-cell schedule engaged:
    one, two and three cells a side (the carry across cells, diagonal cells
    next to interior ones), padded lengths (the `seq_end` mask inside a
    diagonal strip), bf16 and f32, and the calls that keep the old path."""
    s, sk, block, dtype, causal, tiles = _DIAG_CASES[case]
    bq, bk = block if isinstance(block, tuple) else (block, block)
    rng = np.random.default_rng(11)
    h, d = 2, 32
    qf = rng.normal(size=(s, h, d)).astype(np.float32)
    kf = rng.normal(size=(sk, h, d)).astype(np.float32)
    vf = rng.normal(size=(sk, h, d)).astype(np.float32)
    w = jnp.asarray(rng.normal(size=(s, h, d)), jnp.float32)
    q, k, v = (jnp.asarray(a, dtype) for a in (qf, kf, vf))

    def loss(q, k, v):
        out = flash_attention(q, k, v, causal=causal, block_q=bq, block_k=bk)
        return (out.astype(jnp.float32) * w).sum(), out

    def ref_loss(q, k, v):
        # dense f32 softmax attention; positions from zero on both sides,
        # so it also covers causal calls with s != sk
        sc = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(d)
        if causal:
            sc = jnp.where(jnp.arange(s)[:, None] >= jnp.arange(sk)[None],
                           sc, -1e30)
        out = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, axis=-1), v)
        return (out * w).sum(), out

    (g, out), seen = _tiles_delta(
        lambda: jax.grad(loss, argnums=(0, 1, 2), has_aux=True)(q, k, v))
    assert seen == tiles
    gr, ref = jax.grad(ref_loss, argnums=(0, 1, 2), has_aux=True)(
        jnp.asarray(qf), jnp.asarray(kf), jnp.asarray(vf))
    tol_out, tol_grad = (2e-5, 2e-4) if dtype == "float32" else (3e-2, 5e-2)
    assert out.dtype == q.dtype
    err = float(jnp.abs(out.astype(jnp.float32) - ref).max()
                / jnp.abs(ref).max())
    assert err < tol_out, err
    for name, a, b in zip("qkv", g, gr):
        err = float(jnp.abs(a.astype(jnp.float32) - b).max()
                    / jnp.abs(b).max())
        assert err < tol_grad, (name, err)


def test_stats_with_traced_offsets_keep_whole_cell_path():
    """Ring attention's offsets come from `axis_index`: traced, so the
    diagonal is not static and `flash_attention_stats` keeps the whole-cell
    masked branch in both directions (skipped stays 0) with the numbers of
    the dense stats reference."""
    from mmlspark_tpu.ops.flash_attention import (_stats_xla_reference,
                                                  flash_attention_stats)
    rng = np.random.default_rng(4)
    s, h, d = 512, 2, 32
    q, k, v, w = (jnp.asarray(rng.normal(size=(s, h, d)), jnp.float32)
                  for _ in range(4))

    def consumer(acc, m, l):
        wgt = jnp.exp(jnp.minimum(m, 50.0))
        num = jnp.moveaxis(acc, 0, 1) * wgt[..., None]
        den = l * wgt + 1e-9
        return (jnp.moveaxis(num / den[..., None], 0, 1) * w).sum()

    @jax.jit
    def grads(q, k, v, q_off, k_off):
        return jax.grad(lambda q, k, v: consumer(*flash_attention_stats(
            q, k, v, q_off, k_off, causal=True, scale=0.125,
            block_q=512, block_k=512)), argnums=(0, 1, 2))(q, k, v)

    g, seen = _tiles_delta(lambda: grads(q, k, v, jnp.int32(0), jnp.int32(0)))
    assert seen == (3, 0)
    gd = jax.grad(lambda q, k, v: consumer(*_stats_xla_reference(
        q, k, v, 0, 0, True, 0.125)), argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", g, gd):
        rel = float(jnp.abs(a - b).max()) / (float(jnp.abs(b).max()) + 1e-9)
        assert rel < 2e-4, (name, rel)


def test_tiles_counter_reads_the_triangle():
    """Tracing (nothing runs) the cell's attention call, causal 1024 x 1024
    bf16 forward + backward, counts 10 of 16 sub-tiles of 256 in each of
    the three kernels; a 16k forward counts its 120 interior cells whole
    and its 16 diagonal ones by the triangle; a non-causal call and a
    stats call leave nothing out."""
    from mmlspark_tpu.ops.flash_attention import (_DIAG_TILE,
                                                  flash_attention_stats)
    assert _DIAG_TILE == 256
    x = jax.ShapeDtypeStruct((1024, 2, 64), jnp.bfloat16)

    def grad_of(causal):
        return jax.grad(lambda q, k, v: flash_attention(
            q, k, v, causal=causal).astype(jnp.float32).sum(),
            argnums=(0, 1, 2))

    _, seen = _tiles_delta(lambda: jax.make_jaxpr(grad_of(True))(x, x, x))
    assert seen == (3 * 10, 3 * 6)
    _, seen = _tiles_delta(lambda: jax.make_jaxpr(grad_of(False))(x, x, x))
    assert seen == (3, 0)
    x16 = jax.ShapeDtypeStruct((16384, 2, 64), jnp.bfloat16)
    _, seen = _tiles_delta(lambda: jax.make_jaxpr(
        lambda q, k, v: flash_attention(q, k, v, causal=True))(x16, x16, x16))
    assert seen == (120 * 16 + 16 * 10, 16 * 6)
    _, seen = _tiles_delta(lambda: jax.make_jaxpr(
        lambda q, k, v: flash_attention_stats(
            q, k, v, 0, 0, causal=True, scale=0.125))(x, x, x))
    assert seen == (1, 0)


# ------------------------------------------------------- a sliding window
def _windowed_dense(q, k, v, window):
    """Dense masked softmax: key s visible to query t iff
    t - window < s <= t (window None: causal alone)."""
    s = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(q.shape[-1])
    back = jnp.arange(q.shape[0])[:, None] - jnp.arange(k.shape[0])[None, :]
    seen = back >= 0 if window is None else (back >= 0) & (back < window)
    p = jax.nn.softmax(jnp.where(seen[None], s, -1e30), axis=-1)
    return jnp.einsum("hqk,khd->qhd", p, v)


# (sequence, value width, window, block): windows of 1, inside one block,
# of the cell's 512 over two strips of a 512 block, across a padded tail,
# and no shorter than the sequence (the call without a window)
_WINDOW_CASES = {"one": (300, 128, 1, None), "short": (300, 128, 77, None),
                 "cell": (1024, 128, 512, 512),
                 "ragged": (1100, 64, 300, 512),
                 "whole": (300, 128, 300, None)}


@pytest.mark.parametrize("case", sorted(_WINDOW_CASES))
def test_window_matches_dense_masked_softmax(case):
    """Forward and the three gradients, float32, interpret mode; values as
    wide as the keys and twice as wide."""
    seq, dv, window, block = _WINDOW_CASES[case]
    rng = np.random.default_rng(3)
    q, k = (jnp.asarray(rng.normal(size=(seq, 2, 64)), jnp.float32)
            for _ in range(2))
    v, w = (jnp.asarray(rng.normal(size=(seq, 2, dv)), jnp.float32)
            for _ in range(2))

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True, window=window,
                               block_q=block, block_k=block)

    with jax.default_matmul_precision("highest"):
        got, got_g = jax.value_and_grad(
            lambda *a: (flash(*a) * w).sum(), argnums=(0, 1, 2))(q, k, v)
        want, want_g = jax.value_and_grad(
            lambda *a: (_windowed_dense(*a, window) * w).sum(),
            argnums=(0, 1, 2))(q, k, v)
        assert float(jnp.abs(flash(q, k, v)
                             - _windowed_dense(q, k, v, window)).max()) < 1e-5
    assert abs(float(got - want)) < 1e-2
    for name, a, b in zip("qkv", got_g, want_g):
        assert float(jnp.abs(a - b).max()) < 2e-5, name


def test_no_window_is_the_call_it_always_was():
    """`window=None`, no `window` at all and a window no shorter than the
    sequence trace to one jaxpr, whose kernels are the three unwindowed
    ones; a shorter window traces the three `_win` kernels and none of the
    others; tiles are counted either way."""
    x = jax.ShapeDtypeStruct((2048, 2, 64), jnp.bfloat16)

    def jaxpr(**kw):
        return str(jax.make_jaxpr(jax.grad(
            lambda q, k, v: flash_attention(q, k, v, causal=True, **kw
                                            ).astype(jnp.float32).sum(),
            argnums=(0, 1, 2)))(x, x, x))

    plain = jaxpr()
    assert jaxpr(window=None) == plain == jaxpr(window=2048)
    names = ["flash_fwd", "flash_dq", "flash_dkv"]
    assert all(f"name={n}\n" in plain or f"name={n} " in plain
               or f"name={n}]" in plain for n in names), plain[:400]
    assert "_win" not in plain
    (windowed, seen) = _tiles_delta(lambda: jaxpr(window=512))
    assert all(f"name={n}_win" in windowed for n in names)
    assert all(f"name={n}\n" not in windowed and f"name={n} " not in windowed
               and f"name={n}]" not in windowed for n in names)
    # 4 query blocks of 512: the diagonal cell 3 sub-tiles of 256, the one
    # before it 3 (3 blocks have one); of the 10 causal cells' 40
    assert seen == (3 * (4 * 3 + 3 * 3), 3 * (40 - 21))


def test_a_window_wants_causal_self_attention():
    q = jnp.zeros((64, 1, 8))
    with pytest.raises(ValueError, match="bounds causal self-attention"):
        flash_attention(q, q, q, causal=False, window=4)
    with pytest.raises(ValueError, match="bounds causal self-attention"):
        flash_attention(q, jnp.zeros((32, 1, 8)), jnp.zeros((32, 1, 8)),
                        causal=True, window=4)
