"""The Gated-DeltaNet mixer's fused passes (`ops/gdn_mixer.py`: kernels
`gdn_prep_fwd` / `gdn_prep_bwd`, `gdn_post_fwd` / `gdn_post_bwd`) through
the Pallas interpreter on the CPU against their plain slab form: outputs and
every gradient, the convolution's halo across row blocks in both directions,
the shape rule and the counters that say which form was taken. The kernels
compiled for a described v5e, and their place in the compiled layer's
regions, are in test_gated_delta_kernel.py: one file a process may describe
the chip in."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mmlspark_tpu.ops import gdn_mixer as gm
from mmlspark_tpu.reliability.metrics import reliability_metrics
from mmlspark_tpu.telemetry import names as tnames

# 2 key heads under 4 value heads of 128: grouped key heads, lanes of q, k
# and v that start at different blocks of qkv
HEADS = (2, 128, 4, 128)
N_QKV = 2 * 2 * 128 + 4 * 128
EPS = 1e-6
# (positions, positions a block): one, five and five row blocks, the last two
# with a padded tail
SIZES = [(64, 64), (150, 32), (257, 64)]


def prep_inputs(seq, dtype, batch=2):
    ks = jax.random.split(jax.random.PRNGKey(seq), 5)
    qkv = jax.random.normal(ks[0], (batch, seq, N_QKV)).astype(dtype)
    taps = jax.random.normal(ks[1], (4, N_QKV)) * 0.5
    cots = [jax.random.normal(k, (batch, seq, w))
            for k, w in zip(ks[2:], (256, 256, 512))]
    return (qkv, taps), cots


def post_inputs(seq, dtype, batch=2):
    ks = jax.random.split(jax.random.PRNGKey(seq + 1), 4)
    return ((jax.random.normal(ks[0], (batch, seq, 512)).astype(dtype),
             jax.random.normal(ks[1], (batch, seq, 512)).astype(dtype),
             1.0 + 0.1 * jax.random.normal(ks[2], (128,))),
            [jax.random.normal(ks[3], (batch, seq, 512))])


def out_and_grads(fn, args, cots):
    def loss(*a):
        out = fn(*a)
        outs = out if isinstance(out, tuple) else (out,)
        return sum((o.astype(jnp.float32) * c).sum()
                   for o, c in zip(outs, cots)), outs
    (_, outs), grads = jax.jit(jax.value_and_grad(
        loss, argnums=tuple(range(len(args))), has_aux=True))(*args)
    return outs + grads


def worst(got, want):
    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    return float(jnp.abs(got - want).max() / jnp.abs(want).max())


def prepare_kernels(rows, lanes=256):
    return lambda qkv, taps: gm.prepare_pallas(
        qkv, taps, HEADS, interpret=True, rows=rows, lanes=lanes)


def finish_kernels(rows, lanes=256):
    return lambda o, z, w: gm.finish_pallas(
        o, z, w, 128, EPS, interpret=True, rows=rows, lanes=lanes)


def prepare_plain(qkv, taps):
    return gm.prepare_xla(qkv, taps, HEADS)


def finish_plain(o, z, w):
    return gm.finish_xla(o, z, w, 128, EPS)


@pytest.mark.parametrize("seq,rows", SIZES)
def test_float32_prepare_matches_the_plain_form(seq, rows):
    """q, k, v and the gradients of qkv and the taps."""
    args, cots = prep_inputs(seq, jnp.float32)
    got = out_and_grads(prepare_kernels(rows), args, cots)
    want = out_and_grads(prepare_plain, args, cots)
    assert [g.shape for g in got] == [w.shape for w in want]
    for g, w in zip(got, want):
        assert worst(g, w) < 2e-6


@pytest.mark.parametrize("seq,rows", SIZES)
def test_float32_finish_matches_the_plain_form(seq, rows):
    """The gated slab and the gradients of o, z and the norm's weight."""
    args, cots = post_inputs(seq, jnp.float32)
    got = out_and_grads(finish_kernels(rows), args, cots)
    want = out_and_grads(finish_plain, args, cots)
    assert [g.shape for g in got] == [w.shape for w in want]
    for g, w in zip(got, want):
        assert worst(g, w) < 2e-6


@pytest.mark.parametrize("seq,rows", SIZES)
@pytest.mark.parametrize("stage", ["prepare", "finish"])
def test_bfloat16_kernels_match_the_plain_form(stage, seq, rows):
    """In bfloat16 the forward is the plain form's to the bit (the same
    float32 arithmetic, the same rounding points); the gradients differ by
    the roundings autodiff of the plain form makes on the way and the
    kernels do not, and are no further from float32 than it is."""
    inputs, kernels, plain = {
        "prepare": (prep_inputs, prepare_kernels, prepare_plain),
        "finish": (post_inputs, finish_kernels, finish_plain)}[stage]
    args, cots = inputs(seq, jnp.bfloat16)
    exact = tuple(a.astype(jnp.float32) for a in args)
    got = out_and_grads(kernels(rows), args, cots)
    want = out_and_grads(plain, args, cots)
    true = out_and_grads(plain, exact, cots)
    n_out = len(cots)
    for g, w in zip(got[:n_out], want[:n_out]):
        assert g.dtype == jnp.bfloat16
        assert float(jnp.abs(g.astype(jnp.float32)
                             - w.astype(jnp.float32)).max()) == 0.0
    for g, w, t in zip(got[n_out:], want[n_out:], true[n_out:]):
        assert g.dtype == w.dtype
        assert worst(g, w) < 0.02
        assert worst(g, t) < max(1.5 * worst(w, t), 0.01)


def test_convolution_crosses_a_row_block_both_ways():
    """Only the last position of one row block and the first of the next
    are non-zero: the forward's taps reach back over the boundary, the
    backward's transposed taps reach forward over it."""
    rows, seq = 16, 48
    qkv = np.zeros((1, seq, N_QKV), np.float32)
    qkv[0, rows - 1] = 1.0
    qkv[0, rows] = -2.0
    taps = jnp.asarray(np.arange(1, 5, dtype=np.float32)[:, None]
                       * np.ones((1, N_QKV), np.float32))
    # v = silu(convolution): position t sums taps[3 - i] x[t - i]
    _, _, v = prepare_kernels(rows)(jnp.asarray(qkv), taps)
    c = np.zeros(seq, np.float32)
    c[rows - 1:rows + 3] += np.array([4.0, 3.0, 2.0, 1.0])
    c[rows:rows + 4] += -2.0 * np.array([4.0, 3.0, 2.0, 1.0])
    np.testing.assert_allclose(np.asarray(v[0, :, 0]),
                               c / (1.0 + np.exp(-c)), rtol=1e-6, atol=1e-7)
    # the cotangent likewise: d v non-zero at the same two positions
    cots = [jnp.zeros((1, seq, 256)), jnp.zeros((1, seq, 256)),
            jnp.asarray(np.where(np.isin(np.arange(seq), (rows - 1, rows)),
                                 1.0, 0.0)[None, :, None]
                        * np.ones((1, 1, 512), np.float32))]
    args = (jax.random.normal(jax.random.PRNGKey(0), (1, seq, N_QKV)), taps)
    got = out_and_grads(prepare_kernels(rows), args, cots)
    want = out_and_grads(prepare_plain, args, cots)
    dqkv = np.asarray(got[3][0, :, -1])
    assert np.flatnonzero(dqkv).tolist() == list(range(rows - 4, rows + 1))
    for g, w in zip(got[3:], want[3:]):
        assert worst(g, w) < 2e-6


def test_position_0_sees_zeros_not_the_sequence_before():
    """The second sequence of a batch starts from nothing: its outputs and
    its gradient are those it has alone, whatever the first one holds."""
    (qkv, taps), cots = prep_inputs(40, jnp.float32)
    loud = qkv.at[0].set(100.0)
    for fn in (prepare_kernels(16), prepare_plain):
        both = out_and_grads(fn, (loud, taps), cots)
        alone = out_and_grads(fn, (qkv[1:], taps), [c[1:] for c in cots])
        # a leak of the first sequence's 100s would be of order 1
        for b, a in zip(both[:4], alone[:4]):
            assert worst(b[1], a[0]) < 1e-5


def routes(fn):
    names = (tnames.GDN_MIXER_ROUTE_PALLAS, tnames.GDN_MIXER_ROUTE_XLA)
    before = [reliability_metrics.get(n) for n in names]
    fn()
    return tuple(reliability_metrics.get(n) - b
                 for n, b in zip(names, before))


@pytest.mark.parametrize("dk,dv,dtype", [
    (16, 24, jnp.float32),            # the toy widths of the trainer's tests
    (128, 64, jnp.float32),           # a value head that is no 128 lanes
    (128, 128, jnp.float16),          # a dtype the kernels were not written for
    (128, 128, jnp.float32),          # fits, but this is not a TPU
])
def test_what_does_not_fit_takes_the_plain_form_and_is_counted(dk, dv, dtype):
    heads = (2, dk, 2, dv)
    fits = dk == dv == 128 and dtype == jnp.float32
    assert gm.kernels_fit(dtype, dk, dv, 4 * dk) == fits
    qkv = jnp.ones((1, 20, 4 * dk + 2 * dv), dtype)
    taps = jnp.ones((4, 4 * dk + 2 * dv), jnp.float32)
    assert routes(lambda: gm.gdn_prepare(qkv, taps, heads)) == (0, 1)
    o = jnp.ones((1, 20, 2 * dv), dtype)
    assert routes(lambda: gm.gdn_finish(
        o, o, jnp.ones((dv,)), dv, EPS)) == (0, 1)


def test_interpret_calls_take_the_kernels_and_are_counted():
    (qkv, taps), _ = prep_inputs(20, jnp.float32, batch=1)
    (o, z, w), _ = post_inputs(20, jnp.float32, batch=1)
    assert routes(lambda: prepare_kernels(32)(qkv, taps)) == (1, 0)
    assert routes(lambda: finish_kernels(32)(o, z, w)) == (1, 0)
    with pytest.raises(ValueError, match="multiples of 128"):
        gm.prepare_pallas(qkv[..., :96], taps[:, :96], (1, 16, 2, 32),
                          interpret=True)
    with pytest.raises(ValueError, match="multiples of 128"):
        gm.finish_pallas(o[..., :96], z[..., :96], w[:32], 32, EPS,
                         interpret=True)
