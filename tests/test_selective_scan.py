"""The selective state-space scan (`ops/selective_scan.py`): its XLA form
and its Pallas kernels (through the interpreter) against a plain loop over
positions, forward and the gradient of every input."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mmlspark_tpu.ops import selective_scan as ss
from mmlspark_tpu.reliability.metrics import reliability_metrics
from mmlspark_tpu.telemetry import names as tnames

INPUTS = ("v", "dt", "A", "B", "C", "D")
ROUTES = (tnames.SSM_SCAN_ROUTE_PALLAS, tnames.SSM_SCAN_ROUTE_XLA)


def positional(v, dt, a, b, c, d):
    """s_t = exp(dt_t A) s_{t-1} + dt_t v_t B_t; y_t = s_t C_t + D v_t."""
    def one(v, dt, b, c):
        def step(s, x):
            v_t, dt_t, b_t, c_t = x
            s = jnp.exp(dt_t[:, None] * a) * s \
                + (dt_t * v_t)[:, None] * b_t[None, :]
            return s, (s * c_t[None, :]).sum(-1) + d * v_t
        return jax.lax.scan(step, jnp.zeros(a.shape, jnp.float32),
                            (v, dt, b, c))[1]
    return jax.vmap(one)(v, dt, b, c)


def inputs(seq, channels=256, states=16, batch=2, dtype=jnp.float32, seed=0):
    rng = np.random.default_rng(seed)

    def normal(*shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.float32)

    args = (normal(batch, seq, channels).astype(dtype),
            jax.nn.softplus(normal(batch, seq, channels) - 2.0),
            -jnp.exp(normal(channels, states) * 0.5),
            normal(batch, seq, states).astype(dtype),
            normal(batch, seq, states).astype(dtype), normal(channels))
    return args, normal(batch, seq, channels)


def out_and_grads(fn, args, cot):
    def loss(*a):
        return (fn(*a).astype(jnp.float32) * cot).sum()
    return fn(*args), jax.grad(loss, argnums=tuple(range(6)))(*args)


def worst(got, want):
    return float(jnp.abs(got.astype(jnp.float32) - want).max()
                 / (jnp.abs(want).max() + 1e-9))


FORMS = {
    "xla": lambda *a: ss._scan_xla(*a, chunk=16),
    "pallas": lambda *a: ss.selective_scan_pallas(*a, chunk=32,
                                                  interpret=True),
}


@pytest.fixture(scope="module", params=[64, 40], ids=["whole", "ragged"])
def against_the_loop(request):
    """Every form's output and gradients beside the loop's, on a sequence
    of whole chunks and on one that is no multiple of either form's
    chunk."""
    args, cot = inputs(request.param)
    want = out_and_grads(positional, args, cot)
    return want, {name: out_and_grads(fn, args, cot)
                  for name, fn in FORMS.items()}


@pytest.mark.parametrize("form", sorted(FORMS))
def test_forward_matches_the_positional_loop(against_the_loop, form):
    (want, _), got = against_the_loop
    assert worst(got[form][0], want) < 1e-5


@pytest.mark.parametrize("which", range(6), ids=INPUTS)
@pytest.mark.parametrize("form", sorted(FORMS))
def test_gradient_matches_the_positional_loop(against_the_loop, form, which):
    (_, want), got = against_the_loop
    assert worst(got[form][1][which], want[which]) < 1e-5


def test_bfloat16_slabs_stay_near_float32():
    """bfloat16 v, B, C (dt and the state stay float32): the kernels and
    the XLA form agree with each other to bfloat16's rounding of y."""
    args, cot = inputs(48, dtype=jnp.bfloat16)
    y_k, g_k = out_and_grads(FORMS["pallas"], args, cot)
    y_x, g_x = out_and_grads(FORMS["xla"], args, cot)
    assert y_k.dtype == jnp.bfloat16
    assert worst(y_k, y_x.astype(jnp.float32)) < 2e-2
    for name, a, b in zip(INPUTS, g_k, g_x):
        assert worst(a, b.astype(jnp.float32)) < 3e-2, name


def routes(fn):
    before = [reliability_metrics.get(n) for n in ROUTES]
    fn()
    return tuple(reliability_metrics.get(n) - b
                 for n, b in zip(ROUTES, before))


def test_the_route_is_counted_and_never_silent():
    """Off the TPU `selective_scan` takes the XLA form and counts it; an
    interpret-mode call counts the kernels; shapes the kernels do not fit
    are refused by the direct call and fall to the XLA form otherwise."""
    args, _ = inputs(32)
    assert routes(lambda: ss.selective_scan(*args)) == (0, 1)
    assert routes(lambda: ss.selective_scan_pallas(
        *args, chunk=32, interpret=True)) == (1, 0)
    narrow, _ = inputs(32, channels=96)
    assert not ss.pallas_fits(narrow[0], narrow[3])
    assert routes(lambda: ss.selective_scan(*narrow)) == (0, 1)
    with pytest.raises(ValueError, match="channels in 128s"):
        ss.selective_scan_pallas(*narrow, interpret=True)


def test_a_checkpoint_that_keeps_the_scan_runs_no_second_forward():
    """Under `save_only_these_names(ssm.forward)` the backward pass of a
    checkpointed call holds one `ssm_fwd` and one `ssm_bwd`; without the
    name the forward kernel appears twice."""
    args, cot = inputs(32)

    def calls(policy):
        fn = jax.checkpoint(FORMS["pallas"], policy=policy)
        text = str(jax.make_jaxpr(jax.grad(
            lambda *a: (fn(*a) * cot).sum(), argnums=(0, 1)))(*args))
        return text.count("name=" + ss.KERNEL_FWD), \
            text.count("name=" + ss.KERNEL_BWD)

    keep = jax.checkpoint_policies.save_only_these_names(tnames.KEEP_SSM)
    assert calls(keep) == (1, 1)
    assert calls(jax.checkpoint_policies.nothing_saveable) == (2, 1)
