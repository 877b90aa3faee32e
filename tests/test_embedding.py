"""The embedding lookup's gradient kernel (`ops/embedding.py`: `embed_grad`)
through the interpreter on the CPU: the forward is the plain gather bit for
bit, the gradient is the float32 sum of the cotangent rows by id, over ids
that meet every edge of the plan (heavy duplicates, one id for all, the
first and last ids, a vocabulary that ends inside a block, several chunks
in one block), at the cells' widths, both dtypes and inside a scan; the
shape rule and the counters that say which route was taken."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from mmlspark_tpu.ops import embedding as E
from mmlspark_tpu.reliability.metrics import reliability_metrics
from mmlspark_tpu.telemetry import names as tnames

ROUTES = (tnames.EMBED_GRAD_ROUTE_PALLAS, tnames.EMBED_GRAD_ROUTE_XLA)
# uninitialised memory reads as NaN: a block the kernel did not write shows
INTERPRET = pltpu.InterpretParams()


def make_ids(name, vocab, n, rng):
    if name == "zipf":                    # the cells' stream: top id ~10%
        w = np.arange(1, vocab + 1, dtype=np.float64) ** -1.0
        ranks = np.searchsorted(np.cumsum(w / w.sum()), rng.random(n))
        return rng.permutation(vocab)[np.minimum(ranks, vocab - 1)]
    if name == "all_same":
        return np.full(n, vocab // 3)
    if name == "ends":                    # ids 0 and V - 1 only
        return rng.choice([0, vocab - 1], size=n)
    if name == "uniform":
        return rng.integers(0, vocab, n)
    raise KeyError(name)


def grads(vocab, d, n, dtype, ids_name, trips=0, seed=0):
    """(kernel's gradient, float32 reference, XLA's in the table's dtype,
    forward equal) for a loss sum(lookup(table, ids) * g); `trips` > 0:
    ids (trips, n) through a lax.scan that sums the trips' losses."""
    rng = np.random.default_rng(seed)
    shape = (trips, n) if trips else (n,)
    ids = jnp.asarray(make_ids(ids_name, vocab, int(np.prod(shape)), rng)
                      .reshape(shape), jnp.int32)
    table = jnp.asarray(rng.standard_normal((vocab, d)), dtype)
    # the cotangent reaches the backward in the table's dtype
    g = jnp.asarray(rng.standard_normal(shape + (d,)), dtype)

    def loss(lookup, t):
        def one(i, gg):
            return (lookup(t, i).astype(jnp.float32)
                    * gg.astype(jnp.float32)).sum()
        if not trips:
            return one(ids, g)
        return jax.lax.scan(lambda acc, x: (acc + one(*x), None),
                            jnp.float32(0), (ids, g))[0]

    def plain(t, i):
        return t[i]

    def kernel(t, i):
        return E.lookup_pallas(t, i, INTERPRET)

    got = jax.grad(lambda t: loss(kernel, t))(table)
    want = jax.grad(lambda t: loss(plain, t))(table.astype(jnp.float32))
    xla = jax.grad(lambda t: loss(plain, t))(table)
    forward_equal = bool((kernel(table, ids) == table[ids]).all())
    return got, want, xla, forward_equal


def worst(got, want):
    got = got.astype(jnp.float32)
    return float(jnp.abs(got - want).max() / jnp.abs(want).max())


CASES = {
    # name: (vocab, d, n, ids, trips)
    "zipf_duplicates": (700, 128, 1000, "zipf", 0),
    "all_same_id": (700, 128, 600, "all_same", 0),
    "first_and_last_id": (700, 128, 300, "ends", 0),
    "vocab_ends_inside_a_block": (300, 128, 257, "uniform", 0),
    "one_block_many_chunks": (200, 128, 1100, "uniform", 0),
    "width_1024": (520, 1024, 300, "zipf", 0),
    "width_2048": (520, 2048, 300, "zipf", 0),
    "width_2560": (520, 2560, 300, "zipf", 0),
    "two_trip_scan": (700, 256, 512, "zipf", 2),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("case", list(CASES))
def test_gradient_is_the_float32_sum_by_id(case, dtype):
    vocab, d, n, ids_name, trips = CASES[case]
    got, want, xla, forward_equal = grads(vocab, d, n, dtype, ids_name,
                                          trips)
    assert forward_equal
    assert got.dtype == dtype and got.shape == (vocab, d)
    assert bool(jnp.isfinite(got.astype(jnp.float32)).all())
    if dtype == jnp.float32:
        assert worst(got, want) < 1e-6
    else:
        # float32 sums rounded once: within half a bfloat16 ulp of each
        # element (a scan's trips round again as they add), and no worse
        # than the scatter that adds in bfloat16
        gap = jnp.abs(got.astype(jnp.float32) - want)
        if not trips:
            assert bool((gap <= 2.0 ** -8 * jnp.abs(want) + 1e-30).all())
        assert worst(got, want) < 2.0 ** -7
        assert worst(got, want) <= worst(xla, want)


def test_plan_walks_every_block_once_and_each_run_by_its_chunks():
    vocab, n = 1000, 700
    ids = jnp.asarray(make_ids("zipf", vocab, n, np.random.default_rng(3)),
                      jnp.int32)
    plan = E.grad_plan(ids, vocab)
    n_blocks, n_chunks = -(-vocab // E.BLOCK), -(-n // E.CHUNK)
    steps = int(plan["n_steps"][0])
    block = np.asarray(plan["block"])[:steps]
    chunk = np.asarray(plan["chunk"])[:steps]
    assert plan["block"].shape == (n_blocks + n_chunks,)
    np.testing.assert_array_equal(np.unique(block), np.arange(n_blocks))
    assert (np.diff(block) >= 0).all() and (np.diff(chunk) >= 0).all()
    keys = np.asarray(plan["keys"])
    np.testing.assert_array_equal(keys[:n], np.sort(np.asarray(ids)))
    np.testing.assert_array_equal(np.asarray(ids)[np.asarray(plan["order"])],
                                  keys[:n])
    for b in range(n_blocks):
        rows = np.nonzero((keys >= b * E.BLOCK) & (keys < (b + 1) * E.BLOCK))
        want = sorted(set(rows[0] // E.CHUNK)) or None
        mine = chunk[block == b]
        live = np.asarray(plan["live"])[:steps][block == b]
        if want is None:
            assert len(mine) == 1 and live[0] == 0
        else:
            assert list(mine) == want and (live == 1).all()


@pytest.mark.parametrize("shape,dtype,ids_dtype,fits", [
    ((50257, 1024), jnp.bfloat16, jnp.int32, True),
    ((25008, 2560), jnp.bfloat16, jnp.int32, True),
    ((8192, 2048), jnp.float32, jnp.int32, True),
    ((1000, 100), jnp.bfloat16, jnp.int32, False),
    ((1000, 128), jnp.float16, jnp.int32, False),
    ((1000, 128), jnp.bfloat16, jnp.uint8, False),
])
def test_shape_rule(shape, dtype, ids_dtype, fits):
    table = jax.ShapeDtypeStruct(shape, dtype)
    ids = jax.ShapeDtypeStruct((4, 8), ids_dtype)
    assert E.pallas_fits(table, ids) is fits


def routes(fn):
    before = [reliability_metrics.get(r) for r in ROUTES]
    fn()
    return tuple(reliability_metrics.get(r) - b
                 for r, b in zip(ROUTES, before))


def test_off_the_tpu_the_scatter_and_both_routes_are_counted():
    table = jnp.ones((300, 128), jnp.bfloat16)
    ids = jnp.arange(16, dtype=jnp.int32).reshape(2, 8)

    def grad_of(lookup):
        return lambda: jax.eval_shape(jax.grad(
            lambda t: lookup(t, ids).astype(jnp.float32).sum()), table)
    assert routes(grad_of(E.lookup)) == (0, 1)
    assert routes(grad_of(lambda t, i: E.lookup_pallas(t, i, True))) == (1, 0)
    with pytest.raises(ValueError, match="128s"):
        E.lookup_pallas(jnp.ones((300, 100)), ids)
