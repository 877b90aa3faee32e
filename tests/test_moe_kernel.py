"""The expert layer's Pallas kernels (`ops/moe_experts.py`: `moe_fwd`,
`moe_bwd`) through the interpreter on the CPU: output and all five
gradients against the XLA tile loop of `models/dnn/moe.py` over routings
that meet every edge of the tile plan, the shape rule that chooses between
them and the counters that say which was taken. (Compiled for a described
v5e they are in tests/test_gated_delta_kernel.py, the one file that may
describe the chip.)"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from mmlspark_tpu.models.dnn import moe
from mmlspark_tpu.ops import moe_experts as me
from mmlspark_tpu.reliability.metrics import reliability_metrics
from mmlspark_tpu.telemetry import names as tnames

TILE = 16
N, D, F, E_ALL, HELD = 64, 128, 128, 8, (2, 6)
OUTPUTS = ("out", "dx", "dtop_p", "dw_gate", "dw_up", "dw_down")
ROUTES = (tnames.MOE_EXPERTS_ROUTE_PALLAS, tnames.MOE_EXPERTS_ROUTE_XLA)
# uninitialised memory reads as NaN and a copy lands when it is waited for:
# a row read before the tile before has written it, or a row no copy
# filled, shows
INTERPRET = pltpu.InterpretParams()


@pytest.fixture(autouse=True)
def small_tiles(monkeypatch):
    monkeypatch.setattr(moe, "TILE", TILE)


def random_routing(rng, n, k):
    return np.stack([rng.permutation(E_ALL)[:k] for _ in range(n)])


def routing(name):
    """idx (N, k) over all experts: the routing of that name."""
    rng = np.random.default_rng(7)
    lo, hi = HELD
    absent = [e for e in range(E_ALL) if not lo <= e < hi]
    if name == "random":
        return random_routing(rng, N, 2)
    if name == "empty_expert":            # expert lo + 1 gets no pair
        idx = random_routing(rng, N, 2)
        return np.where(idx == lo + 1, absent[0], idx)
    if name in ("one_full_tile", "one_tile_and_a_row"):
        rows = TILE + (name == "one_tile_and_a_row")
        idx = np.full((N, 1), absent[0])
        idx[:rows, 0] = lo
        return idx
    if name == "all_on_one":              # the trip count at its largest
        return np.full((N, 1), lo + 2)
    if name == "none_held":
        return rng.choice(absent, size=(N, 2))
    if name == "token_in_two_tiles":      # token 0 ends a run and starts one
        idx = np.full((N, 2), absent[0])
        idx[0] = (lo, lo + 1)
        idx[1:4, 0] = lo
        idx[4:30, 0] = lo + 1
        idx[4:30, 1] = absent[1]
        idx[1:4, 1] = absent[1]
        return idx
    raise KeyError(name)


ROUTINGS = ("random", "empty_expert", "one_full_tile", "one_tile_and_a_row",
            "all_on_one", "none_held", "token_in_two_tiles")


def inputs(idx, dtype, f=F, seed=0):
    rng = np.random.default_rng(seed)
    n, k = idx.shape
    e = HELD[1] - HELD[0]

    def normal(*shape, scale=1.0):
        return jnp.asarray(rng.standard_normal(shape) * scale, dtype)

    args = (normal(n, D), jnp.asarray(rng.random((n, k)) + 0.1, jnp.float32),
            normal(e, D, f, scale=D ** -0.5), normal(e, D, f, scale=D ** -0.5),
            normal(e, f, D, scale=f ** -0.5))
    return args, jnp.asarray(rng.standard_normal((n, D)), jnp.float32)


def out_and_grads(fn, args, cot):
    def loss(*a):
        o = fn(*a)
        return (o.astype(jnp.float32) * cot).sum(), o
    (_, o), grads = jax.value_and_grad(
        loss, argnums=(0, 1, 2, 3, 4), has_aux=True)(*args)
    return (o,) + grads


def both_forms(idx, dtype, f=F):
    idx = jnp.asarray(idx, jnp.int32)
    args, cot = inputs(idx, dtype, f)

    def loop(*a):
        return moe._experts_xla(*a, moe.dispatch_plan(idx, *HELD))

    def kernels(*a):
        return me.experts_pallas(*a, moe.dispatch_plan(idx, *HELD, a[1]),
                                 INTERPRET)

    return out_and_grads(loop, args, cot), out_and_grads(kernels, args, cot)


def worst(got, want):
    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    return float(jnp.abs(got - want).max() / (jnp.abs(want).max() + 1e-9))


@pytest.fixture(scope="module", params=ROUTINGS)
def float32_forms(request):
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(moe, "TILE", TILE)
        return request.param, both_forms(routing(request.param), jnp.float32)


@pytest.mark.parametrize("which", range(6), ids=OUTPUTS)
def test_float32_kernels_match_the_xla_loop(float32_forms, which):
    name, (want, got) = float32_forms
    assert got[which].shape == want[which].shape
    assert got[which].dtype == want[which].dtype
    assert worst(got[which], want[which]) < (2e-6 if which == 0 else 2e-5)
    if name == "none_held":
        assert not np.asarray(got[which]).any()


def test_plan_pads_every_run_to_whole_tiles_and_reaches_the_bound():
    """Every held expert owns at least one tile, real rows come first in a
    tile, the sorted weights are the pairs', and with every pair on one
    expert the trip count is the grid's bound less the tile an expert
    always has."""
    lo, hi = HELD
    idx = jnp.asarray(routing("all_on_one"), jnp.int32)
    top_p = jnp.arange(N, dtype=jnp.float32).reshape(N, 1) + 1.0
    plan = me.tile_plan(idx, top_p, lo, hi, TILE)
    n_tiles = plan["tile_expert"].shape[0]
    assert n_tiles == N // TILE + (hi - lo)
    assert int(plan["n_tiles"][0]) == n_tiles - 1
    assert plan["pair"].shape == (n_tiles * TILE,)
    valid = np.asarray(plan["tile_valid"])
    experts = np.asarray(plan["tile_expert"])
    assert list(experts[:n_tiles - 1]) == [0, 1] + [2] * (N // TILE) + [3]
    assert list(valid) == [0, 0] + [TILE] * (N // TILE) + [0, 0]
    first = 2 * TILE
    assert list(np.asarray(plan["pair"])[first:first + N]) == list(range(N))
    assert np.array_equal(np.asarray(plan["weight"])[first:first + N],
                          np.asarray(top_p[:, 0]))
    assert not np.asarray(plan["weight"])[:first].any()
    assert list(np.asarray(plan["counts"])) == [0, 0, N, 0]


@pytest.mark.parametrize("name", ["random", "token_in_two_tiles"])
def test_bfloat16_kernels_match_the_bfloat16_loop(name):
    want, got = both_forms(routing(name), jnp.bfloat16)
    for which in range(6):
        assert got[which].dtype == want[which].dtype
        assert worst(got[which], want[which]) < 1e-2, OUTPUTS[which]


def test_expert_width_in_two_blocks(monkeypatch):
    """An expert whose blocks do not fit the budget: the outer grid axis
    walks two blocks of its width, every tile once a block."""
    f = 256
    monkeypatch.setattr(me, "_BLOCK_BUDGET", 3 * D * 128 * (8 + 4 + 8))
    assert me._width_block(D, f, 4, grads=True) == 128
    assert me._width_block(D, f, 4, grads=False) == 256
    monkeypatch.setattr(me, "_BLOCK_BUDGET", 3 * D * 128 * 8)
    assert me._width_block(D, f, 4, grads=False) == 128
    want, got = both_forms(routing("random"), jnp.float32, f=f)
    for which in range(6):
        assert worst(got[which], want[which]) < (2e-6 if which == 0
                                                 else 2e-5), OUTPUTS[which]


def test_width_blocks_at_the_published_shapes():
    """Qwen3-Next's expert (2048 x 512) and LFM2's (2048 x 1536), bfloat16:
    whole in the forward; LFM2's gradient accumulators and blocks halve it
    in the backward."""
    assert me._width_block(2048, 512, 2, grads=False) == 512
    assert me._width_block(2048, 512, 2, grads=True) == 512
    assert me._width_block(2048, 1536, 2, grads=False) == 1536
    assert me._width_block(2048, 1536, 2, grads=True) == 768


def routes(fn):
    before = [reliability_metrics.get(r) for r in ROUTES]
    fn()
    return tuple(int(reliability_metrics.get(r) - b)
                 for r, b in zip(ROUTES, before))


@pytest.mark.parametrize("n,d,f,k,dtype,fits", [
    (16384, 2048, 512, 10, jnp.bfloat16, True),     # the hybrid cell
    (32768, 2048, 1536, 4, jnp.bfloat16, True),     # the LFM2 cell
    (64, 128, 128, 2, jnp.float32, True),
    (64, 1536, 128, 2, jnp.float32, True),
    (64, 1024, 128, 2, jnp.bfloat16, True),
    (64, 128, 128, 2, jnp.bfloat16, False),         # a bfloat16 row of one
    (64, 1536, 128, 2, jnp.bfloat16, False),        # packed row, or twelve
    (64, 96, 128, 2, jnp.float32, False),           # model width
    (64, 128, 64, 2, jnp.float32, False),           # expert width
    (64, 128, 128, 2, jnp.float16, False),          # dtype
    (100, 128, 128, 1, jnp.float32, False),         # pairs in whole tiles
    (65536, 128, 128, 4, jnp.float32, False),       # ids past SMEM
])
def test_shape_rule(n, d, f, k, dtype, fits):
    x = jax.ShapeDtypeStruct((n, d), dtype)
    w = jax.ShapeDtypeStruct((4, d, f), dtype)
    tile = 128 if n >= 16384 else TILE     # the cells run `moe.TILE`
    assert me.pallas_fits(x, w, k, tile) == fits


def test_layer_off_the_tpu_takes_the_loop_and_both_routes_are_counted():
    idx = jnp.asarray(routing("random"), jnp.int32)
    (x, top_p, wg, wu, wd), _ = inputs(idx, jnp.float32)
    rng = np.random.default_rng(1)
    p = {"router": jnp.asarray(rng.standard_normal((D, E_ALL)), jnp.float32),
         "w_gate": wg, "w_up": wu, "w_down": wd}
    assert me.pallas_fits(x, wg, 2, TILE)
    assert routes(lambda: moe.moe_layer(x, p, 2, HELD)) == (0, 1)
    # a plan of the kernels' kind sends `_experts` down them (traced only:
    # off the TPU nothing compiles them)
    assert routes(lambda: jax.eval_shape(
        lambda *a: moe._experts(*a, moe.dispatch_plan(idx, *HELD, a[1])),
        x, top_p, wg, wu, wd)) == (1, 0)
