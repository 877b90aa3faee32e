"""Histogram op: XLA scatter path vs the Pallas kernel family (interpret
mode) — parity for EVERY route (direct / joint radix / precomputed planes),
padded key-span and padded-row edges, bagging count weights, and a pin of
the (m, B) routing table so a silent route change is a visible diff."""
import numpy as np
import jax.numpy as jnp
import pytest

from mmlspark_tpu.ops.histogram import _xla_hist
from mmlspark_tpu.ops import histogram_pallas as hp
from mmlspark_tpu.ops.histogram_pallas import (build_hist_plan, kernel_route,
                                               pallas_hist, plan_lo_bins)


def _data(n, f, m, b, seed=None, count_w=False):
    rng = np.random.default_rng(n if seed is None else seed)
    bins = jnp.asarray(rng.integers(0, b, size=(n, f)).astype(np.uint8))
    grad = jnp.asarray(rng.normal(size=n).astype(np.float32))
    hess = jnp.asarray(rng.uniform(0.1, 1, size=n).astype(np.float32))
    node = jnp.asarray(rng.integers(-1, m, size=n).astype(np.int32))
    cw = (jnp.asarray(rng.integers(0, 2, size=n).astype(np.float32))
          if count_w else None)
    return bins, grad, hess, node, node >= 0, cw


def _assert_parity(a, p, tag=""):
    for name, x, y in zip(["grad", "hess", "count"], a, p):
        # bf16 one-hot path: stat sums carry ~0.4% input-rounding noise
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=6e-3,
                                   atol=5e-2, err_msg=f"{tag}{name}")


@pytest.mark.parametrize("n,f,m,b", [(5000, 7, 4, 256), (3000, 16, 1, 64),
                                     (2048, 8, 32, 256), (100, 3, 2, 64),
                                     # joint-key radix routes, incl.
                                     # non-power-of-two bin counts (255)
                                     # whose key span pads up to the LO
                                     # multiple
                                     (4000, 5, 8, 256), (3000, 6, 16, 255),
                                     (2500, 4, 2, 128), (2000, 3, 4, 255),
                                     # round-6 B=64 joint rows (LO 16/32)
                                     # + a 64<=B<128 non-pow2 key span
                                     (3000, 5, 2, 64), (2500, 6, 4, 64),
                                     (2000, 4, 2, 100), (1500, 3, 4, 96)])
def test_pallas_matches_xla(n, f, m, b):
    bins, grad, hess, node, active, _ = _data(n, f, m, b)
    a = _xla_hist(bins, grad, hess, node, active, m, b)
    p = pallas_hist(bins, grad, hess, node, active, m, b, interpret=True)
    _assert_parity(a, p)


@pytest.mark.parametrize("route", [("direct", 64), ("joint", 16),
                                   ("joint", 32), ("joint", 64)])
def test_every_route_matches_xla_with_count_w(route):
    """Explicit route overrides: every kernel the family can express must
    agree with the scatter path on the SAME inputs, including bagging
    count weights (count_w=0 rows keep grad/hess but drop from counts)."""
    n, f, m, b = 3000, 5, 4, 64
    bins, grad, hess, node, active, cw = _data(n, f, m, b, count_w=True)
    a = _xla_hist(bins, grad, hess, node, active, m, b, count_w=cw)
    p = pallas_hist(bins, grad, hess, node, active, m, b, count_w=cw,
                    route=route, interpret=True)
    _assert_parity(a, p, tag=f"{route} ")


# (1500, 3, 4, 128) went with the LO=64 plane route (plan_lo_bins)
@pytest.mark.parametrize("n,f,m,b", [(3000, 5, 1, 64), (2500, 4, 2, 64),
                                     (2000, 6, 4, 64), (900, 3, 2, 96)])
def test_planes_route_matches_xla(n, f, m, b):
    """Precomputed level-invariant plane route: build_hist_plan once, then
    parity against the scatter path — incl. the padded-row edge (n is
    never a PLANES_TILE_ROWS multiple here) and bagging weights."""
    bins, grad, hess, node, active, cw = _data(n, f, m, b, count_w=True)
    lo = plan_lo_bins(b)
    assert lo > 0
    planes = build_hist_plan(bins, b)
    assert planes.dtype == jnp.int8
    assert planes.shape[1] == lo
    a = _xla_hist(bins, grad, hess, node, active, m, b, count_w=cw)
    p = pallas_hist(bins, grad, hess, node, active, m, b, count_w=cw,
                    lo_planes=planes, plane_lo=lo, interpret=True)
    _assert_parity(a, p, tag="planes ")
    # the auto-router must actually take the planes route when a plan
    # rides along (m <= PLANES_M_MAX)
    assert kernel_route(m, b, has_planes=True)[0] == "planes"


def test_planes_plan_shape_mismatch_raises():
    """A plan built from DIFFERENT bins (other row count) must fail loudly,
    not silently histogram the wrong data."""
    bins, grad, hess, node, active, _ = _data(2000, 4, 2, 64)
    other_bins = _data(6000, 4, 2, 64)[0]
    planes = build_hist_plan(other_bins, 64)
    with pytest.raises(ValueError, match="plan"):
        pallas_hist(bins, grad, hess, node, active, 2, 64,
                    lo_planes=planes, plane_lo=16, interpret=True)


def test_inactive_rows_dropped():
    n, f, m, b = 1000, 4, 2, 64
    rng = np.random.default_rng(0)
    bins = jnp.asarray(rng.integers(0, b, size=(n, f)).astype(np.uint8))
    grad = jnp.asarray(np.ones(n, np.float32))
    hess = jnp.asarray(np.ones(n, np.float32))
    node = jnp.asarray(np.full(n, -1, np.int32))  # nothing active
    out = pallas_hist(bins, grad, hess, node, node >= 0, m, b, interpret=True)
    for arr in out:
        assert float(np.abs(np.asarray(arr)).max()) == 0.0
    # same for the planes kernel (inactive rows drop via the hi digit even
    # though their lo plane rows are populated)
    planes = build_hist_plan(bins, b)
    out = pallas_hist(bins, grad, hess, node, node >= 0, m, b,
                      lo_planes=planes, plane_lo=plan_lo_bins(b),
                      interpret=True)
    for arr in out:
        assert float(np.abs(np.asarray(arr)).max()) == 0.0


def test_fit_booster_planes_end_to_end(monkeypatch):
    """MMLSPARK_TPU_HIST=planes through the REAL fit path (plan built once
    per fit, hoisted through the fused scan, planes kernel in interpret
    mode on CPU): scores must match the default XLA-scatter fit — at this
    tiny shape no gain tie sits inside the bf16 rounding band, so trees
    come out identical. Also pins the route counters and the plan gauge."""
    from mmlspark_tpu.models.gbdt.boosting import BoostParams, fit_booster
    from mmlspark_tpu.reliability.metrics import reliability_metrics

    rng = np.random.default_rng(0)
    n, f = 600, 5
    x = rng.normal(size=(n, f)).astype(np.float32)
    y = (x[:, 0] + x[:, 1] > 0).astype(np.float32)
    p = {"objective": "binary", "num_iterations": 2, "max_depth": 3,
         "max_bin": 63, "min_data_in_leaf": 5, "num_leaves": 8}
    ref, base_ref, _ = fit_booster(x, y, BoostParams(**p))

    reliability_metrics.reset("gbdt.hist.")
    monkeypatch.setenv("MMLSPARK_TPU_HIST", "planes")
    monkeypatch.setenv("MMLSPARK_TPU_HIST_INTERPRET", "1")
    got, base, _ = fit_booster(x, y, BoostParams(**p))
    monkeypatch.delenv("MMLSPARK_TPU_HIST")
    monkeypatch.delenv("MMLSPARK_TPU_HIST_INTERPRET")

    assert base == base_ref and got.n_trees == ref.n_trees
    np.testing.assert_allclose(got.raw_score(x), ref.raw_score(x),
                               rtol=2e-2, atol=2e-2)
    snap = reliability_metrics.snapshot()
    # depth 3 + sibling subtraction: levels m = 1, 1, 2 — all within
    # PLANES_M_MAX, so every level routed through the planes kernel
    assert snap.get("gbdt.hist.route.planes", 0) == 3, snap
    assert snap.get("gbdt.hist.plan.bytes", 0) > 0, snap


# ------------------------------------------------------------ routing table
def test_kernel_route_table_pinned():
    """THE routing table (histogram_pallas docstring) as executable pins:
    a route change must show up as a diff here, not silently in perf."""
    expect = {
        # B = 64 (round-6 analytic rows, not timed since: ROADMAP D3)
        (1, 64): ("joint", 16), (2, 64): ("joint", 16),
        (4, 64): ("joint", 32), (8, 64): ("direct", 64),
        (16, 64): ("direct", 64), (32, 64): ("direct", 64),
        # 64 <= B < 128 shares the B=64 rows
        (2, 96): ("joint", 16), (4, 100): ("joint", 32),
        (8, 100): ("direct", 100),
        # B >= 128 (measured rounds 4-5)
        (1, 128): ("joint", 64), (4, 256): ("joint", 64),
        (8, 256): ("joint", 128), (16, 255): ("joint", 128),
        (32, 256): ("direct", 256),
        # below the radix family: direct
        (1, 32): ("direct", 32), (8, 63): ("direct", 63),
    }
    got = {k: kernel_route(*k) for k in expect}
    assert got == expect


def test_kernel_route_planes():
    # planes route only with a plan, only at shallow m, only when LO | B
    assert kernel_route(1, 64, has_planes=True) == ("planes", 16)
    assert kernel_route(4, 96, has_planes=True) == ("planes", 16)
    # B >= 128: the LO=64 plane block does not fit v5e's scoped VMEM
    assert kernel_route(4, 256, has_planes=True) == ("joint", 64)
    assert kernel_route(8, 64, has_planes=True) == ("direct", 64)
    assert kernel_route(4, 255, has_planes=True) == ("joint", 64)
    assert kernel_route(16, 256, has_planes=True) == ("joint", 128)


def test_plan_lo_bins_pinned():
    assert plan_lo_bins(64) == 16
    assert plan_lo_bins(96) == 16
    assert plan_lo_bins(128) == 0    # LO=64 planes exceed scoped VMEM
    assert plan_lo_bins(256) == 0
    assert plan_lo_bins(255) == 0    # no LO divides 255: route unavailable
    assert plan_lo_bins(63) == 0     # below the radix family
    assert hp.PLANES_M_MAX == 4
