"""The GBDT hot op: per-(node, feature, bin) gradient/hessian/count histograms.

This is the TPU-native equivalent of LightGBM's C++ histogram construction
kernels (the work inside `LGBM_BoosterUpdateOneIter`, reference:
lightgbm/TrainUtils.scala:326-358 — SURVEY.md §2.9 item 1). Histogram build is
memory-bandwidth-shaped (scatter-add over binned features), not matmul-shaped;
the XLA path lowers to a single fused scatter-add via segment_sum over
composite keys. The Pallas TPU kernel family (histogram_pallas.py) keeps the
bins tile in VMEM and accumulates all three statistics in one pass; selection
is automatic by backend with an env escape hatch:

    MMLSPARK_TPU_HIST = auto | xla | pallas | planes

`planes` additionally makes fit_booster precompute the level-invariant lo
one-hot planes once per fit (build_hist_plan) and routes shallow levels
through the plane-streaming kernel — see the routing table and ledger at the
top of histogram_pallas.py. Every kernel-route selection is counted at trace
time (`gbdt.hist.route.<route>`), so a compile log shows which kernels a fit
actually instantiated.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from ..reliability.metrics import reliability_metrics
from ..telemetry import names as tnames
from .histogram_pallas import M_MAX, kernel_route, pallas_hist


def _xla_hist(bins, grad, hess, node_local, active, n_nodes: int, n_bins: int,
              count_w=None):
    """One fused scatter-add: key = ((node * F) + f) * B + bin.

    Inactive rows get an out-of-range segment id and are dropped by XLA's
    scatter OOB semantics — the moral equivalent of the reference's 'ignore'
    ring members for empty partitions (TrainUtils.scala:577-580).
    """
    n, f = bins.shape
    num_segments = n_nodes * f * n_bins
    feat_ids = jnp.arange(f, dtype=jnp.int32)[None, :]
    keys = (node_local[:, None] * f + feat_ids) * n_bins + bins.astype(jnp.int32)
    keys = jnp.where(active[:, None], keys, num_segments)  # OOB -> dropped
    keys = keys.reshape(-1)

    def seg(vals):
        out = jax.ops.segment_sum(vals.reshape(-1), keys,
                                  num_segments=num_segments)
        return out.reshape(n_nodes, f, n_bins)

    # count histogram: count_w is the bagging/padding indicator (1 = row is
    # present this iteration, 0 = bagged-out / GOSS-dropped / distributed
    # padding). LightGBM removes such rows from data counts; user sample
    # weights do NOT change counts, so this must be an indicator, not hess.
    cnt = (jnp.ones_like(hess) if count_w is None
           else count_w.astype(jnp.float32))
    hg = seg(jnp.broadcast_to(grad[:, None], (n, f)))
    hh = seg(jnp.broadcast_to(hess[:, None], (n, f)))
    hc = seg(jnp.broadcast_to(cnt[:, None], (n, f)))
    return hg, hh, hc


def node_feature_histograms(bins, grad, hess, node_local, active,
                            n_nodes: int, n_bins: int, count_w=None,
                            lo_planes=None, plane_lo: int = 0):
    """(n,F) uint8 bins + per-row grad/hess -> three (n_nodes, F, n_bins) f32
    histograms. Rows with active=False contribute nothing; rows with
    count_w=0 contribute to no statistic's count (see _xla_hist).

    `lo_planes`/`plane_lo`: per-fit precomputed level-invariant one-hot
    planes (histogram_pallas.build_hist_plan) — routes shallow levels
    through the plane-streaming kernel when present."""
    impl = os.environ.get("MMLSPARK_TPU_HIST", "auto")
    if impl in ("pallas", "planes") or (impl == "auto"
                                        and _should_use_pallas(n_nodes)):
        has_planes = lo_planes is not None and plane_lo > 0
        kind, _lo = kernel_route(n_nodes, n_bins, has_planes=has_planes)
        # trace-time routing record: one count per compiled (m, B) kernel
        # instantiation — the compile-log view of which route a fit took
        reliability_metrics.inc(tnames.gbdt_hist_route(kind))
        return pallas_hist(bins, grad, hess, node_local, active, n_nodes,
                           n_bins, count_w=count_w,
                           lo_planes=lo_planes if has_planes else None,
                           plane_lo=plane_lo if has_planes else 0,
                           # interpreter escape hatch: exercises the REAL
                           # routed-kernel plumbing on the CPU backend
                           # (tier-1 end-to-end planes test; debugging)
                           interpret=os.environ.get(
                               "MMLSPARK_TPU_HIST_INTERPRET") == "1")
    reliability_metrics.inc(tnames.gbdt_hist_route("xla"))
    return _xla_hist(bins, grad, hess, node_local, active, n_nodes, n_bins,
                     count_w=count_w)


def _should_use_pallas(n_nodes: int) -> bool:
    """Pallas matmul-histogram on TPU (the XLA scatter is serialized there);
    the node-onehot trick is VMEM-bounded, so levels past M_MAX nodes take
    the scatter (counted as `gbdt.hist.route.xla`, never silent)."""
    return n_nodes <= M_MAX and jax.default_backend() == "tpu"


# --------------------------------------------------- semantic contract
# Registered in analysis/semantic/registry.py: the histogram build at a
# canonical routed shape. On CPU this lowers the XLA scatter route (the
# Pallas routes need a TPU) — degraded but non-vacuous: identity,
# host-sync, and the zero-collective budget still bind the program the
# tier-1 backend actually compiles.
from ..analysis.semantic import Case, hot_path_contract  # noqa: E402


@hot_path_contract(
    "gbdt.hist.kernel",
    expected_executables=1,
    donate_expected=(),
    collective_budget={},        # node-local histograms: the psum lives
                                 # in the distributed tree contract
)
def gbdt_hist_route_contract():
    import functools as _ft

    import jax.numpy as jnp
    import numpy as _np

    fn = _ft.partial(node_feature_histograms, n_nodes=8, n_bins=16)
    rng = _np.random.default_rng(0)

    def args():
        return (jnp.asarray(rng.integers(0, 16, (256, 4)), jnp.uint8),
                jnp.asarray(rng.normal(size=256), jnp.float32),
                jnp.asarray(rng.uniform(0.1, 1.0, 256), jnp.float32),
                jnp.asarray(rng.integers(0, 8, 256), jnp.int32),
                jnp.ones(256, bool))
    return [Case("level-0", fn, args()), Case("level-1", fn, args())]
