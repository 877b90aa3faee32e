"""SAR on the serving fleet: device-built fit, ONE sharded scoring matmul.

`recommendation/sar.py` is the seed-era port — the affinity/similarity
build runs `np.add.at` on the host and recommend re-uploads the dense
matrices per call. This module grows the same semantics onto the fleet
stack (ROADMAP item 6):

- **Fit** (`SARServing`): affinity A (U x I) and the binary interaction
  matrix B come out of `jax.ops.segment_sum` over flattened (user, item)
  event keys; C = BᵀB, the support threshold and the jaccard/lift
  normalization all stay on device. Semantics (time decay, thresholds,
  normalizations) match the seed estimator.
- **Serving** (`SARServingModel.recommend_plan`): one sharded
  `A[users] @ S` matmul — S row-sharded over the item axis of the data
  mesh, each device contracting its item slice, `lax.psum` fan-in as the
  single declared all-reduce — followed by on-device `lax.top_k` per
  user row. The compiled executable is cached per (mesh, catalog, k) in
  an `AotCache`; `_serving_kernel` marks itself `row_ids` so `io/plan.py`
  buckets scalar user ids and answers `recommend?user=...` with
  `plan.recompiles` pinned 0.

Parity: the sharded top-k returns exactly the numpy `top_k(A @ S)` index
set per user (pinned in tier-1 on the 8-virtual-device CPU mesh); ties
inside a score level may order differently between backends, which is
the documented tie-order caveat. Unknown user ids answer items=-1 /
ratings=NaN (cold-start 'nan' convention of the seed `_transform`).
"""
from __future__ import annotations

import functools
import hashlib

import numpy as np

from ..core import Param
from ..core.params import in_range
from ..parallel import DATA_AXIS, data_mesh
from ..recommendation.sar import SAR, SARModel
from ..reliability.metrics import reliability_metrics
from ..telemetry import names as tnames
from .base import attach_workload_observability

# ratings below this are masked slots (padded catalog columns or
# remove_seen holes) — finite so JSON replies stay strict-parseable
_NEG = np.float32(-3.0e38)


def _stable_tag(*parts) -> str:
    return hashlib.sha1(repr(parts).encode()).hexdigest()[:10]


def _mesh_tag(mesh):
    return tuple(sorted((str(k), int(v)) for k, v in mesh.shape.items()))


@functools.lru_cache(maxsize=64)
def _compiled_recommend_fn(mesh, n_items_pad: int, k: int):
    """(A rows, S, penalty) -> (top-k items, top-k ratings), S row-sharded
    over the item axis: each device contracts its (I/p) item slice of the
    affinity columns against its S rows, ONE `lax.psum` folds the partial
    (n, I) products, and `lax.top_k` runs on the replicated sum. The
    penalty matrix rides in as data (already -inf-masked on the host), so
    no gather/all-to-all shows up — the psum is the whole collective
    story, which is what the `sar.score.sharded` contract pins."""
    import jax
    from jax.sharding import PartitionSpec as P

    from jax import shard_map
    from ..telemetry.perf import AotCache

    def fn(a, s, pen):
        part = a @ s                            # (n, I) partial product
        scores = jax.lax.psum(part, DATA_AXIS)  # the ONE all-reduce
        vals, idx = jax.lax.top_k(scores + pen, k)
        return idx, vals

    mapped = shard_map(fn, mesh=mesh,
                       in_specs=(P(None, DATA_AXIS), P(DATA_AXIS, None),
                                 P(None, None)),
                       out_specs=(P(None, None), P(None, None)),
                       check_vma=False)
    return AotCache(
        mapped, label="workloads.sar.recommend",
        fingerprint="workloads.sar.recommend#"
                    f"{_stable_tag(_mesh_tag(mesh), n_items_pad, k)}")


class SARServing(SAR):
    """SAR fit with device segment sums, producing the serving-integrated
    model. Seed Params plus the serving knobs (k, remove_seen) the
    compiled plan bakes in."""
    num_recommendations = Param(
        "num_recommendations", "k the compiled serving plan answers", 10,
        validator=in_range(1))
    remove_seen = Param(
        "remove_seen",
        "mask already-interacted items out of served recommendations",
        False)
    faults = Param(
        "faults", "reliability.faults.FaultInjector armed at the "
        "workloads.sar.refit site (chaos drills)", None, transient=True)

    def _fit(self, t) -> "SARServingModel":
        users = np.asarray(t[self.user_col], np.int64)
        items = np.asarray(t[self.item_col], np.int64)
        if users.min() < 0 or items.min() < 0:
            raise ValueError("SARServing expects non-negative integer "
                             "user/item ids (run RecommendationIndexer "
                             "first)")
        n_users = int(users.max()) + 1
        n_items = int(items.max()) + 1

        have_time = self.time_col is not None and self.time_col in t
        have_rating = self.rating_col is not None and self.rating_col in t
        weights = np.ones(len(t), np.float64)
        if have_rating:
            weights = np.asarray(t[self.rating_col], np.float64).copy()
        if have_time:
            ts = np.asarray(t[self.time_col], np.float64)
            ref = float(self.start_time) if self.start_time is not None \
                else float(ts.max())
            half_life_s = self.time_decay_coeff * 24.0 * 3600.0
            weights = weights * np.power(2.0, -(ref - ts) / half_life_s)

        import jax
        import jax.numpy as jnp
        # segment sums over flattened (user, item) keys replace the host
        # np.add.at scatter of the seed fit; B clips repeat events to the
        # distinct-user semantics of SAR.calculateItemItemSimilarity
        seg = jnp.asarray(users * n_items + items)
        affinity = np.asarray(jax.ops.segment_sum(
            jnp.asarray(weights, jnp.float32), seg,
            num_segments=n_users * n_items)).reshape(n_users, n_items)
        b = jnp.minimum(jax.ops.segment_sum(
            jnp.ones(len(users), jnp.float32), seg,
            num_segments=n_users * n_items), 1.0).reshape(n_users, n_items)
        cooc = b.T @ b
        occ = jnp.diagonal(cooc)
        sim = jnp.where(cooc >= self.support_threshold, cooc, 0.0)
        if self.similarity_function == "jaccard":
            denom = occ[:, None] + occ[None, :] - cooc
            sim = jnp.where(denom > 0, sim / jnp.maximum(denom, 1e-12), 0.0)
        elif self.similarity_function == "lift":
            denom = occ[:, None] * occ[None, :]
            sim = jnp.where(denom > 0, sim / jnp.maximum(denom, 1e-12), 0.0)

        if self.faults is not None:
            # chaos site: a refit that dies here must leave any serving
            # incumbent untouched (install only happens on a whole model)
            self.faults.perturb("workloads.sar.refit")

        m = SARServingModel(**{p: getattr(self, p) for p in (
            "user_col", "item_col", "rating_col", "similarity_function",
            "support_threshold", "num_recommendations", "remove_seen")})
        m._affinity = affinity
        m._similarity = np.asarray(sim, np.float32)
        reliability_metrics.set_gauge(tnames.WORKLOADS_SAR_CATALOG_ITEMS,
                                      float(n_items))
        # drift reference: the ids and scores this model actually serves
        # for a head slice of users — top-k overlap shift is the canary
        out = m.recommend_plan()(np.arange(min(n_users, 512)))
        attach_workload_observability(
            self, m,
            {"recommended_item": out[:, 0, :].ravel(),
             "recommended_score": out[:, 1, :].ravel()},
            categorical=("recommended_item",))
        return m


class SARServingModel(SARModel):
    """Seed model plus the compiled serving surface: the sharded
    `recommend_plan` and the `row_ids` serving kernel that answers
    `recommend?user=...` through the io/plan.py bucketed fast path."""
    num_recommendations = Param(
        "num_recommendations", "k the compiled serving plan answers", 10,
        validator=in_range(1))
    remove_seen = Param(
        "remove_seen",
        "mask already-interacted items out of served recommendations",
        False)

    def recommend_plan(self, num_items=None, remove_seen=None):
        """Prebuilt user-ids -> (n, 2, k) closure: row r answers user
        ids[r] with out[r, 0] = top-k item ids and out[r, 1] = their
        scores. The catalog axis is padded to a multiple of the mesh size
        once at build; per call the host gathers affinity rows + the
        penalty matrix (padded columns, and seen items when remove_seen)
        and the cached executable runs one psum matmul + top_k. Unknown
        ids (outside the fitted user range) answer items=-1/ratings=NaN
        and count `workloads.sar.unknown_users`."""
        k = int(self.num_recommendations if num_items is None else num_items)
        rm = bool(self.remove_seen if remove_seen is None else remove_seen)
        aff = np.asarray(self._affinity, np.float32)
        n_users, n_items = aff.shape
        k = min(k, n_items)
        mesh = data_mesh()
        n_shards = int(mesh.shape[DATA_AXIS])
        pad = (-n_items) % n_shards
        i_pad = n_items + pad
        aff_p = np.pad(aff, ((0, 0), (0, pad))) if pad else aff
        sim_p = (np.pad(np.asarray(self._similarity, np.float32),
                        ((0, pad), (0, pad)))
                 if pad else np.asarray(self._similarity, np.float32))
        import jax.numpy as jnp
        sim_dev = jnp.asarray(sim_p)
        col_pen = np.zeros(i_pad, np.float32)
        col_pen[n_items:] = _NEG
        fn = _compiled_recommend_fn(mesh, i_pad, k)

        def plan(ids: np.ndarray) -> np.ndarray:
            ids = np.asarray(ids, np.int64)
            known = (ids >= 0) & (ids < n_users)
            a = aff_p[np.where(known, ids, 0)]        # (n, I_pad) gather
            pen = np.broadcast_to(col_pen, a.shape)
            if rm:
                pen = np.where(a > 0, _NEG, pen)
            idx, vals = fn(jnp.asarray(a), sim_dev,
                           jnp.asarray(np.ascontiguousarray(pen)))
            out = np.empty((a.shape[0], 2, k), np.float64)
            out[:, 0, :] = np.asarray(idx)
            out[:, 1, :] = np.asarray(vals)
            out[~known, 0, :] = -1.0
            out[~known, 1, :] = np.nan
            n_unknown = int((~known).sum())
            if n_unknown:
                reliability_metrics.inc(tnames.WORKLOADS_SAR_UNKNOWN_USERS,
                                        n_unknown)
            return out

        return plan

    def _transform(self, t):
        """Users-only tables answer with the seed host recommend path
        (affinity re-upload + per-batch top_k) shaped like the compiled
        plan's (n, 2, k) output — the uncompiled fast_path=False serving
        baseline. Tables carrying the
        item column keep the seed (user, item) -> rating scoring."""
        if self.item_col in t:
            return super()._transform(t)
        ids = np.asarray(t[self.user_col], np.int64).ravel()
        k = min(int(self.num_recommendations),
                int(np.asarray(self._affinity).shape[1]))
        rec = self.recommend_for_user_subset(ids, k, bool(self.remove_seen))
        out = np.stack([np.asarray(rec["recommendations"], np.float64),
                        np.asarray(rec["ratings"], np.float64)], axis=1)
        return t.with_column("recommendations", out)

    def _serving_kernel(self, output_col: str):
        """Scalar-integer-id kernel for the io/plan.py fast path: marks
        itself `row_ids` so the plan buckets 1-d id batches (not feature
        matrices) and validates ids at assembly. Only the canonical
        'recommendations' output has a compiled plan."""
        if output_col != "recommendations":
            return None
        kernel = self.recommend_plan()
        kernel.row_ids = True
        kernel.rows_metric = tnames.WORKLOADS_SAR_RECOMMEND_ROWS
        return kernel


# --- graftsem contract ------------------------------------------------------
from ..analysis.semantic import Case, hot_path_contract  # noqa: E402


@hot_path_contract(
    "sar.score.sharded",
    expected_executables=1,
    donate_expected=(),
    # the psum fan-in of the (rows x I) partial products is the ONLY
    # collective: measured on the 8-way CPU mesh, x2 headroom. A gather
    # or all-to-all appearing here means the penalty-as-data design
    # regressed into resharding the catalog per request.
    collective_budget={"all-reduce": {"ops": 2, "bytes": 4_096}},
)
def sar_score_sharded_contract():
    import jax.numpy as jnp
    mesh = data_mesh()
    n_shards = int(mesh.shape[DATA_AXIS])
    rows, k = 8, 4
    i_pad = max(16, n_shards * 2)
    rng = np.random.default_rng(0)
    fn = _compiled_recommend_fn(mesh, i_pad, k).fn
    args = (jnp.asarray(rng.normal(size=(rows, i_pad)), jnp.float32),
            jnp.asarray(rng.normal(size=(i_pad, i_pad)), jnp.float32),
            jnp.zeros((rows, i_pad), jnp.float32))
    # same (mesh, catalog, k) twice: second lowering hits the first
    # executable — per-request recompiles would tank the serving p99
    return [Case("first-batch", fn, args),
            Case("next-batch", fn, args)]
