"""Quantile feature binning: float features -> uint8 bin ids + bin upper bounds.

Role-equivalent to LightGBM's native BinMapper/Dataset construction, which the
reference reaches through per-value JNI streaming (lightgbm/TrainUtils.scala:33-186,
LightGBMUtils.scala:204-286 — `LGBM_DatasetCreateFromMats`). TPU-first design:
binning happens once on host over whole columns (vectorized numpy, no row loop),
producing a dense (n_rows, n_features) uint8 matrix that lives in HBM — 4-8x
smaller than f32 features, which is what makes histogram building HBM-friendly.

Bin semantics match LightGBM's: bin b holds values x <= upper_bound[b], the last
bin is +inf. NaN maps to the LAST bin of each feature (missing treated as
largest — LightGBM's default missing-value direction with `use_missing` and
`zero_as_missing=False`).
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import numpy as np

from ..telemetry import names as tnames


class BinMapper(NamedTuple):
    """Per-feature binning decided on (a sample of) the training data."""
    upper_bounds: np.ndarray   # (n_features, max_bin) f32; +inf padded
    n_bins: np.ndarray         # (n_features,) actual bin count used
    max_bin: int
    # bool (n_features,) — True columns hold integer category ids and are
    # binned by IDENTITY (bin = clip(floor(x), 0, max_bin)); None = all
    # numeric (old artifacts). Reference: categoricalSlotIndexes,
    # lightgbm/params/LightGBMParams.scala:184-196.
    categorical: Optional[np.ndarray] = None

    @property
    def n_features(self) -> int:
        return self.upper_bounds.shape[0]

    def _cat_mask(self) -> np.ndarray:
        if self.categorical is None:
            return np.zeros(self.n_features, bool)
        return self.categorical


def fit_bins(x: np.ndarray, max_bin: int = 255,
             sample_cnt: int = 200_000, seed: int = 2,
             categorical_features=()) -> BinMapper:
    """Choose at most max_bin quantile boundaries per feature.

    LightGBM samples `bin_construct_sample_cnt` (default 200000) rows to find
    boundaries; we do the same so 1B-row tables don't need a full pass.

    `categorical_features` columns are identity-binned: the value IS the
    category id, clipped to [0, max_bin] (index categories by frequency —
    featurize's ValueIndexer does — so rare tails share the overflow bin).
    NaN maps to the last bin, like the numeric missing-value direction.
    """
    n, f = x.shape
    if n > sample_cnt:
        rng = np.random.default_rng(seed)
        x = x[rng.choice(n, sample_cnt, replace=False)]
    ubs = np.full((f, max_bin), np.inf, dtype=np.float32)
    nbins = np.zeros(f, dtype=np.int32)
    cat_mask = np.zeros(f, dtype=bool)
    if len(categorical_features):
        cat_mask[np.asarray(categorical_features, int)] = True
    for j in range(f):
        if cat_mask[j]:
            # identity bins; boundaries at k + 0.5 keep even a cat-unaware
            # threshold consumer piecewise-consistent with the bin ids
            nbins[j] = max_bin + 1
            ubs[j] = np.arange(max_bin, dtype=np.float32) + 0.5
            continue
        col = x[:, j]
        col = col[~np.isnan(col)]
        uniq = np.unique(col)
        if uniq.size <= 1:
            nbins[j] = 1
            continue
        if uniq.size <= max_bin:
            # distinct-value bins: boundary = midpoint between neighbors
            bounds = (uniq[:-1] + uniq[1:]) / 2.0
        else:
            # max_bin+1 grid points -> max_bin-1 interior boundaries ->
            # a full max_bin bins (was off by one before)
            qs = np.linspace(0, 1, max_bin + 1)[1:-1]
            bounds = np.unique(np.quantile(col, qs))
        k = min(bounds.size, max_bin - 1)
        ubs[j, :k] = bounds[:k]
        ubs[j, k:] = np.inf
        nbins[j] = k + 1
    return BinMapper(upper_bounds=ubs, n_bins=nbins, max_bin=max_bin,
                     categorical=cat_mask if cat_mask.any() else None)


def apply_bins(mapper: BinMapper, x: np.ndarray) -> np.ndarray:
    """Vectorized bin assignment: (n_rows, n_features) -> uint8 bins.

    bin = searchsorted(upper_bounds, x, 'left'): value <= ub[b] lands in b.
    NaN lands in the last bin of each feature (treated as largest, matching
    LightGBM's default missing handling direction).
    """
    n, f = x.shape
    out = np.empty((n, f), dtype=np.uint8)
    for j in range(f):
        k = int(mapper.n_bins[j])
        b = np.searchsorted(mapper.upper_bounds[j, : max(k - 1, 0)], x[:, j],
                            side="left")
        b = np.where(np.isnan(x[:, j]), k - 1, b)
        out[:, j] = b.astype(np.uint8)
    return out


def bin_threshold_value(mapper: BinMapper, feature: int, bin_id: int) -> float:
    """Real-valued decision threshold for 'go left if bin <= bin_id'."""
    return float(mapper.upper_bounds[feature, bin_id])


_assign_bins_jit = None


def _get_assign_bins():
    """Module-level jitted assigner so repeated fits hit the jit cache
    (a per-call closure would retrace + recompile every training run)."""
    global _assign_bins_jit
    if _assign_bins_jit is None:
        import jax
        import jax.numpy as jnp

        @jax.jit
        def _assign(ub, nb, xd):
            def one_feature(ub_j, nb_j, col):
                b = jnp.searchsorted(ub_j, col, side="left")
                b = jnp.where(jnp.isnan(col), nb_j - 1, b)
                return jnp.minimum(b, nb_j - 1)
            with jax.named_scope(tnames.GBDT_BIN):
                out = jax.vmap(one_feature, in_axes=(0, 0, 1),
                               out_axes=1)(ub, nb, xd)
                return out.astype(jnp.uint8)

        _assign_bins_jit = _assign
    return _assign_bins_jit


def assign_bins_program_text(mapper: BinMapper, shape) -> str:
    """Optimized HLO of the device bin assignment for a float32 table of
    `shape`, for `telemetry.perf.register_program`: shapes only, nothing
    is placed on a device."""
    import jax
    import jax.numpy as jnp
    return _get_assign_bins().lower(
        jax.eval_shape(jnp.asarray, mapper.upper_bounds),
        jax.eval_shape(jnp.asarray, mapper.n_bins),
        jax.ShapeDtypeStruct(tuple(shape), jnp.float32)
    ).compile().as_text()


def apply_bins_device(mapper: BinMapper, x):
    """Device-side bin assignment: one jitted vmapped searchsorted instead of
    a host loop (the host path costs ~6s at 1M x 32; this is milliseconds on
    TPU and keeps the bins matrix on-device for training)."""
    import jax.numpy as jnp
    return _get_assign_bins()(jnp.asarray(mapper.upper_bounds),
                              jnp.asarray(mapper.n_bins),
                              jnp.asarray(x, jnp.float32))
