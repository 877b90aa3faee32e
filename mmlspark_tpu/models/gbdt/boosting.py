"""Boosting loop: gbdt / rf / dart / goss over the jitted tree grower.

Role-equivalent to the reference's trainCore iteration loop
(lightgbm/TrainUtils.scala:360-427): per-iteration booster update, eval-metric
fetch, early stopping on round tolerance, and the boosting-mode variants the
reference exposes via `boosting` (lightgbm/params/LightGBMParams.scala dart/
goss params). The loop is host Python over iterations (like the reference's),
but each iteration is one XLA program over whole columns — there is no per-row
anything.

Supports a `callbacks` delegate with before/after-iteration hooks and dynamic
learning rate, mirroring LightGBMDelegate (lightgbm/LightGBMDelegate.scala).
"""
from __future__ import annotations

import dataclasses
import functools
import os
import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ...ops import binning
from ...reliability.metrics import reliability_metrics
from ...telemetry.spans import get_tracer
from ...telemetry import names as tnames
from ...telemetry.perf import register_program
from ...utils import tracing
from . import objectives as obj_mod
from . import trainer
from .booster import Booster


@dataclasses.dataclass(frozen=True)
class BoostParams:
    objective: str = "binary"
    boosting: str = "gbdt"            # gbdt | rf | dart | goss
    num_iterations: int = 100
    learning_rate: float = 0.1
    num_leaves: int = 31
    max_depth: int = 5
    max_bin: int = 255
    lambda_l1: float = 0.0
    lambda_l2: float = 0.0
    min_gain_to_split: float = 0.0
    min_data_in_leaf: int = 20
    min_sum_hessian_in_leaf: float = 1e-3
    feature_fraction: float = 1.0
    bagging_fraction: float = 1.0
    bagging_freq: int = 0
    # goss
    top_rate: float = 0.2
    other_rate: float = 0.1
    # dart
    drop_rate: float = 0.1
    max_drop: int = 50
    skip_drop: float = 0.5
    uniform_drop: bool = False
    xgboost_dart_mode: bool = False
    # objective extras
    alpha: float = 0.9                # huber delta / quantile level
    tweedie_variance_power: float = 1.5
    # native categorical splits (reference: categoricalSlotIndexes,
    # lightgbm/params/LightGBMParams.scala:184-196): these features hold
    # integer category ids; binning is identity and split search orders
    # categories by gradient statistic per node (see trainer.TreeConfig)
    categorical_features: tuple = ()
    cat_smooth: float = 10.0
    cat_l2: float = 10.0
    max_cat_threshold: int = 32
    # multiclass / ranking
    num_class: int = 1
    sigmoid: float = 1.0
    max_position: int = 0             # lambdarank NDCG truncation (0 = off)
    # user-supplied objective: (margin, y) -> (grad, hess)
    # (reference: FObjTrait.getGradient, lightgbm/params/FObjTrait.scala:17);
    # forces the host boosting loop so arbitrary numpy/jax callables work
    fobj: Optional[Callable] = None
    # rf continuation: total ensemble size for 1/T averaging weights when a
    # resumed fit trains only the remaining trees (0 = num_iterations)
    rf_total: int = 0
    # control
    seed: int = 0
    early_stopping_round: int = 0
    metric: Optional[str] = None
    boost_from_average: bool = True
    verbosity: int = -1


@dataclasses.dataclass
class Callbacks:
    """Delegate hooks (reference: lightgbm/LightGBMDelegate.scala)."""
    before_iteration: Optional[Callable[[int], None]] = None
    after_iteration: Optional[Callable[[int, float], None]] = None
    get_learning_rate: Optional[Callable[[int], float]] = None


def _eval_metric(name, objective, margin, y, num_class):
    m = np.asarray(margin)
    y = np.asarray(y)
    if name is None:
        name = {"binary": "binary_logloss", "multiclass": "multi_logloss",
                "lambdarank": "l2"}.get(objective, "l2")
    if name == "auc":
        p = 1 / (1 + np.exp(-m))
        order = np.argsort(p, kind="stable")
        ranks = np.empty_like(order, dtype=np.float64)
        ranks[order] = np.arange(1, len(p) + 1)
        npos, nneg = y.sum(), (1 - y).sum()
        if npos == 0 or nneg == 0:
            return 0.5, True
        auc = (ranks[y == 1].sum() - npos * (npos + 1) / 2) / (npos * nneg)
        return float(auc), True
    if name == "binary_logloss":
        p = np.clip(1 / (1 + np.exp(-m)), 1e-15, 1 - 1e-15)
        return float(-(y * np.log(p) + (1 - y) * np.log(1 - p)).mean()), False
    if name == "multi_logloss":
        e = np.exp(m - m.max(axis=1, keepdims=True))
        p = np.clip(e / e.sum(axis=1, keepdims=True), 1e-15, None)
        return float(-np.log(p[np.arange(len(y)), y.astype(int)]).mean()), False
    # default l2
    return float(((m.squeeze() - y) ** 2).mean()), False


# objectives whose leaf outputs are refit host-side (median/quantile renewal)
RENEWAL_OBJECTIVES = ("regression_l1", "quantile", "huber")


def _grad_hess(p: BoostParams, margin, y_j, y_onehot, g_idx):
    if p.fobj is not None:
        grad, hess = p.fobj(margin, y_j)
        return jnp.asarray(grad, jnp.float32), jnp.asarray(hess, jnp.float32)
    if p.objective == "multiclass":
        return obj_mod.multiclass_grad_hess(margin, y_onehot)
    if p.objective == "binary":
        return obj_mod.binary_grad_hess(margin, y_j, p.sigmoid)
    if p.objective == "lambdarank":
        return obj_mod.lambdarank_grad_hess(margin, y_j, g_idx, sigmoid=p.sigmoid,
                                            max_position=p.max_position)
    if p.objective in ("huber", "quantile"):
        return obj_mod.OBJECTIVES[p.objective](margin, y_j, p.alpha)
    if p.objective == "tweedie":
        return obj_mod.tweedie_grad_hess(margin, y_j, p.tweedie_variance_power)
    return obj_mod.OBJECTIVES[p.objective](margin, y_j)


def _presence(pres_j, row_w):
    """min_data_in_leaf count indicator (None when every row counts — lets
    the histogram op skip the column). pres_j marks physically-present rows
    (0 = distributed padding); row_w is the bagging/GOSS mask. User sample
    weights deliberately do NOT change counts (LightGBM semantics — see
    histogram._xla_hist)."""
    present = None
    if pres_j is not None:
        present = (pres_j != 0)
    if row_w is not None:
        rw = row_w != 0
        present = rw if present is None else (present & rw)
    return None if present is None else present.astype(jnp.float32)


def _row_weights(p: BoostParams, grad, key, it_offset, multiclass):
    """Per-iteration GOSS / bagging row weights (None = keep all)."""
    n = grad.shape[0]
    if p.boosting == "goss":
        g_abs = jnp.abs(grad).sum(-1) if multiclass else jnp.abs(grad)
        n_top = max(int(p.top_rate * n), 1)
        thresh = jnp.sort(g_abs)[-n_top]
        is_top = g_abs >= thresh
        rnd = jax.random.uniform(key, (n,))
        keep_other = (~is_top) & (rnd < p.other_rate / max(1 - p.top_rate, 1e-9))
        amp = (1.0 - p.top_rate) / max(p.other_rate, 1e-9)
        return jnp.where(is_top, 1.0, jnp.where(keep_other, amp, 0.0))
    rf = p.boosting == "rf"
    if p.bagging_fraction < 1.0 and (rf or p.bagging_freq > 0):
        w = (jax.random.uniform(key, (n,)) < p.bagging_fraction).astype(jnp.float32)
        if rf or p.bagging_freq == 1:
            return w
        do_bag = (it_offset % p.bagging_freq) == 0  # traced under scan
        return jnp.where(do_bag, w, jnp.ones(n, jnp.float32))
    return None


def _feature_mask(p: BoostParams, key, n_features):
    if p.feature_fraction < 1.0:
        kf = max(1, int(round(p.feature_fraction * n_features)))
        perm = jax.random.permutation(key, n_features)
        return jnp.zeros(n_features, bool).at[perm[:kf]].set(True)
    return jnp.ones(n_features, bool)


def _device_metric(name, objective, margin, y, num_class):
    """(metric_value, larger_is_better) — computed in-graph so eval never
    forces a host round-trip inside the fused loop."""
    if name is None:
        name = {"binary": "binary_logloss", "multiclass": "multi_logloss",
                "lambdarank": "l2"}.get(objective, "l2")
    larger = name == "auc"
    if name == "auc":
        order = jnp.argsort(margin)
        ranks = jnp.zeros_like(margin).at[order].set(
            jnp.arange(1, margin.shape[0] + 1, dtype=margin.dtype))
        npos = y.sum()
        nneg = y.shape[0] - npos
        val = (jnp.sum(jnp.where(y == 1, ranks, 0.0)) - npos * (npos + 1) / 2) \
            / jnp.maximum(npos * nneg, 1.0)
    elif name == "binary_logloss":
        pr = jnp.clip(jax.nn.sigmoid(margin), 1e-15, 1 - 1e-15)
        val = -(y * jnp.log(pr) + (1 - y) * jnp.log(1 - pr)).mean()
    elif name == "multi_logloss":
        logp = jax.nn.log_softmax(margin, axis=-1)
        val = -jnp.take_along_axis(logp, y.astype(jnp.int32)[:, None],
                                   axis=1).mean()
    else:
        m = margin if margin.ndim == 1 else margin[:, 0]
        val = ((m - y) ** 2).mean()
    return val, larger


@functools.partial(
    jax.jit,
    static_argnames=("p", "cfg", "chunk_len", "k_out", "axis_name",
                     "has_valid", "voting_top_k", "plane_lo"))
def _boost_chunk(d_bins, y_j, w_j, pres_j, margin, init_margin, v_bins, vy,
                 v_margin, key, it_base, p: BoostParams, cfg, chunk_len: int,
                 k_out: int, axis_name=None, has_valid: bool = False,
                 voting_top_k=None, lo_planes=None, plane_lo: int = 0):
    """One fused chunk of boosting iterations: a lax.scan with NO host
    round-trips — the design that actually fits the TPU (the reference's
    per-iteration JNI hot loop, TrainUtils.scala:360-427, becomes one XLA
    program; the ~100ms/dispatch host<->device latency is paid once per
    chunk instead of once per tree)."""
    multiclass = p.objective == "multiclass"
    y_onehot = (jax.nn.one_hot(y_j.astype(jnp.int32), p.num_class,
                               dtype=jnp.float32) if multiclass else None)
    rf = p.boosting == "rf"

    def one_iter(carry, inp):
        margin, v_margin = carry
        it, key_it = inp
        k_bag, k_feat = jax.random.split(key_it)
        if axis_name:  # decorrelate per-shard sampling
            k_bag = jax.random.fold_in(k_bag, jax.lax.axis_index(axis_name))
        # rf trees are independent: gradients always at the initial margin
        g_margin = init_margin if rf else margin
        with jax.named_scope(tnames.GBDT_OBJECTIVE):
            grad, hess = _grad_hess(p, g_margin, y_j, y_onehot, None)
            if w_j is not None:
                grad = grad * (w_j[:, None] if multiclass else w_j)
                hess = hess * (w_j[:, None] if multiclass else w_j)
            row_w = _row_weights(p, grad, k_bag, it, multiclass)
            if row_w is not None:
                grad = grad * (row_w[:, None] if multiclass else row_w)
                hess = hess * (row_w[:, None] if multiclass else row_w)
            # presence indicator for min_data_in_leaf: bagged-out + padding
            # rows are absent; genuine rows count 1 regardless of sample
            # weight
            count_w = _presence(pres_j, row_w)
        fmask = _feature_mask(p, k_feat, cfg.n_features)

        sfs, sbs, lvs, gns, cvs, ics, cws = [], [], [], [], [], [], []
        for k in range(k_out):
            gk = grad[:, k] if multiclass else grad
            hk = hess[:, k] if multiclass else hess
            tree, delta = trainer.train_one_tree(d_bins, gk, hk, fmask, cfg,
                                                 axis_name=axis_name,
                                                 voting_top_k=voting_top_k,
                                                 count_w=count_w,
                                                 lo_planes=lo_planes,
                                                 plane_lo=plane_lo)
            sfs.append(tree.split_feature)
            sbs.append(tree.split_bin)
            lvs.append(tree.leaf_value)
            gns.append(tree.gain)
            cvs.append(tree.cover)
            ics.append(tree.split_is_cat)
            cws.append(tree.cat_words)
            with jax.named_scope(tnames.GBDT_OBJECTIVE):
                if multiclass:
                    margin = margin.at[:, k].add(delta)
                else:
                    margin = margin + delta
            if has_valid:
                vd = trainer.predict_binned(v_bins, tree.split_feature,
                                            tree.split_bin, tree.leaf_value,
                                            cfg.max_depth,
                                            split_is_cat=tree.split_is_cat,
                                            cat_words=tree.cat_words)
                if multiclass:
                    v_margin = v_margin.at[:, k].add(vd)
                else:
                    v_margin = v_margin + vd
        if has_valid:
            metric, _ = _device_metric(p.metric, p.objective, v_margin, vy,
                                       p.num_class)
        else:
            metric = jnp.float32(0.0)
        out = (jnp.stack(sfs), jnp.stack(sbs), jnp.stack(lvs),
               jnp.stack(gns), jnp.stack(cvs), jnp.stack(ics),
               jnp.stack(cws), metric)
        return (margin, v_margin), out

    its = it_base + jnp.arange(chunk_len)
    keys = jax.random.split(key, chunk_len)
    (margin, v_margin), (sf, sb, lv, gn, cv, ic, cw, metrics) = jax.lax.scan(
        one_iter, (margin, v_margin), (its, keys))
    # (chunk, K, max_nodes) -> (chunk*K, max_nodes), class-major per iteration
    sf = sf.reshape(-1, sf.shape[-1])
    sb = sb.reshape(-1, sb.shape[-1])
    lv = lv.reshape(-1, lv.shape[-1])
    gn = gn.reshape(-1, gn.shape[-1])
    cv = cv.reshape(-1, cv.shape[-1])
    ic = ic.reshape(-1, ic.shape[-1])
    # explicit leading dim: reshape(-1) on a zero-width cat_words (no
    # categorical features) would divide by zero
    cw = cw.reshape(cw.shape[0] * cw.shape[1], cw.shape[2], cw.shape[3])
    return margin, v_margin, sf, sb, lv, gn, cv, ic, cw, metrics


def _chunk_program_text(args, kwargs):
    """A thunk for `telemetry.perf.register_program`: the optimized HLO of
    `_boost_chunk` for the shapes (not the arrays) of one call, lowered
    and compiled again only when a reader of captures asks."""
    def abstract(a):
        return (jax.ShapeDtypeStruct(a.shape, a.dtype,
                                     sharding=getattr(a, "sharding", None))
                if isinstance(a, (jax.Array, np.ndarray)) else a)
    args, kwargs = jax.tree_util.tree_map(abstract, (args, kwargs))
    return lambda: _boost_chunk.lower(*args, **kwargs).compile().as_text()


def _fetch_packed(parts):
    """One D2H round-trip for all chunk outputs: concat each of the seven
    tree-array stacks across chunks on device, bitcast the integer ones to
    f32, flatten everything into ONE 1-D device array and fetch it whole.
    Per-array fetches each pay a full transfer round-trip, which dominates
    wall time on high-latency device links."""
    cat = [parts[0][i] if len(parts) == 1
           else jnp.concatenate([p[i] for p in parts]) for i in range(7)]
    sf, sb, lv, gn, cv, ic, cw = cat
    planes = [
        jax.lax.bitcast_convert_type(sf.astype(jnp.int32), jnp.float32),
        jax.lax.bitcast_convert_type(sb.astype(jnp.int32), jnp.float32),
        lv.astype(jnp.float32), gn.astype(jnp.float32),
        cv.astype(jnp.float32), ic.astype(jnp.float32),
        jax.lax.bitcast_convert_type(cw.astype(jnp.int32), jnp.float32),
    ]
    shapes = [p_.shape for p_ in planes]
    flat = jnp.concatenate([p_.reshape(-1) for p_ in planes])
    host = np.asarray(flat)
    out, off = [], 0
    for s in shapes:
        size = int(np.prod(s)) if s else 1
        out.append(host[off:off + size].reshape(s))
        off += size
    return (out[0].view(np.int32), out[1].view(np.int32), out[2], out[3],
            out[4], out[5] > 0.5, out[6].view(np.int32))


def _build_booster(sf, sb, lv, tree_classes, mapper, p: BoostParams,
                   k_out: int, n_features: int, best_iter: int,
                   init_booster, base, gain=None, cover=None,
                   is_cat=None, cat_words=None):
    """Stacked tree arrays -> Booster with real-valued thresholds.

    Categorical split nodes keep threshold 0 — they route by the packed
    membership words, not a value compare (raw inputs are category ids)."""
    thr = mapper.upper_bounds[np.clip(sf, 0, n_features - 1),
                              np.clip(sb, 0, p.max_bin - 1)]
    thr = np.where(sf >= 0, thr, 0.0).astype(np.float32)
    has_cat = (is_cat is not None and cat_words is not None
               and cat_words.size and is_cat.any())
    if has_cat:
        thr = np.where(is_cat, 0.0, thr).astype(np.float32)
    booster = Booster(split_feature=sf.astype(np.int32), threshold=thr,
                      split_bin=sb.astype(np.int32),
                      leaf_value=lv.astype(np.float32),
                      tree_class=np.asarray(tree_classes, np.int32),
                      max_depth=p.max_depth, n_classes=k_out,
                      objective=p.objective, n_features=n_features,
                      best_iteration=best_iter,
                      gain=None if gain is None else gain.astype(np.float32),
                      cover=None if cover is None else cover.astype(np.float32),
                      split_is_cat=(is_cat.astype(bool) if has_cat else None),
                      cat_words=(cat_words.astype(np.int32) if has_cat
                                 else None))
    if init_booster is not None:
        booster = init_booster.merge(booster)
    return booster


def fit_booster(x: np.ndarray, y: np.ndarray, params: BoostParams,
                *args, **kwargs):
    """Train a Booster on host arrays (see `_fit_booster_impl` for the full
    parameter list — this wrapper owns only the telemetry lifecycle).

    The `gbdt.fit` span wraps the WHOLE fit so a fit that dies (injected
    fault, bad params, device OOM) still lands in the span log with its
    error — per-iteration/per-chunk children attach through the activated
    context inside."""
    if isinstance(x, str):
        # out-of-core source: an .npy path memory-maps here so nothing
        # below this line ever holds the raw matrix host-resident
        x = np.load(x, mmap_mode="r")
    _tel = get_tracer()
    span = _tel.start_span(tnames.GBDT_FIT_SPAN, attrs={
        "rows": int(x.shape[0]), "features": int(x.shape[1]),
        "iterations": int(params.num_iterations),
        "objective": params.objective, "boosting": params.boosting})
    if span is None:
        return _fit_booster_impl(x, y, params, *args, **kwargs)
    try:
        with _tel.use(span):
            out = _fit_booster_impl(x, y, params, *args, **kwargs)
    except BaseException as e:
        span.finish(error=type(e).__name__)
        raise
    span.finish(trees=int(out[0].n_trees))
    return out


def _fit_booster_impl(x: np.ndarray, y: np.ndarray,
                      params: BoostParams,
                      weights: Optional[np.ndarray] = None,
                      init_scores: Optional[np.ndarray] = None,
                      group: Optional[np.ndarray] = None,
                      valid: Optional[tuple] = None,
                      init_booster: Optional[Booster] = None,
                      callbacks: Optional[Callbacks] = None,
                      tree_fn=None, put_fn=None, chunk_fn=None,
                      prebinned: Optional[tuple] = None,
                      presence: Optional[np.ndarray] = None,
                      checkpoint_fn=None, checkpoint_interval: int = 25,
                      init_base: float = 0.0, ingest=None, oocore=None,
                      init_margin: Optional[np.ndarray] = None,
                      init_rng_key: Optional[np.ndarray] = None,
                      iter_offset: int = 0, step_clock=None):
    """Train a Booster on host arrays. Single-device by default; the
    distributed path (distributed.py) passes a shard_map-wrapped `tree_fn`
    and a sharding `put_fn`, and this same loop runs over the mesh.

    `ingest` (a data.IngestOptions) routes the bin-matrix build through the
    parallel host pipeline: chunked multi-worker apply_bins overlapped with
    per-chunk device_put (data.stage_binned) instead of the serial
    whole-matrix staging — the Spark-partitioned-ingest analog. Output is
    bit-identical to the sequential path (tests/test_data_pipeline.py).
    `oocore` (a data.OocoreOptions) takes precedence and streams chunked
    binning under a bounded residency budget with a durable mid-dataset
    resume cursor — the out-of-core path for sources larger than host RAM
    (`x` may be an .npy path; docs/gbdt.md "Out-of-core training").

    Padded rows (distributed ragged handling) carry weight 0 and therefore
    contribute nothing to histograms, leaf values, or the init score.

    Deterministic crash-resume (the supervisor contract, docs/reliability.md):
    `checkpoint_fn(it, booster, base, final=, margin=, rng_key=)` receives
    the LIVE training margin and the current PRNG key at each checkpoint;
    a resumed fit passing them back as `init_margin`/`init_rng_key` (plus
    `iter_offset` = completed iterations, so bagging phase lines up)
    replays the remaining iterations on bit-identical state — the float
    re-association of recomputing margins via `init_booster.raw_score`
    would otherwise cost exact resume. Caveat: validation-metric state
    (best_metric/patience, the incremental v_margin) is NOT checkpointed —
    a run killed before an early stop triggers may resume to a different
    stopping iteration (the stop decision restarts fresh); completed early
    stops are final-marked and never retrained.
    """
    p = params
    cb = callbacks or Callbacks()
    n, n_features = x.shape
    # telemetry: the `gbdt.fit` wrapper span is the ambient context here;
    # per-iteration (host loop) / per-chunk (fused scan) children attach to
    # it. No ambient context (unsampled fit) -> every mark is one compare.
    _tel = get_tracer()
    # goodput accounting (telemetry/goodput.py): opt-in per fit — bench
    # and supervised fits pass a StepClock; a bare fit pays nothing.
    _clk = step_clock
    import contextlib

    def _clk_step(idx):
        return _clk.step(idx) if _clk is not None else \
            contextlib.nullcontext()

    def _clk_ckpt(fn, *a, **kw):
        if _clk is None:
            return fn(*a, **kw)
        t0 = time.perf_counter()
        try:
            return fn(*a, **kw)
        finally:
            _clk.note("checkpoint", time.perf_counter() - t0)
            _clk.marked()

    def _iter_mark(it_idx, t0, ck_s: float = 0.0):
        if _clk is not None:
            # host-loop iterations feed the clock via externally-measured
            # walls (the body has break paths a context manager can't
            # straddle); the periodic checkpoint's stall rides as a note
            _clk.add_step(time.perf_counter() - t0,
                          {"checkpoint": ck_s} if ck_s > 0.0 else None)
            if ck_s > 0.0:
                _clk.marked()
        if _tel.current() is not None:
            _tel.record(tnames.GBDT_ITERATION_SPAN,
                        duration_ms=(time.perf_counter() - t0) * 1000.0,
                        attrs={"iteration": int(it_idx) + iter_offset})
    multiclass = p.objective == "multiclass"
    k_out = p.num_class if multiclass else 1
    put = put_fn or jnp.asarray
    custom_tree_fn = tree_fn is not None
    # level-invariant one-hot planes (round 6, MMLSPARK_TPU_HIST=planes):
    # built ONCE per fit below (bins never change across levels/trees/
    # iterations); the default tree_fn closes over the locals LATE so the
    # plan staged after binning is what the host loop uses too
    _hist_planes, _hist_plane_lo = None, 0
    if tree_fn is None:
        tree_fn = lambda b, g, h, fm, cfg, cw=None: trainer.train_one_tree(
            b, g, h, fm, cfg, count_w=cw, lo_planes=_hist_planes,
            plane_lo=_hist_plane_lo)

    staged_y = y_j = None
    if prebinned is not None:
        # (mapper, device_bins[, device_y]): data already staged on device
        # — training throughput can then be measured without the
        # host->device copies (the optional third element also skips the
        # label upload; `y` itself stays a HOST array for the host-side
        # init-score statistics either way)
        if len(prebinned) == 3:
            mapper, d_bins, staged_y = prebinned
        else:
            mapper, d_bins = prebinned
        d_bins = put(d_bins)
    else:
        with tracing.annotate(tnames.GBDT_FIT_FIT_BINS), \
                tracing.wall_clock(tnames.DATA_FIT_BINS,
                                   sink=reliability_metrics.observe):
            mapper = binning.fit_bins(
                x, max_bin=p.max_bin, seed=p.seed,
                categorical_features=p.categorical_features)
        if oocore is not None:
            # out-of-core: stream chunked binning under the residency
            # budget; the stager hands put_fn (sharded placement) the
            # assembled uint8 cache, or feeds a donated device buffer
            # per chunk on accelerators (data/oocore.py)
            from ...data.oocore import ChunkStager
            stager = ChunkStager(x, mapper, oocore)
            d_bins = stager.stage(put=put_fn)
        elif ingest is not None:
            from ...data import parallel_apply_bins, stage_binned
            if put_fn is None:
                # single-device: chunk binning overlaps the device feed
                d_bins = stage_binned(mapper, x, ingest)
            else:
                # sharded put: bin host-parallel, place the whole matrix
                # once (per-chunk placement would fight the row sharding)
                d_bins = put(parallel_apply_bins(mapper, x, ingest))
        else:
            # host dispatch and H2D of the default path; the device side
            # is the gbdt.bin scope of the capture
            with tracing.annotate(tnames.GBDT_FIT_BIN_DISPATCH):
                d_bins = put(binning.apply_bins_device(mapper, x))
                y_j = put(np.asarray(y, dtype=np.float32))
            register_program(
                f"gbdt.bin[{n}x{n_features}]",
                lambda: binning.assign_bins_program_text(
                    mapper, (n, n_features)))
    if (os.environ.get("MMLSPARK_TPU_HIST") == "planes"
            and not custom_tree_fn and chunk_fn is None and put_fn is None):
        # precompute the level-invariant lo one-hot planes once per fit;
        # they ride the fused scan as a hoisted constant (F*LO*n int8
        # bytes resident in HBM — see histogram_pallas's routing notes)
        from ...ops import histogram_pallas as _hp
        _lo = _hp.plan_lo_bins(p.max_bin + 1)
        if _lo:
            _hist_planes = _hp.build_hist_plan(d_bins, p.max_bin + 1)
            _hist_plane_lo = _lo
            reliability_metrics.set_gauge(tnames.GBDT_HIST_PLAN_BYTES,
                                          float(_hist_planes.nbytes))
    if y_j is None:
        y_j = (put(staged_y.astype(jnp.float32)) if staged_y is not None
               else put(np.asarray(y, dtype=np.float32)))
    w_j = None if weights is None else put(np.asarray(weights, dtype=np.float32))
    # physical-row indicator (0 = distributed padding); user weights must not
    # affect min_data_in_leaf counts, so this is a separate channel
    pres_j = None if presence is None else put(np.asarray(presence, np.float32))
    # lambdarank: the padded per-group gather layout is computed once, host-side
    g_idx = (jnp.asarray(obj_mod.make_group_index(group))
             if group is not None else None)

    base = 0.0
    if init_booster is not None:
        # continuation: new trees fit the residuals of the existing ensemble;
        # its base (init_base) carries over instead of recomputing the mean
        base = float(init_base)
    elif p.boost_from_average and init_scores is None and not multiclass:
        with tracing.annotate(tnames.GBDT_FIT_INIT_SCORE):
            base = obj_mod.init_score(p.objective, y, weights=weights)
    init_margin_arr = None
    if init_booster is not None and init_margin is None:
        # resumed-without-saved-margin (legacy checkpoints) / warm starts:
        # rebuild the continuation margin by scoring the restored ensemble
        init_margin_arr = init_booster.raw_score(x)  # (n, K)
    margin_no_continuation = None  # rf: gradients target y, not residuals
    # margins are DEVICE-created: np.full/np.zeros here would upload
    # n (x K) f32 through the host link per fit — a wasted copy
    if multiclass:
        margin = put(jnp.zeros((n, p.num_class), dtype=jnp.float32))
        y_onehot = jax.nn.one_hot(y_j.astype(jnp.int32), p.num_class,
                                  dtype=jnp.float32)
        if init_scores is not None:
            init_arr = np.asarray(init_scores, dtype=np.float32)
            if init_arr.shape != (n, p.num_class):
                raise ValueError(
                    f"multiclass init_scores must be (n, num_class)="
                    f"({n}, {p.num_class}), got {init_arr.shape}")
            margin = margin + put(init_arr)
        # captured AFTER init_scores: resumed-rf gradients target the
        # init_scores baseline, excluding only the restored ensemble
        margin_no_continuation = margin
        if init_margin_arr is not None:
            margin = margin + put(init_margin_arr.astype(np.float32))
    else:
        margin = put(jnp.full((n,), base, dtype=jnp.float32))
        if init_scores is not None:
            margin = margin + put(np.asarray(init_scores, dtype=np.float32))
        margin_no_continuation = margin
        if init_margin_arr is not None:
            margin = margin + put(init_margin_arr[:, 0].astype(np.float32))
    if init_margin is not None:
        # checkpointed live margin: REPLACES the reconstruction above so the
        # resumed device state is bitwise the uninterrupted run's. A saved
        # margin only makes sense against the SAME rows — pairing it with a
        # regenerated dataset would silently train on wrong per-row scores
        # (the pre-margin raw_score path at least recomputed against x)
        init_margin = np.asarray(init_margin, np.float32)
        if init_margin.shape[0] != n:
            raise ValueError(
                f"init_margin has {init_margin.shape[0]} rows but x has "
                f"{n} — the checkpoint was saved against different data; "
                f"delete the checkpoint dir (or drop init_margin) to "
                f"restart from the restored trees alone")
        margin = put(init_margin)

    # validation margins maintained incrementally on binned valid rows
    has_valid = valid is not None
    if has_valid:
        vx, vy = valid
        if ingest is not None:
            from ...data import parallel_apply_bins
            v_bins = jnp.asarray(parallel_apply_bins(mapper, vx, ingest))
        else:
            v_bins = jnp.asarray(binning.apply_bins(mapper, vx))
        if multiclass:
            v_margin = jnp.zeros((vx.shape[0], p.num_class), jnp.float32)
        else:
            v_margin = jnp.full((vx.shape[0],), base, jnp.float32)
        if init_booster is not None:
            v_init = init_booster.raw_score(np.asarray(vx, np.float32))
            v_margin = v_margin + jnp.asarray(
                v_init if multiclass else v_init[:, 0], jnp.float32)

    cfg_base = dict(n_features=n_features, n_bins=p.max_bin + 1,
                    max_depth=p.max_depth, num_leaves=p.num_leaves,
                    lambda_l1=p.lambda_l1, lambda_l2=p.lambda_l2,
                    min_gain_to_split=p.min_gain_to_split,
                    min_data_in_leaf=p.min_data_in_leaf,
                    min_sum_hessian_in_leaf=p.min_sum_hessian_in_leaf,
                    categorical_features=tuple(p.categorical_features),
                    cat_smooth=p.cat_smooth, cat_l2=p.cat_l2,
                    max_cat_threshold=p.max_cat_threshold)

    rf = p.boosting == "rf"
    dart = p.boosting == "dart"
    goss = p.boosting == "goss"
    key = (jax.random.PRNGKey(p.seed) if init_rng_key is None
           else jnp.asarray(np.asarray(init_rng_key, np.uint32)))
    iter_offset = int(iter_offset)
    if checkpoint_fn is not None:
        # legacy checkpoint_fn signatures predate the margin/rng_key
        # kwargs — only pass them to callbacks that can take them, so an
        # external `lambda it, booster, base, final=False: ...` keeps
        # working (it just loses exact-resume margins)
        import inspect
        try:
            ck_params = inspect.signature(checkpoint_fn).parameters
            _ck_extended = ("margin" in ck_params
                            or any(q.kind == q.VAR_KEYWORD
                                   for q in ck_params.values()))
        except (TypeError, ValueError):
            _ck_extended = True
        _user_ck = checkpoint_fn
        # multi-host: the margin is row-sharded over the GLOBAL mesh — not
        # fully addressable from one process, so np.asarray would raise.
        # Skip the exact-resume margin there (legacy raw_score resume
        # still works); single-host sharded margins gather fine.
        _margin_addressable = jax.process_count() == 1

        def checkpoint_fn(it, booster, fit_base, final=False, margin=None,
                          rng_key=None):
            if not _margin_addressable:
                margin = None
            elif margin is not None:
                margin = np.asarray(margin)
            if rng_key is not None:
                rng_key = np.asarray(rng_key)
            if _ck_extended:
                return _user_ck(it, booster, fit_base, final=final,
                                margin=margin, rng_key=rng_key)
            return _user_ck(it, booster, fit_base, final=final)

    # ---- fused path: whole boosting loop as chunked lax.scan (no host in
    # the loop). Host-loop fallback covers DART (needs per-tree delta
    # history), L1-family leaf renewal, lambdarank, and delegate callbacks.
    use_fused = (callbacks is None and not dart
                 and p.fobj is None
                 and p.objective not in RENEWAL_OBJECTIVES
                 and p.objective != "lambdarank"
                 and (chunk_fn is not None or not custom_tree_fn))
    if use_fused:
        eval_history = []
        fused = chunk_fn or _boost_chunk
        cfg = trainer.TreeConfig(
            learning_rate=(1.0 / (p.rf_total or p.num_iterations) if rf
                           else p.learning_rate),
            **cfg_base)
        if has_valid:
            vy_j = jnp.asarray(np.asarray(vy, np.float32))
            v_bins_, v_margin_ = v_bins, v_margin
        else:  # static dummies; has_valid=False branches never read them
            v_bins_ = jnp.zeros((1, n_features), jnp.uint8)
            vy_j = jnp.zeros((1,), jnp.float32)
            v_margin_ = jnp.zeros((1, p.num_class) if multiclass else (1,),
                                  jnp.float32)
        mname = p.metric or {"binary": "binary_logloss",
                             "multiclass": "multi_logloss"}.get(p.objective, "l2")
        larger = mname == "auc"
        patience = p.early_stopping_round
        track = has_valid and (patience > 0 or p.metric is not None)
        chunk = (max(patience, 16) if (track and patience > 0)
                 else p.num_iterations)
        if checkpoint_fn is not None:
            # checkpoints happen at chunk boundaries; bound the chunk so a
            # crash loses at most checkpoint_interval iterations
            chunk = min(chunk, max(int(checkpoint_interval), 1))
        parts, stop_at = [], None
        best_metric, best_iter, rounds_since = None, -1, 0
        it = 0
        registered_clen = None
        # rf gradients stay at the pre-loop margin EXCLUDING any restored
        # ensemble: resumed rf trees must fit the same bagged target as the
        # first half, not the half-forest's residuals
        margin_init = (margin_no_continuation if rf and init_booster is not None
                       else margin)
        while it < p.num_iterations:
            _chunk_t0 = time.perf_counter()
            clen = min(chunk, p.num_iterations - it)
            key, kc = jax.random.split(key)
            # planes ride as explicit kwargs ONLY when built: a custom
            # chunk_fn (distributed) predates them and is never paired
            # with a plan (the build above is gated on chunk_fn is None)
            _plane_kw = ({"lo_planes": _hist_planes,
                          "plane_lo": _hist_plane_lo}
                         if _hist_planes is not None else {})
            chunk_args = (d_bins, y_j, w_j, pres_j, margin, margin_init,
                          v_bins_, vy_j, v_margin_, kc, it + iter_offset, p,
                          cfg, clen, k_out)
            chunk_kw = dict(has_valid=has_valid, **_plane_kw)
            if fused is _boost_chunk and clen != registered_clen:
                registered_clen = clen
                register_program(
                    f"gbdt.chunk[{n}x{n_features}/{clen}]",
                    _chunk_program_text(chunk_args, chunk_kw))
            with _clk_step(it):
                with tracing.annotate(tnames.GBDT_FIT_BOOST,
                                      iterations=int(clen)):
                    (margin, v_margin_, sf_c, sb_c, lv_c, gn_c, cv_c, ic_c,
                     cw_c, mts) = fused(*chunk_args, **chunk_kw)
                parts.append((sf_c, sb_c, lv_c, gn_c, cv_c, ic_c, cw_c))
                if checkpoint_fn is not None:
                    # chunk boundary = natural checkpoint step: build the
                    # booster-so-far from the accumulated parts (host-
                    # cheap). The live margin + PRNG key ride along so a
                    # resumed fit continues on bit-identical state (the
                    # snapshot D2H is the cheap host copy; the disk write
                    # may be async downstream)
                    def _chunk_ckpt():
                        _sf, _sb, _lv, _gn, _cv, _ic, _cw = \
                            _fetch_packed(parts)
                        _tc = np.tile(np.arange(k_out, dtype=np.int32),
                                      _sf.shape[0] // max(k_out, 1))
                        checkpoint_fn(it + clen, _build_booster(
                            _sf, _sb, _lv, _tc, mapper, p, k_out,
                            n_features, -1, init_booster, base, gain=_gn,
                            cover=_cv, is_cat=_ic, cat_words=_cw), base,
                            final=False, margin=margin, rng_key=key)
                    _clk_ckpt(_chunk_ckpt)
            if track:
                for i, mv in enumerate(np.asarray(mts)):
                    mv = float(mv)
                    eval_history.append(mv)
                    improved = (best_metric is None
                                or ((mv > best_metric) == larger
                                    and mv != best_metric))
                    if improved:
                        best_metric, best_iter, rounds_since = mv, it + i, 0
                    else:
                        rounds_since += 1
                        if patience > 0 and rounds_since >= patience:
                            stop_at = it + i + 1
                            break
            if _tel.current() is not None:
                # the fused scan has no host-visible per-iteration boundary;
                # the chunk IS the granularity device work surfaces at
                _tel.record(tnames.GBDT_CHUNK_SPAN,
                            duration_ms=(time.perf_counter() - _chunk_t0)
                            * 1000.0,
                            attrs={"first_iteration": it + iter_offset,
                                   "iterations": int(clen)})
            it += clen
            if stop_at is not None:
                break
        # ONE D2H for every chunk's outputs: per-array fetches each pay a
        # full transfer round-trip, so pack the five (T, max_nodes) arrays
        # into a single f32 device array (bitcasting the i32 ones) and
        # fetch once.
        # This fetch is the loop's block-until-ready boundary — where the
        # async dispatch's device time surfaces for the goodput account.
        with tracing.annotate(tnames.GBDT_FIT_FETCH):
            if _clk is not None:
                sf, sb, lv, gn, cv, ic, cw = _clk.device_block(
                    lambda: _fetch_packed(parts))
            else:
                sf, sb, lv, gn, cv, ic, cw = _fetch_packed(parts)
        if stop_at is not None:  # drop trees grown past the stopping point
            keep = stop_at * k_out
            sf, sb, lv = sf[:keep], sb[:keep], lv[:keep]
            gn, cv, ic, cw = gn[:keep], cv[:keep], ic[:keep], cw[:keep]
            if checkpoint_fn is not None:
                # overwrite the overgrown chunk checkpoint with the truncated
                # state and mark training COMPLETE so a re-fit doesn't
                # continue past the early stop
                tc_ = np.tile(np.arange(k_out, dtype=np.int32),
                              sf.shape[0] // max(k_out, 1))
                checkpoint_fn(stop_at, _build_booster(
                    sf, sb, lv, tc_, mapper, p, k_out, n_features,
                    best_iter, init_booster, base, gain=gn, cover=cv,
                    is_cat=ic, cat_words=cw),
                    base, final=True)
        with tracing.annotate(tnames.GBDT_FIT_ASSEMBLE):
            tree_classes = np.tile(np.arange(k_out, dtype=np.int32),
                                   sf.shape[0] // max(k_out, 1))
            booster = _build_booster(
                sf, sb, lv, tree_classes, mapper, p, k_out, n_features,
                best_iter if (track and patience > 0) else -1, init_booster,
                base, gain=gn, cover=cv, is_cat=ic, cat_words=cw)
        return booster, base, eval_history

    trees, tree_classes, train_deltas = [], [], []
    dart_weights: list = []
    val_deltas: list = []  # per-iteration val-set deltas (DART reweighting)
    best_metric, best_iter, rounds_since = None, -1, 0
    eval_history = []
    init_margin = (margin_no_continuation
                   if rf and init_booster is not None else margin)

    n_grown = 0
    for it in range(p.num_iterations):
        _it_t0 = time.perf_counter()
        if cb.before_iteration:
            cb.before_iteration(it)
        lr = cb.get_learning_rate(it) if cb.get_learning_rate else p.learning_rate
        if rf:
            lr = 1.0 / (p.rf_total or p.num_iterations)  # averaging via scaled sum
        key, k_feat, k_bag, k_drop = jax.random.split(key, 4)

        # DART: drop a subset of prior trees from the margin for this iteration
        if dart and train_deltas and float(jax.random.uniform(k_drop)) >= p.skip_drop:
            n_prev = len(train_deltas)
            drop_p = min(p.drop_rate, p.max_drop / max(n_prev, 1))
            drop_mask = np.asarray(
                jax.random.uniform(k_drop, (n_prev,)) < drop_p)
            dropped = np.nonzero(drop_mask)[0]
        else:
            dropped = np.array([], dtype=int)

        if dart and len(dropped):
            margin_used = margin
            for t_i in dropped:
                margin_used = margin_used - train_deltas[t_i] * dart_weights[t_i]
        elif rf:
            # rf trees are independent: gradients at the initial margin
            margin_used = init_margin
        else:
            margin_used = margin

        # gradients at the current (possibly dropped) margin
        grad, hess = _grad_hess(p, margin_used, y_j,
                                y_onehot if multiclass else None, g_idx)
        if w_j is not None:
            grad = grad * (w_j[:, None] if multiclass else w_j)
            hess = hess * (w_j[:, None] if multiclass else w_j)

        # row sampling: bagging or GOSS (shared with the fused path);
        # iter_offset keeps a resumed fit's bagging phase aligned with the
        # absolute iteration the uninterrupted run would be at
        row_w = _row_weights(p, grad, k_bag, it + iter_offset, multiclass)
        if row_w is not None:
            grad = grad * (row_w[:, None] if multiclass else row_w)
            hess = hess * (row_w[:, None] if multiclass else row_w)

        fmask = _feature_mask(p, k_feat, n_features)
        count_w = _presence(pres_j, row_w)

        cfg = trainer.TreeConfig(learning_rate=lr, **cfg_base)
        it_deltas = jnp.zeros_like(margin)
        v_it_delta = jnp.zeros_like(v_margin) if has_valid else None
        for k in range(k_out):
            gk = grad[:, k] if multiclass else grad
            hk = hess[:, k] if multiclass else hess
            tree, delta = tree_fn(d_bins, gk, hk, fmask, cfg, count_w)
            if p.objective in ("regression_l1", "quantile", "huber"):
                # leaf-output renewal: refit each leaf to the residual
                # median/quantile (LightGBM's RenewTreeOutput for L1-family
                # objectives — plain -g/h steps of ±lr converge hopelessly
                # slowly when labels aren't unit-scale).
                q = p.alpha if p.objective == "quantile" else 0.5
                nodes = np.asarray(trainer.leaf_of_binned(
                    d_bins, tree.split_feature, tree.split_bin, p.max_depth,
                    split_is_cat=tree.split_is_cat,
                    cat_words=tree.cat_words))
                resid = np.asarray(y_j) - np.asarray(margin_used)
                w_np = None if w_j is None else np.asarray(w_j)
                lv = np.asarray(tree.leaf_value)
                new_lv = lv.copy()
                for node in np.unique(nodes):
                    mask = nodes == node
                    if w_np is not None:
                        mask = mask & (w_np > 0)
                    if mask.any():
                        new_lv[node] = lr * np.quantile(resid[mask], q)
                tree = tree._replace(leaf_value=jnp.asarray(new_lv))
                delta = jnp.asarray(new_lv)[nodes]
            trees.append(jax.tree_util.tree_map(np.asarray, tree))
            tree_classes.append(k)
            if multiclass:
                it_deltas = it_deltas.at[:, k].add(delta)
            else:
                it_deltas = it_deltas + delta
            if has_valid:
                vd = trainer.predict_binned(v_bins, tree.split_feature,
                                            tree.split_bin, tree.leaf_value,
                                            p.max_depth,
                                            split_is_cat=tree.split_is_cat,
                                            cat_words=tree.cat_words)
                if multiclass:
                    v_it_delta = v_it_delta.at[:, k].add(vd)
                else:
                    v_it_delta = v_it_delta + vd
        n_grown += 1

        # DART weight bookkeeping (LightGBM normalization); with an empty
        # drop set this degenerates to new_w=1, scale irrelevant.
        if dart:
            k_dropped = len(dropped)
            new_w = 1.0 / (k_dropped + 1.0) if not p.xgboost_dart_mode else lr
            scale = k_dropped / (k_dropped + 1.0)
            for t_i in dropped:
                shrink = dart_weights[t_i] * (1 - scale)
                margin = margin - train_deltas[t_i] * shrink
                if has_valid:
                    v_margin = v_margin - val_deltas[t_i] * shrink
                dart_weights[t_i] *= scale
            train_deltas.append(it_deltas)
            dart_weights.append(new_w)
            margin = margin + it_deltas * new_w
            if has_valid:
                val_deltas.append(v_it_delta)
                v_margin = v_margin + v_it_delta * new_w
        else:
            margin = margin + it_deltas
            if has_valid:
                v_margin = v_margin + v_it_delta

        # eval + early stopping (reference: TrainUtils.scala:385-419)
        metric_val = None
        if has_valid and (p.early_stopping_round > 0 or p.metric):
            metric_val, larger_better = _eval_metric(
                p.metric, p.objective, v_margin, vy, p.num_class)
            eval_history.append(metric_val)
            improved = (best_metric is None
                        or ((metric_val > best_metric) == larger_better
                            and metric_val != best_metric))
            if improved:
                best_metric, best_iter, rounds_since = metric_val, it, 0
            else:
                rounds_since += 1
            if p.early_stopping_round > 0 and rounds_since >= p.early_stopping_round:
                if cb.after_iteration:
                    cb.after_iteration(it, metric_val)
                _iter_mark(it, _it_t0)
                break
        if cb.after_iteration:
            cb.after_iteration(it, metric_val if metric_val is not None else float("nan"))
        _ck_s = 0.0
        if checkpoint_fn is not None and (it + 1) % max(int(checkpoint_interval), 1) == 0:
            _ck_t0 = time.perf_counter()
            _max_nodes = 2 ** (p.max_depth + 1) - 1
            _sf = np.stack([tr.split_feature for tr in trees])
            _sb = np.stack([tr.split_bin for tr in trees])
            _lv = np.stack([tr.leaf_value for tr in trees])
            _gn = np.stack([tr.gain for tr in trees])
            _cv = np.stack([tr.cover for tr in trees])
            _ic = np.stack([tr.split_is_cat for tr in trees])
            _cw = np.stack([tr.cat_words for tr in trees])
            if dart:
                _w = np.repeat(np.asarray(dart_weights, np.float32), k_out)
                _lv = _lv * _w[:, None]
            checkpoint_fn(it + 1, _build_booster(
                _sf, _sb, _lv, np.asarray(tree_classes, np.int32), mapper, p,
                k_out, n_features, -1, init_booster, base, gain=_gn,
                cover=_cv, is_cat=_ic, cat_words=_cw), base, final=False,
                margin=margin, rng_key=key)
            _ck_s = time.perf_counter() - _ck_t0
        _iter_mark(it, _it_t0, ck_s=_ck_s)

    max_nodes = 2 ** (p.max_depth + 1) - 1
    T = len(trees)
    sf = np.stack([t.split_feature for t in trees]) if T else np.zeros((0, max_nodes), np.int32)
    sb = np.stack([t.split_bin for t in trees]) if T else np.zeros((0, max_nodes), np.int32)
    lv = np.stack([t.leaf_value for t in trees]) if T else np.zeros((0, max_nodes), np.float32)
    gn = np.stack([t.gain for t in trees]) if T else np.zeros((0, max_nodes), np.float32)
    cv = np.stack([t.cover for t in trees]) if T else np.zeros((0, max_nodes), np.float32)
    ic = np.stack([t.split_is_cat for t in trees]) if T else np.zeros((0, max_nodes), bool)
    cw = np.stack([t.cat_words for t in trees]) if T else np.zeros((0, max_nodes, 0), np.int32)
    if dart and T:
        per_iter_w = np.repeat(np.asarray(dart_weights, np.float32), k_out)
        lv = lv * per_iter_w[:, None]
    final_booster = _build_booster(
        sf, sb, lv, np.asarray(tree_classes, np.int32), mapper, p, k_out,
        n_features, best_iter if p.early_stopping_round > 0 else -1,
        init_booster, base, gain=gn, cover=cv, is_cat=ic, cat_words=cw)
    if (checkpoint_fn is not None and p.early_stopping_round > 0
            and rounds_since >= p.early_stopping_round):
        # early stop: persist the truncated model and mark training complete
        checkpoint_fn(n_grown, final_booster, base, final=True)
    return final_booster, base, eval_history


# --------------------------------------------------- semantic contract
# Registered in analysis/semantic/registry.py: the fused boosting chunk
# (the single-host hot path above) lowered at a tiny canonical shape.
# Single host => zero collectives; nothing donated; no callbacks.
from ...analysis.semantic import Case, hot_path_contract  # noqa: E402


@hot_path_contract(
    "gbdt.chunk.fused",
    expected_executables=1,
    donate_expected=(),
    collective_budget={},        # axis_name=None: any collective is a bug
)
def gbdt_fused_chunk_contract():
    """Two identical-layout chunk lowerings must share one executable."""
    import functools as _ft

    import numpy as _np

    p = BoostParams(objective="binary", num_iterations=2, num_leaves=7,
                    max_depth=2, max_bin=15, min_data_in_leaf=1)
    cfg = trainer.TreeConfig(n_features=4, n_bins=16, max_depth=2,
                             num_leaves=7, learning_rate=p.learning_rate,
                             min_data_in_leaf=1)
    n = 64
    rng = _np.random.default_rng(0)
    fn = _ft.partial(getattr(_boost_chunk, "__wrapped__", _boost_chunk),
                     p=p, cfg=cfg, chunk_len=2, k_out=1, axis_name=None,
                     has_valid=False, voting_top_k=None, plane_lo=0)

    def args():
        d_bins = jnp.asarray(rng.integers(0, 16, (n, 4)), jnp.uint8)
        y_j = jnp.asarray(rng.integers(0, 2, n), jnp.float32)
        margin = jnp.zeros(n, jnp.float32)
        v_dummy = jnp.zeros((1, 4), jnp.uint8)
        return (d_bins, y_j, None, jnp.ones(n, jnp.float32), margin,
                margin, v_dummy, jnp.zeros(1, jnp.float32),
                jnp.zeros(1, jnp.float32), jax.random.PRNGKey(0),
                jnp.asarray(0, jnp.int32))

    return [Case("first-chunk", fn, args()),
            Case("next-chunk", fn, args())]
