"""GBDT pipeline stages: the LightGBMClassifier/Regressor/Ranker equivalents.

Parameter surface mirrors the reference's 60+ LightGBM params
(lightgbm/params/LightGBMParams.scala) under the same names where sensible;
`parallelism` selects data_parallel | voting_parallel histogram exchange
(LightGBMParams.scala:16-29), executed here as mesh collectives
(see distributed.py) instead of socket rings. Model classes expose
predict/leaf-index/SHAP output columns like LightGBMModelMethods
(lightgbm/LightGBMClassifier.scala:110-189) and native-model string round-trip
(saveNativeModel / loadNativeModelFromFile, LightGBMClassifier.scala:185-206).
"""
from __future__ import annotations

import dataclasses
import os

from typing import Optional

import numpy as np

from ...core import (Estimator, Model, Param, Table, HasFeaturesCol,
                     HasLabelCol, HasWeightCol, HasPredictionCol,
                     HasProbabilitiesCol, one_of, in_range)
from ...telemetry import names as tnames
from ...utils import tracing
from .boosting import BoostParams, Callbacks, fit_booster
from .booster import Booster


class _GBDTParams(HasFeaturesCol, HasLabelCol, HasWeightCol, HasPredictionCol):
    boosting = Param("boosting", "gbdt|rf|dart|goss", "gbdt",
                     validator=one_of("gbdt", "rf", "dart", "goss"))
    num_iterations = Param("num_iterations", "number of boosting rounds", 100,
                           validator=in_range(1))
    learning_rate = Param("learning_rate", "shrinkage rate", 0.1)
    num_leaves = Param("num_leaves", "max leaves per tree", 31, validator=in_range(2))
    max_depth = Param("max_depth", "max tree depth (levels)", 5, validator=in_range(1, 12))
    max_bin = Param("max_bin", "max feature bins", 255, validator=in_range(2, 255))
    lambda_l1 = Param("lambda_l1", "L1 regularization", 0.0)
    lambda_l2 = Param("lambda_l2", "L2 regularization", 0.0)
    min_gain_to_split = Param("min_gain_to_split", "min split gain", 0.0)
    min_data_in_leaf = Param("min_data_in_leaf", "min rows per leaf", 20)
    min_sum_hessian_in_leaf = Param("min_sum_hessian_in_leaf",
                                    "min hessian mass per leaf", 1e-3)
    feature_fraction = Param("feature_fraction", "feature subsample per tree", 1.0,
                             validator=in_range(0.0, 1.0))
    bagging_fraction = Param("bagging_fraction", "row subsample", 1.0,
                             validator=in_range(0.0, 1.0))
    bagging_freq = Param("bagging_freq", "bag every k iterations (0=off)", 0)
    top_rate = Param("top_rate", "GOSS large-gradient keep rate", 0.2)
    other_rate = Param("other_rate", "GOSS small-gradient sample rate", 0.1)
    drop_rate = Param("drop_rate", "DART tree drop rate", 0.1)
    max_drop = Param("max_drop", "DART max dropped trees per iteration", 50)
    skip_drop = Param("skip_drop", "DART probability of skipping drop", 0.5)
    xgboost_dart_mode = Param("xgboost_dart_mode", "use xgboost-style dart weights", False)
    seed = Param("seed", "random seed", 0)
    early_stopping_round = Param("early_stopping_round",
                                 "stop after k rounds w/o val improvement (0=off)", 0)
    metric = Param("metric", "eval metric for early stopping", None)
    validation_indicator_col = Param(
        "validation_indicator_col",
        "bool column marking validation rows (reference: HasValidationIndicatorCol)",
        None)
    init_score_col = Param("init_score_col", "per-row initial margin column", None)
    boost_from_average = Param("boost_from_average", "init margin at label mean", True)
    # distribution (reference: LightGBMParams.scala:16-58)
    parallelism = Param("parallelism", "data_parallel|voting_parallel", "data_parallel",
                        validator=one_of("data_parallel", "voting_parallel"))
    top_k = Param("top_k", "voting_parallel: features voted per worker", 20)
    use_barrier_execution_mode = Param(
        "use_barrier_execution_mode",
        "gang-schedule workers (always true on a TPU mesh; kept for parity)", False)
    num_batches = Param("num_batches", "split training into sequential batches", 0)
    num_tasks = Param("num_tasks", "override worker count (0=all mesh devices)", 0)
    sigmoid = Param("sigmoid", "sigmoid scale for binary objective", 1.0)
    verbosity = Param("verbosity", "log level", -1)
    # native categorical splits (reference: categoricalSlotIndexes /
    # categoricalSlotNames, lightgbm/params/LightGBMParams.scala:184-196).
    # Listed feature slots hold integer category ids; they are identity-
    # binned and split by sorted-by-gradient category sets instead of the
    # artificial ordinal ordering. Names resolve against the features
    # column's `feature_names` metadata when present.
    categorical_slot_indexes = Param(
        "categorical_slot_indexes",
        "feature slots to treat as categorical", ())
    categorical_slot_names = Param(
        "categorical_slot_names",
        "feature names to treat as categorical (resolved via the features "
        "column's feature_names metadata)", ())
    cat_smooth = Param("cat_smooth",
                       "categorical sort-ratio smoothing", 10.0)
    cat_l2 = Param("cat_l2", "extra L2 for categorical splits", 10.0)
    max_cat_threshold = Param(
        "max_cat_threshold",
        "max categories on the smaller side of a categorical split", 32)
    leaf_prediction_col = Param("leaf_prediction_col",
                                "output column for per-tree leaf indices", None)
    features_shap_col = Param("features_shap_col",
                              "output column for SHAP contributions", None)

    fobj = Param("fobj", "custom objective: (margin, y) -> (grad, hess) "
                 "(reference: FObjTrait.scala:17)", None, transient=True)

    # parallel host ingest (data/ subsystem — the Spark-partitions analog;
    # see docs/data.md). num_ingest_workers=1 keeps the legacy serial
    # staging; 0 = all cores; >1 = that many workers. Parallel output is
    # bit-identical to serial (tests/test_data_pipeline.py pins it).
    num_ingest_workers = Param(
        "num_ingest_workers",
        "host ingest/binning workers (1=serial legacy path, 0=all cores)", 1,
        validator=in_range(0))
    ingest_mode = Param(
        "ingest_mode", "worker pool backend: auto|process|thread", "auto",
        validator=one_of("auto", "process", "thread"))
    ingest_chunk_rows = Param(
        "ingest_chunk_rows", "rows per ingest chunk (0=auto ~32MB)", 0,
        validator=in_range(0))
    ingest_prefetch = Param(
        "ingest_prefetch",
        "bounded host->device prefetch depth (double buffer)", 2,
        validator=in_range(1))

    # out-of-core staging (data/oocore.py; docs/gbdt.md "Out-of-core
    # training"): stream chunked binning under a bounded raw-bytes
    # residency budget with a durable mid-dataset resume cursor. The
    # spill cache lands next to the checkpoints when checkpoint_dir is
    # set, so a preempted fit resumes staging where it died.
    out_of_core = Param(
        "out_of_core",
        "stream chunked binning under max_resident_bytes instead of "
        "staging the whole matrix (bit-identical output)", False)
    max_resident_bytes = Param(
        "max_resident_bytes",
        "out-of-core residency budget for raw input bytes held host-"
        "resident at once (0 = one auto ~32MB chunk window)", 0,
        validator=in_range(0))

    checkpoint_dir = Param(
        "checkpoint_dir",
        "step-checkpoint directory (utils.checkpoint.CheckpointManager); "
        "fit() resumes from the latest digest-valid step and saves every "
        "checkpoint_interval iterations", None)
    checkpoint_interval = Param("checkpoint_interval",
                                "iterations between checkpoints", 25)
    checkpoint_async = Param(
        "checkpoint_async",
        "write periodic checkpoints on a background thread "
        "(reliability.AsyncCheckpointWriter) so the boosting loop never "
        "blocks on disk; the final/early-stop checkpoint stays synchronous",
        True)
    quality_profile = Param(
        "quality_profile",
        "freeze a reference feature/label/prediction distribution profile "
        "at fit time (telemetry.quality; bounded head sample) — serving "
        "installs it so live drift gauges and the /quality export compare "
        "the serving stream against THIS fit's data", True)

    def _boost_params(self, objective: str, num_class: int = 1) -> BoostParams:
        return BoostParams(
            # objective extras live on subclasses (GBDTRegressor.alpha /
            # tweedie_variance_power, GBDTRanker.max_position) — getattr with
            # BoostParams' own field defaults keeps one source of truth
            alpha=getattr(self, "alpha", BoostParams.alpha),
            tweedie_variance_power=getattr(self, "tweedie_variance_power",
                                           BoostParams.tweedie_variance_power),
            max_position=getattr(self, "max_position", BoostParams.max_position),
            fobj=self.fobj,
            objective=objective, boosting=self.boosting,
            num_iterations=self.num_iterations, learning_rate=self.learning_rate,
            num_leaves=self.num_leaves, max_depth=self.max_depth,
            max_bin=self.max_bin, lambda_l1=self.lambda_l1,
            lambda_l2=self.lambda_l2, min_gain_to_split=self.min_gain_to_split,
            min_data_in_leaf=self.min_data_in_leaf,
            min_sum_hessian_in_leaf=self.min_sum_hessian_in_leaf,
            feature_fraction=self.feature_fraction,
            bagging_fraction=self.bagging_fraction, bagging_freq=self.bagging_freq,
            top_rate=self.top_rate, other_rate=self.other_rate,
            drop_rate=self.drop_rate, max_drop=self.max_drop,
            skip_drop=self.skip_drop, xgboost_dart_mode=self.xgboost_dart_mode,
            num_class=num_class, sigmoid=self.sigmoid, seed=self.seed,
            early_stopping_round=self.early_stopping_round, metric=self.metric,
            boost_from_average=self.boost_from_average,
            categorical_features=tuple(
                int(i) for i in (self.categorical_slot_indexes or ())),
            cat_smooth=self.cat_smooth, cat_l2=self.cat_l2,
            max_cat_threshold=self.max_cat_threshold,
            verbosity=self.verbosity)

    def _resolve_categoricals(self, table: Table, params: BoostParams):
        """Merge categorical_slot_names (via feature_names metadata) into
        the slot-index set (reference: LightGBMBase resolves slot names
        against the assembled vector's attribute names)."""
        names = tuple(self.categorical_slot_names or ())
        if not names:
            return params
        feature_names = table.column_meta(self.features_col).get(
            "feature_names")
        if feature_names is None:
            raise ValueError(
                "categorical_slot_names given but the features column "
                f"{self.features_col!r} carries no feature_names metadata; "
                "use categorical_slot_indexes or attach names via "
                "Table.with_column_meta")
        name_to_idx = {nm: i for i, nm in enumerate(feature_names)}
        missing = [nm for nm in names if nm not in name_to_idx]
        if missing:
            raise KeyError(f"categorical_slot_names not in feature_names: "
                           f"{missing}")
        merged = tuple(sorted(set(params.categorical_features)
                              | {name_to_idx[nm] for nm in names}))
        return dataclasses.replace(params, categorical_features=merged)

    def _split_validation(self, table: Table):
        vcol = self.validation_indicator_col
        if vcol:
            if vcol not in table:
                raise KeyError(
                    f"validation_indicator_col {vcol!r} not in table; "
                    f"have {table.columns}")
            mask = np.asarray(table[vcol], dtype=bool)
            train = table.filter(~mask)
            vx = np.asarray(table[self.features_col], np.float32)[mask]
            vy = np.asarray(table[self.label_col], np.float32)[mask]
            return train, (vx, vy)
        return table, None

    def _fit_data(self, table: Table):
        x = np.asarray(table[self.features_col], dtype=np.float32)
        y = np.asarray(table[self.label_col], dtype=np.float32)
        w = (np.asarray(table[self.weight_col], np.float32)
             if self.weight_col and self.weight_col in table else None)
        init = (np.asarray(table[self.init_score_col], np.float32)
                if self.init_score_col and self.init_score_col in table else None)
        return x, y, w, init

    def _train(self, table: Table, objective: str, num_class: int = 1,
               group: Optional[np.ndarray] = None,
               callbacks: Optional[Callbacks] = None):
        train, valid = self._split_validation(table)
        x, y, w, init = self._fit_data(train)
        params = self._resolve_categoricals(
            table, self._boost_params(objective, num_class))
        n_batches = self.num_batches or 0
        ingest = None
        if self.num_ingest_workers != 1:
            from ...data import IngestOptions
            ingest = IngestOptions(num_workers=self.num_ingest_workers,
                                   mode=self.ingest_mode,
                                   chunk_rows=self.ingest_chunk_rows,
                                   prefetch=self.ingest_prefetch)
        oocore = None
        if self.out_of_core:
            from ...data import OocoreOptions
            cache = None
            if self.checkpoint_dir:
                cache = os.path.join(self.checkpoint_dir, "oocore_bins.npy")
            oocore = OocoreOptions(
                max_resident_bytes=self.max_resident_bytes,
                cache_path=cache,
                num_workers=self.num_ingest_workers,
                mode=("thread" if self.ingest_mode == "auto"
                      else self.ingest_mode),
                chunk_rows=self.ingest_chunk_rows,
                prefetch=self.ingest_prefetch)

        # step-level checkpoint/resume (SURVEY.md §5); single-batch fits only
        ck_fn, resume_booster, done, resume_base = None, None, 0, 0.0
        resume_margin, resume_key, writer = None, None, None
        if self.checkpoint_dir and n_batches <= 1:
            from ...reliability.supervisor import AsyncCheckpointWriter
            from ...utils.checkpoint import CheckpointManager
            from .booster import Booster as _B
            mgr = CheckpointManager(self.checkpoint_dir)
            latest = mgr.latest_step()
            if latest is not None:
                # restore() (not restore(latest)): a torn or
                # silently-corrupted newest step falls back to the
                # next-newest digest-valid one instead of killing the fit
                payload = mgr.restore()
                resume_booster = _B.load_model_string(str(payload["booster"]))
                done = int(payload["iteration"])
                resume_base = float(payload.get("base", 0.0))
                # live margin + PRNG key (absent in legacy checkpoints):
                # with them the resumed fit replays on bit-identical state
                resume_margin = payload.get("margin")
                resume_key = payload.get("rng_key")
                if payload.get("final"):
                    # training completed (possibly early-stopped): the
                    # checkpoint IS the final model
                    return resume_booster, resume_base, []
            total = params.num_iterations
            if (resume_booster is not None and self.boosting == "rf"):
                # restored rf leaves embed 1/denom averaging weights from the
                # run that built them; extending the forest to a new total
                # rescales them to 1/total (crash-resume: denom == total,
                # no-op)
                denom = int(payload.get("rf_denom", total))
                if denom != total:
                    resume_booster = resume_booster._replace(
                        leaf_value=(resume_booster.leaf_value
                                    * (denom / total)).astype(np.float32))
                    # rescaled trees invalidate the saved margin (it embeds
                    # the old weights); fall back to raw_score continuation
                    resume_margin = resume_key = None
            remaining = max(total - done, 0)
            # rf averaging weights must stay 1/TOTAL across the resume split
            params = dataclasses.replace(params, num_iterations=remaining,
                                         rf_total=total)
            # periodic writes ride a background thread (the boosting loop
            # never blocks on disk); the final/early-stop write is
            # synchronous and prunes newer steps as before
            writer = AsyncCheckpointWriter(mgr) if self.checkpoint_async \
                else None

            def ck_fn(it, booster, fit_base, final=False, margin=None,
                      rng_key=None, _mgr=mgr, _done=done,
                      _denom=params.rf_total or params.num_iterations,
                      _oocore=bool(self.out_of_core)):
                payload = {"booster": booster.save_model_string(),
                           "iteration": _done + it, "base": float(fit_base),
                           "final": bool(final), "rf_denom": int(_denom)}
                if _oocore:
                    # the durable staging cursor rides the supervisor/
                    # checkpoint payload for observability; the cursor's
                    # source of truth for resume is the spill-cache
                    # sidecar (data/oocore.py), which survives kills the
                    # checkpoint cadence would miss
                    from ...reliability.metrics import reliability_metrics
                    from ...telemetry import names as _tn
                    cur = reliability_metrics.peek_gauge(
                        _tn.DATA_OOCORE_CURSOR)
                    payload["oocore_cursor"] = int(cur or 0)
                if margin is not None:
                    payload["margin"] = np.asarray(margin, np.float32)
                if rng_key is not None:
                    payload["rng_key"] = np.asarray(rng_key)
                if writer is None:
                    _mgr.save(_done + it, payload, prune_newer=final)
                elif final:
                    writer.write_sync(_done + it, payload, prune_newer=True)
                else:
                    writer.submit(_done + it, payload)
            if remaining == 0:
                return resume_booster, resume_base, []
        if self.parallelism and self._use_mesh():
            from .distributed import fit_booster_distributed
            fit = lambda **kw: fit_booster_distributed(
                parallelism=self.parallelism, top_k=self.top_k,
                num_tasks=self.num_tasks, ingest=ingest, oocore=oocore,
                **kw)
        else:
            fit = lambda **kw: fit_booster(ingest=ingest, oocore=oocore,
                                           **kw)
        if n_batches > 1:
            # batch continuation (reference: LightGBMBase.scala:34-51)
            booster, base, hist = None, 0.0, []
            idx = np.array_split(np.arange(x.shape[0]), n_batches)
            for bi in idx:
                if bi.size == 0:
                    continue
                booster, base, hist = fit(
                    x=x[bi], y=y[bi], params=params,
                    weights=None if w is None else w[bi],
                    init_scores=None if init is None else init[bi],
                    group=None if group is None else group[bi],
                    valid=valid, init_booster=booster, callbacks=callbacks,
                    init_base=base)
            return booster, base, hist
        try:
            return fit(x=x, y=y, params=params, weights=w, init_scores=init,
                       group=group, valid=valid, callbacks=callbacks,
                       init_booster=resume_booster, checkpoint_fn=ck_fn,
                       checkpoint_interval=self.checkpoint_interval,
                       init_base=resume_base, init_margin=resume_margin,
                       init_rng_key=resume_key, iter_offset=done)
        finally:
            if writer is not None:
                writer.close()

    def _use_mesh(self) -> bool:
        import jax
        return self.num_tasks > 1 or (self.num_tasks == 0 and
                                      jax.device_count() > 1)

    def _attach_quality_profile(self, table: Table, model,
                                score_rows: int = 8192):
        """Freeze the fit-time reference profile onto the fitted model
        (ISSUE 12 tentpole tap (1): the ingest/fit-time reference the
        serving-stream live sketches drift against). Bounded: quantile
        grids + sketch counts come from a head sample
        (`quality.MAX_REFERENCE_ROWS`), folded CHUNK BY CHUNK through
        `data.pipeline.profile_columns` — the same exact merge the fleet
        scrape uses — plus label and head-sample model predictions. The
        profile rides the model as a JSON-safe state dict, so it travels
        with the plan payload into `compile_serving_transform`. Guarded:
        profiling must never fail a fit."""
        if not self.quality_profile:
            return model
        try:
            with tracing.annotate(tnames.GBDT_ESTIMATOR_PROFILE):
                self._freeze_quality_profile(table, model, score_rows)
        except Exception:  # noqa: BLE001 - observability never fails a fit
            pass
        return model

    def _freeze_quality_profile(self, table: Table, model, score_rows: int):
        from ...data.pipeline import profile_columns
        from ...telemetry import quality as tquality
        x = np.asarray(table[self.features_col],
                       np.float32)[:tquality.MAX_REFERENCE_ROWS]
        y = np.asarray(table[self.label_col],
                       np.float64)[:tquality.MAX_REFERENCE_ROWS]
        feature_cols = tquality.matrix_columns(x)
        categorical = tuple(
            f"f{int(i)}" for i in (self.categorical_slot_indexes or ()))
        head = Table({self.features_col: x[:score_rows]})
        pred = np.asarray(
            model.transform(head)[self.prediction_col], np.float64)
        all_cols = dict(feature_cols)
        all_cols["label"] = y
        all_cols["prediction"] = pred
        # grids frozen over the full bounded sample, counts folded
        # chunk-wise (ingest-shaped, exact-merge path)
        prof = tquality.DatasetProfile.fit(
            all_cols, categorical=categorical, observe=False)
        profile_columns(prof, feature_cols)
        prof.observe("label", y)
        prof.observe("prediction", pred)
        model.quality_profile = prof.state()

    def _attach_lineage(self, model):
        """Stamp the fit's provenance onto the fitted model — the lineage
        record `telemetry.lineage.model_version` freezes into the
        content-addressed ModelVersion, so `/versions` can answer "what
        trained the thing currently serving" without reaching back to the
        training job. JSON-safe dict: estimator class + uid, the
        non-transient Param snapshot, a digest of the frozen quality
        reference profile (WHICH reference this version drifts against),
        the resumable checkpoint step (checkpoint_dir fits), and the
        fit's goodput/wall readout (telemetry.goodput.StepClock). Also
        appended to the process RunLedger when one is configured.
        Guarded: provenance must never fail a fit."""
        try:
            import hashlib
            import json
            params = {}
            for pname, p in type(self).params().items():
                if p.transient:
                    continue
                v = self.get_or_default(pname)
                try:
                    json.dumps(v)
                    params[pname] = v
                except (TypeError, ValueError):
                    params[pname] = repr(v)
            lineage = {"estimator": type(self).__name__, "uid": self.uid,
                       "params": params}
            prof = getattr(model, "quality_profile", None)
            if prof is not None:
                canon = json.dumps(prof, sort_keys=True, default=str)
                lineage["reference_profile"] = hashlib.sha256(
                    canon.encode()).hexdigest()[:12]
            if self.checkpoint_dir:
                from ...utils.checkpoint import CheckpointManager
                step = CheckpointManager(self.checkpoint_dir).latest_step()
                if step is not None:
                    lineage["checkpoint_step"] = int(step)
            from ...telemetry.goodput import get_clock
            clock = get_clock()
            if clock is not None:
                snap = clock.snapshot()
                lineage["fit"] = {
                    k: snap.get(k)
                    for k in ("steps", "wall_s", "goodput", "mfu")
                    if snap.get(k) is not None}
            model.lineage = lineage
            from ...telemetry import lineage as tlineage
            ledger = tlineage.get_run_ledger()
            if ledger is not None:
                ledger.append(
                    tlineage.model_version(model, content=True).export())
        except Exception:  # noqa: BLE001 - observability never fails a fit
            pass
        return model


class _GBDTModelBase(Model, HasFeaturesCol, HasPredictionCol):
    """Shared scoring surface (reference: LightGBMModelMethods.scala)."""

    def __init__(self, booster: Optional[Booster] = None, init_score: float = 0.0,
                 **kw):
        super().__init__(**kw)
        self._booster = booster
        self._init_score = init_score

    def _raw_score(self, x):
        """Raw scores of a transform's rows, under `gbdt.transform.score`
        (host span; the device scorer's time surfaces inside it)."""
        with tracing.annotate(tnames.GBDT_TRANSFORM_SCORE):
            return self._booster.raw_score(x, self._init_score)

    def _get_state(self):
        d = self._booster.to_dict()
        d["init_score"] = np.float64(self._init_score)
        return d

    def _set_state(self, s):
        self._init_score = float(np.asarray(s.pop("init_score")))
        self._booster = Booster.from_dict(s)

    @property
    def booster(self) -> Booster:
        return self._booster

    def set_best_iteration(self, it: int):
        self._booster = self._booster._replace(best_iteration=it)
        return self

    def feature_importances(self, importance_type="split"):
        return self._booster.feature_importances(importance_type)

    def save_native_model(self, path: str):
        import json
        payload = json.loads(self._booster.save_model_string())
        payload["init_score"] = self._init_score
        with open(path, "w") as f:
            f.write(json.dumps(payload))

    def _serving_kernel(self, output_col: str):
        """Vectorized `(n, F) -> values` closure for the serving fast path
        (io/plan.py): scoring without Table construction or the transform
        telemetry, on the booster's prebuilt host plan. Returns None when
        `output_col` isn't one this model can compute standalone — the
        caller falls back to the generic bucketed `transform` plan."""
        return None

    def _stamp_kernel(self, fn):
        """Annotate a kernel with the feature width the serving decode
        validates against (a wrong-width request 400s at assembly instead
        of reaching the scorer)."""
        fn.expected_features = self._booster.n_features
        return fn

    def _maybe_extra_cols(self, t: Table, x) -> Table:
        lcol = self.get("leaf_prediction_col") if self.has_param("leaf_prediction_col") else None
        if lcol:
            t = t.with_column(lcol, self._booster.predict_leaf(x))
        scol = self.get("features_shap_col") if self.has_param("features_shap_col") else None
        if scol:
            contrib = self._booster.feature_contributions(x)
            # the init score (boost_from_average base) is part of the model's
            # expected value: it belongs in the bias column so that
            # sum(contrib) == full prediction (LightGBM pred_contrib does
            # the same)
            contrib[:, -1] += self._init_score
            t = t.with_column(scol, contrib)
        return t


class GBDTClassifier(Estimator, _GBDTParams, HasProbabilitiesCol):
    """Binary/multiclass GBDT classifier (reference: LightGBMClassifier.scala)."""
    objective = Param("objective", "binary|multiclass", "binary",
                      validator=one_of("binary", "multiclass"))
    num_class = Param("num_class", "number of classes (multiclass)", 2)
    raw_prediction_col = Param("raw_prediction_col", "raw margin output column",
                               "raw_prediction")

    def _fit(self, table: Table) -> "GBDTClassificationModel":
        y = np.asarray(table[self.label_col])
        n_classes = int(y.max()) + 1 if self.objective == "multiclass" else 2
        if self.objective == "multiclass":
            n_classes = max(n_classes, self.num_class)
        booster, base, _ = self._train(
            table, self.objective,
            num_class=n_classes if self.objective == "multiclass" else 1)
        m = GBDTClassificationModel(
            booster=booster, init_score=base, n_classes=n_classes,
            features_col=self.features_col, prediction_col=self.prediction_col,
            probabilities_col=self.probabilities_col,
            raw_prediction_col=self.raw_prediction_col,
            leaf_prediction_col=self.leaf_prediction_col,
            features_shap_col=self.features_shap_col,
            sigmoid=self.sigmoid)
        return self._attach_lineage(self._attach_quality_profile(table, m))


class GBDTClassificationModel(_GBDTModelBase, HasProbabilitiesCol):
    raw_prediction_col = Param("raw_prediction_col", "raw margin output column",
                               "raw_prediction")
    leaf_prediction_col = Param("leaf_prediction_col", "leaf index output col", None)
    features_shap_col = Param("features_shap_col", "SHAP output col", None)
    n_classes = Param("n_classes", "number of classes", 2)
    sigmoid = Param("sigmoid", "sigmoid scale", 1.0)

    def _proba_from_raw(self, raw: np.ndarray) -> np.ndarray:
        """Raw margins -> class probabilities — the ONE copy of the
        objective's output map, shared by the batch transform and the
        serving kernel so the two paths can never drift."""
        if self._booster.objective == "multiclass":
            e = np.exp(raw - raw.max(axis=1, keepdims=True))
            return e / e.sum(axis=1, keepdims=True)
        p1 = 1.0 / (1.0 + np.exp(-self.sigmoid * raw[:, 0]))
        return np.stack([1 - p1, p1], axis=1)

    def _transform(self, t: Table) -> Table:
        x = np.asarray(t[self.features_col], np.float32)
        raw = self._raw_score(x)
        proba = self._proba_from_raw(raw)
        pred = proba.argmax(axis=1).astype(np.float64)
        t = (t.with_column(self.raw_prediction_col, raw)
              .with_column(self.probabilities_col, proba)
              .with_column(self.prediction_col, pred))
        return self._maybe_extra_cols(t, x)

    def _serving_kernel(self, output_col: str):
        multiclass = self._booster.objective == "multiclass"
        if output_col == self.prediction_col:
            plan = self._booster.scoring_plan(self._init_score)
            if multiclass:
                # softmax is monotonic: argmax(proba) == argmax(raw),
                # including ties (both pick the first maximum)
                kern = lambda x: plan(x).argmax(axis=1).astype(np.float64)
            else:
                # argmax([1-p1, p1]) == 1 iff p1 > 0.5 iff raw > 0
                kern = lambda x: (plan(x)[:, 0] > 0).astype(np.float64)
            return self._stamp_kernel(kern)
        if output_col == self.raw_prediction_col:
            return self._stamp_kernel(
                self._booster.scoring_plan(self._init_score))
        if output_col == self.probabilities_col:
            plan = self._booster.scoring_plan(self._init_score)
            return self._stamp_kernel(
                lambda x: self._proba_from_raw(plan(x)))
        return None


class GBDTRegressor(Estimator, _GBDTParams):
    """Reference: LightGBMRegressor.scala; objectives incl. tweedie/huber/quantile."""
    objective = Param("objective", "regression objective", "regression",
                      validator=one_of("regression", "regression_l2", "regression_l1",
                                       "huber", "quantile", "poisson", "tweedie"))
    alpha = Param("alpha", "huber/quantile alpha", 0.9)
    tweedie_variance_power = Param("tweedie_variance_power", "tweedie rho", 1.5)

    def _fit(self, table: Table) -> "GBDTRegressionModel":
        booster, base, _ = self._train(table, self.objective)
        m = GBDTRegressionModel(
            booster=booster, init_score=base,
            features_col=self.features_col, prediction_col=self.prediction_col,
            leaf_prediction_col=self.leaf_prediction_col,
            features_shap_col=self.features_shap_col)
        return self._attach_lineage(self._attach_quality_profile(table, m))


class GBDTRegressionModel(_GBDTModelBase):
    leaf_prediction_col = Param("leaf_prediction_col", "leaf index output col", None)
    features_shap_col = Param("features_shap_col", "SHAP output col", None)

    def _link(self, raw: np.ndarray) -> np.ndarray:
        """Margin -> prediction link (one copy for transform + kernel)."""
        if self._booster.objective in ("poisson", "tweedie"):
            raw = np.exp(raw)
        return raw.astype(np.float64)

    def _transform(self, t: Table) -> Table:
        x = np.asarray(t[self.features_col], np.float32)
        raw = self._raw_score(x)[:, 0]
        t = t.with_column(self.prediction_col, self._link(raw))
        return self._maybe_extra_cols(t, x)

    def _serving_kernel(self, output_col: str):
        if output_col != self.prediction_col:
            return None
        plan = self._booster.scoring_plan(self._init_score)
        return self._stamp_kernel(lambda x: self._link(plan(x)[:, 0]))


class GBDTRanker(Estimator, _GBDTParams):
    """LambdaRank ranker with group column (reference: LightGBMRanker.scala)."""
    group_col = Param("group_col", "query/group id column", "group")
    max_position = Param("max_position", "NDCG truncation", 30)

    def _fit(self, table: Table) -> "GBDTRankerModel":
        groups_raw = np.asarray(table[self.group_col])
        _, group_ids = np.unique(groups_raw, return_inverse=True)
        booster, base, _ = self._train(table, "lambdarank",
                                       group=group_ids.astype(np.int32))
        m = GBDTRankerModel(
            booster=booster, init_score=base,
            features_col=self.features_col, prediction_col=self.prediction_col,
            leaf_prediction_col=self.leaf_prediction_col,
            features_shap_col=self.features_shap_col)
        return self._attach_lineage(self._attach_quality_profile(table, m))


class GBDTRankerModel(_GBDTModelBase):
    leaf_prediction_col = Param("leaf_prediction_col", "leaf index output col", None)
    features_shap_col = Param("features_shap_col", "SHAP output col", None)

    def _transform(self, t: Table) -> Table:
        x = np.asarray(t[self.features_col], np.float32)
        raw = self._raw_score(x)[:, 0]
        t = t.with_column(self.prediction_col, raw.astype(np.float64))
        return self._maybe_extra_cols(t, x)

    def _serving_kernel(self, output_col: str):
        if output_col != self.prediction_col:
            return None
        plan = self._booster.scoring_plan(self._init_score)
        return self._stamp_kernel(
            lambda x: plan(x)[:, 0].astype(np.float64))


def load_native_model(path: str, model_cls=GBDTRegressionModel):
    """reference: loadNativeModelFromFile (LightGBMClassifier.scala:185-206)"""
    import json
    with open(path) as f:
        payload = json.loads(f.read())
    init_score = float(payload.pop("init_score", 0.0))
    booster = Booster.from_dict(payload)
    return model_cls(booster=booster, init_score=init_score)
