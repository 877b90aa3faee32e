"""gbdt_fit driver: what an MMLSpark user calls, from raw host rows.

Set-up makes the host float32 table once (the traffic needs it) and runs the
mix's warm-up fit + transform. The window is back-to-back
`GBDTClassifier(...).fit(Table)` then `transform` of the first `score_rows`
rows, ended by fetching the probabilities; nothing below the estimator is
called. The estimator picks the mesh from the device count, so the same
driver serves one chip and four. In the traced run one more fit + transform
is traced and, on one chip, the stages the fit is made of are then called
one by one through the program's default functions (`fit_bins`,
`apply_bins_device` + label upload, `fit_booster(prebinned)`).
"""
import statistics
import time

import numpy as np

import gbdt_common as common


def run(bench):
    import jax
    from mmlspark_tpu import Table
    from mmlspark_tpu.models.gbdt import GBDTClassifier
    from mmlspark_tpu.models.gbdt.boosting import BoostParams, fit_booster
    from mmlspark_tpu.ops import binning
    from mmlspark_tpu.reliability.metrics import reliability_metrics

    cfg, mix = bench.cfg, bench.mix
    n, f, iters = cfg["n_rows"], cfg["n_features"], cfg["num_iterations"]
    score_rows = min(mix["score_rows"], n)
    kw = common.boost_kwargs(cfg)
    problems, notes = [], {}

    x, y = common.host_table(bench.seed, n, f,
                             cfg["label_rule"]["noise_scale"])
    table = Table({"features": x, "label": y})
    score_table = Table({"features": x[:score_rows]})
    estimator = GBDTClassifier(**kw)

    def fit_and_score():
        with bench.span("fit"):
            model = estimator.fit(table)
        with bench.span("transform"):
            proba = np.asarray(
                model.transform(score_table)["probabilities"])
        bad = []
        if model.booster.n_trees != iters:
            bad.append(f"a fit returned {model.booster.n_trees} trees")
        if proba.shape != (score_rows, 2) or not np.isfinite(proba).all():
            bad.append("probabilities are not finite (score_rows, 2)")
        return model, proba, bad

    reliability_metrics.reset("gbdt.hist.")
    for _ in range(mix["warmup_fits"]):
        _model, _proba, bad = fit_and_score()
        problems += bad
    common.check_routes(common.routes_taken(), bench.device["platform"],
                        problems)
    bench.spans.clear()
    before = reliability_metrics.snapshot()

    bench.setup_done()
    attempted = failed = 0
    walls, proba = [], None
    while bench.open():
        attempted += 1
        t0 = time.perf_counter()
        model, proba, bad = fit_and_score()
        walls.append(time.perf_counter() - t0)
        if bad:
            failed += 1
            problems += bad
    bench.end_window()
    after = reliability_metrics.snapshot()
    if attempted - failed < mix["min_fits"]:
        problems.append(f"{attempted - failed} fits completed in the "
                        f"window; the median wants {mix['min_fits']}")

    facts = {}
    if bench.trace_on:
        with bench.traced():
            fit_and_score()
            facts["traced_iterations"] = iters
            if bench.device["count"] == 1:
                with bench.span("fit_bins"):
                    mapper = binning.fit_bins(x, max_bin=cfg["max_bin"],
                                              seed=0)
                with bench.span("bin_stage"):
                    d_bins = binning.apply_bins_device(mapper, x)
                    d_y = jax.device_put(y)
                    jax.block_until_ready((d_bins, d_y))
                with bench.span("train_loop"):
                    fit_booster(x, y, BoostParams(objective="binary", **kw),
                                prebinned=(mapper, d_bins, d_y))
                del d_bins, d_y
                facts["traced_iterations"] = 2 * iters

    head = min(common.PARITY_ROWS, n)
    mapper = binning.fit_bins(x[:max(head, 200_000)], max_bin=cfg["max_bin"],
                              seed=0)
    floor = common.parity(bench, mapper, x[:head], y[:head], problems, notes)
    common.check_auc(y[:score_rows], proba[:, 1], floor, problems, notes)
    notes.update(fits=attempted - failed, fit_walls_s=walls)
    program = {k: after[k] - before.get(k, 0) for k in after
               if isinstance(after[k], (int, float))}
    return {"metrics": {"gbdt_fit_raw_s": statistics.median(walls)},
            "attempted": attempted, "failed": failed, "problems": problems,
            "facts": facts, "program": program, "notes": notes}
