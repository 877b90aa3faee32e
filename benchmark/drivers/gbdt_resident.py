"""gbdt_resident driver: the boosting loop on a table that lives on the chip.

Set-up makes the table on the device from the seed (one jitted call, in row
blocks), fits the bin mapper on a host sample, bins on the device with the
program's `apply_bins_device`, and runs the mix's warm-up fits. The window
is back-to-back `fit_booster(..., prebinned=(mapper, bins, y))`: with
`prebinned` the program reads its host `x` for the shape only, so a
zero-stride stand-in of the right shape is passed and no host table exists.
Each fit ends in the program's packed fetch of the trees.
"""
import time

import numpy as np

import gbdt_common as common

HEAD_ROWS = 262_144
BLOCKS = 8


def make_table(key, n_rows, n_features, noise):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def make(key):
        kw, kb = jax.random.split(key)
        w = jax.random.normal(kw, (n_features,), jnp.float32)

        def block(k):
            kx, ke = jax.random.split(k)
            x = jax.random.normal(kx, (n_rows // BLOCKS, n_features),
                                  jnp.float32)
            z = jnp.dot(x, w, precision=jax.lax.Precision.HIGHEST)
            e = jax.random.normal(ke, (n_rows // BLOCKS,), jnp.float32)
            return x, (z + noise * e > 0).astype(jnp.float32)

        x, y = jax.lax.map(block, jax.random.split(kb, BLOCKS))
        return x.reshape(n_rows, n_features), y.reshape(n_rows)

    return make(key)


def run(bench):
    import jax
    from mmlspark_tpu.models.gbdt.boosting import BoostParams, fit_booster
    from mmlspark_tpu.ops import binning
    from mmlspark_tpu.reliability.metrics import reliability_metrics

    cfg, mix = bench.cfg, bench.mix
    n, f, iters = cfg["n_rows"], cfg["n_features"], cfg["num_iterations"]
    if n % BLOCKS:
        raise ValueError(f"n_rows must divide by {BLOCKS}")
    params = BoostParams(objective="binary", **common.boost_kwargs(cfg))
    problems, notes = [], {}

    x, d_y = make_table(bench.jax_key(), n, f,
                        cfg["label_rule"]["noise_scale"])
    head = min(HEAD_ROWS, n)
    x_head = np.asarray(x[:head])
    y_host = np.asarray(d_y)
    mapper = binning.fit_bins(x_head, max_bin=params.max_bin,
                              seed=params.seed)
    d_bins = binning.apply_bins_device(mapper, x)
    d_bins.block_until_ready()
    del x
    x_shape = np.broadcast_to(np.float32(0), (n, f))
    staged = (mapper, d_bins, d_y)
    reliability_metrics.reset("gbdt.hist.")
    for _ in range(mix["warmup_fits"]):
        fit_booster(x_shape, y_host, params, prebinned=staged)
    common.check_routes(common.routes_taken(), bench.device["platform"],
                        problems)

    t0 = bench.setup_done()
    attempted = failed = 0
    t_last, booster, base = t0, None, 0.0
    while bench.open():
        attempted += 1
        with bench.span("train_loop"):
            booster, base, _ = fit_booster(x_shape, y_host, params,
                                           prebinned=staged)
        if booster.n_trees != iters:
            failed += 1
            problems.append(f"a fit returned {booster.n_trees} trees")
        t_last = time.perf_counter()
    bench.end_window()
    done = attempted - failed
    facts = {}
    if bench.trace_on:
        with bench.traced():
            with bench.span("train_loop"):
                fit_booster(x_shape, y_host, params, prebinned=staged)
        facts["traced_iterations"] = iters

    floor = common.parity(bench, mapper, x_head, y_host[:head], problems,
                          notes)
    common.check_auc(y_host[:head], booster.raw_score(x_head)[:, 0] + base,
                     floor, problems, notes)
    notes.update(fits=done, window_s=t_last - t0)
    return {"metrics": {"gbdt_mrow_iters_per_s":
                        n * iters * done / (t_last - t0) / 1e6},
            "attempted": attempted, "failed": failed, "problems": problems,
            "facts": facts, "notes": notes}
