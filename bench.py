#!/usr/bin/env python
"""Headline benchmark: GBDT training throughput on the accelerator.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}, where
the extra keys anchor the headline number to the hardware:

- measured_copy_gbps: device memory bandwidth measured IN THIS RUN by a
  big-array copy kernel (not a spec-sheet constant);
- hist_bytes_per_sec / hbm_utilization: the histogram pass's per-level
  memory traffic lower bound — depth levels x n x (F bins bytes + 12 bytes
  of f32 grad/hess/count) per iteration — against that measured bandwidth.
  Roofline math: at 8M x 32feat x 64bins x depth5, one iteration touches
  >= 5 * 8e6 * 44 B = 1.76 GB; 20 iterations = 35.2 GB.
- ns_per_row_level: achieved inner-loop cost. The Pallas histogram kernel
  is VPU-bound on bin one-hot construction (measured floor ~1.4 ns/row/level
  on v5e, see ops/histogram_pallas.py tile-sweep notes), NOT HBM-bound —
  hbm_utilization < 1 with ns_per_row_level near the floor means the chip's
  vector units, not memory, are the binding resource at this shape.

Run BENCH_SHAPES=wide for the two extra shapes the round-2 verdict asked
for (128 features / 255 bins, and 1M rows); each prints its own line, the
LAST line stays the canonical 8M x 32 x 63 headline the driver records.

The north-star workload (BASELINE.json) is LightGBMRegressor/Classifier
training rows/sec — the reference's own published claims are qualitative
("10-30% faster than SparkML GBT", docs/lightgbm.md:17-21), so the baseline
constant below is an A100-class LightGBM training-throughput estimate:
LightGBM GPU on Higgs-sized data sustains ~2e7 (rows x boosting iterations)/s.
vs_baseline > 1.0 means we beat that on this chip.
"""
import json
import os
import sys
import threading
import time

import numpy as np

BASELINE_ROWS_ITERS_PER_SEC = 2.0e7  # A100-class LightGBM estimate (see docstring)

# 8M rows: large enough that steady-state device throughput dominates the
# fixed per-fit dispatch/fetch latency; fits v5e HBM with wide margin
N_ROWS = int(os.environ.get("BENCH_ROWS", 8_000_000))
N_FEATURES = int(os.environ.get("BENCH_FEATURES", 32))
N_ITERS = int(os.environ.get("BENCH_ITERS", 20))


def measure_copy_bandwidth_gbps() -> float:
    """Achievable device memory bandwidth via a big scaled-copy kernel
    (reads + writes 2 x 1 GiB per pass). The passes are data-chained and
    synced by ONE scalar fetch."""
    import jax.numpy as jnp
    from mmlspark_tpu.telemetry import perf as tperf
    a = jnp.ones((256, 1024, 1024), jnp.float32)  # 1 GiB
    # AOT compile through the perf tier: the copy kernel's compile time,
    # flops, and bytes-accessed land in the compile log next to the
    # serving plan builds (reported in the headline's "compile" field)
    f = tperf.compile_with_analysis(lambda x: x * 1.0000001, a,
                                    label="bench.copy_bandwidth")
    float(f(a)[0, 0, 0])  # warm

    def timed(reps):
        t0 = time.time()
        r = a
        for _ in range(reps):
            r = f(r)
        float(r[0, 0, 0])  # sync the whole chain
        return time.time() - t0
    # two-point measurement cancels the fixed dispatch+fetch cost of a
    # timed chain (which would otherwise be charged to the ~3 ms passes)
    d_small, d_big = timed(4), timed(36)
    return 32 * 2 * a.nbytes / max(d_big - d_small, 1e-6) / 1e9


def _hist_route_table(n_bins: int, depth: int, has_planes: bool = False):
    """Chosen kernel route per training level (ops.histogram_pallas's
    routing table evaluated at the shapes this fit actually runs): level 0
    is a full m=1 pass; sibling subtraction makes every later level a
    left-children-only pass with m = 2^(d-1)."""
    from mmlspark_tpu.ops.histogram_pallas import kernel_route
    table = {}
    for d in range(depth):
        m = 1 if d == 0 else 2 ** (d - 1)
        kind, lo = kernel_route(m, n_bins, has_planes=has_planes)
        table[f"level{d}_m{m}"] = f"{kind}:lo{lo}"
    return table


def _phase_breakdown(d_bins, d_y, params, iters: int = 2):
    """Per-phase device time of one boosting iteration, via in-graph
    chained-prefix programs: four jitted programs run (objective),
    (objective+histograms), (+split search), (+row routing) over the SAME
    staged bins with in-graph `lax.scan` repetition and ONE value fetch
    each; consecutive differences are the phase costs. Histogram/split
    cost is data-independent (one-hot compares run regardless of node
    assignment), so the prefix subtraction stays valid even though only
    the full program routes rows. The routing phase runs the SHIPPED
    `trainer.route_rows_level` — the measured line is the shipped code."""
    import jax
    import jax.numpy as jnp
    from mmlspark_tpu.models.gbdt import objectives as obj_mod
    from mmlspark_tpu.models.gbdt import trainer as tr
    from mmlspark_tpu.ops.histogram import node_feature_histograms

    n, F = d_bins.shape
    B = params.max_bin + 1
    depth = params.max_depth
    cfg = tr.TreeConfig(n_features=F, n_bins=B, max_depth=depth,
                        num_leaves=params.num_leaves,
                        min_data_in_leaf=params.min_data_in_leaf)
    fmask = jnp.ones(F, bool)
    row = jnp.arange(n, dtype=jnp.int32)

    def interleave(left, sub):
        return jnp.stack([left, sub], axis=1).reshape(
            left.shape[0] * 2, *left.shape[1:])

    def make(stage):
        @jax.jit
        def run(margin):
            def body(carry, i):
                marg = margin * (1.0 + i * 1e-6)
                g, h = obj_mod.binary_grad_hess(marg, d_y, 1.0)
                acc = carry + g.sum() + h.sum()
                if stage == "objective":
                    return acc, None
                bins_t = d_bins.T
                node_of_row = jnp.zeros(n, jnp.int32)
                for d in range(depth):
                    level_base = 2 ** d - 1
                    m = 2 ** d
                    node_local = node_of_row - level_base
                    active = (node_local >= 0) & (node_local < m)
                    if d == 0:
                        hg, hh, hc = node_feature_histograms(
                            d_bins, g, h, node_local, active, 1, B)
                    else:
                        # mirror sibling subtraction: left children only,
                        # synthetic node ids when routing isn't in the
                        # prefix (kernel cost is node-independent)
                        if stage == "route":
                            nl, act = node_local // 2, \
                                active & (node_local % 2 == 0)
                        else:
                            nl = jax.lax.rem(row, m // 2)
                            act = jnp.ones(n, bool)
                        lg, lh, lc = node_feature_histograms(
                            d_bins, g, h, nl, act, m // 2, B)
                        hg = interleave(lg, lg)
                        hh = interleave(lh, lh)
                        hc = interleave(lc, lc)
                    acc = acc + hg.sum() + hh.sum() + hc.sum()
                    if stage == "hist":
                        continue
                    pg, ph, pc = (hg[:, 0].sum(-1), hh[:, 0].sum(-1),
                                  hc[:, 0].sum(-1))
                    gain, feat, thr, is_cat, words = \
                        tr._best_splits_for_level(hg, hh, hc, fmask, cfg,
                                                  pg, ph, pc)
                    acc = acc + jnp.where(jnp.isfinite(gain), gain,
                                          0.0).sum() + feat.sum()
                    if stage == "split":
                        continue
                    node_of_row = tr.route_rows_level(
                        bins_t, node_of_row, node_local, feat, thr,
                        jnp.isfinite(gain), level_base, m)
                if stage == "route":
                    acc = acc + node_of_row.sum()
                return acc, None
            out, _ = jax.lax.scan(body, jnp.float32(0), jnp.arange(iters))
            return out
        return run

    margin = jnp.zeros(n, jnp.float32)
    chain = {}
    for stage in ("objective", "hist", "split", "route"):
        fn = make(stage)
        float(fn(margin))                     # compile + warm
        t0 = time.time()
        float(fn(margin))
        chain[stage] = (time.time() - t0) / iters * 1000.0
    out = {"objective_ms_per_iter": round(chain["objective"], 3)}
    for name, hi, lo in (("histogram_ms_per_iter", "hist", "objective"),
                         ("split_ms_per_iter", "split", "hist"),
                         ("routing_ms_per_iter", "route", "split")):
        out[name] = round(max(chain[hi] - chain[lo], 0.0), 3)
    out["chain_ms_per_iter"] = {k: round(v, 3) for k, v in chain.items()}
    return out


def _planes_ab(staged, x, y, params, n_iters: int = 5):
    """A/B of the level-invariant precomputed one-hot planes route vs the
    default routed family, on the already-staged bins: two short fits per
    arm (compile+warm, then timed). The plan build (once per fit) rides
    inside the planes arm's time, as it does in production. This is the
    measurement that decides whether the planes route becomes the default
    next round."""
    import dataclasses
    from mmlspark_tpu.models.gbdt.boosting import fit_booster
    p_ab = dataclasses.replace(params, num_iterations=n_iters)
    out = {"iters": n_iters}
    prev = os.environ.get("MMLSPARK_TPU_HIST")
    try:
        for tag, env in (("routed", "auto"), ("planes", "planes")):
            os.environ["MMLSPARK_TPU_HIST"] = env
            fit_booster(x, y, p_ab, prebinned=staged)
            t0 = time.time()
            fit_booster(x, y, p_ab, prebinned=staged)
            out[f"{tag}_s"] = round(time.time() - t0, 4)
    finally:
        if prev is None:
            os.environ.pop("MMLSPARK_TPU_HIST", None)
        else:
            os.environ["MMLSPARK_TPU_HIST"] = prev
    out["planes_speedup"] = round(out["routed_s"]
                                  / max(out["planes_s"], 1e-9), 3)
    return out


def _hist_traffic_bytes(n_rows: int, n_feat: int, depth: int,
                        n_iters: int) -> float:
    """Lower bound on histogram-pass HBM traffic: every level re-reads the
    (n, F) uint8 bins plus f32 grad/hess/count per row; histogram outputs
    (m x F x B x 3 x 4B) are KB-scale next to that and ignored."""
    return float(depth) * n_rows * (n_feat + 12) * n_iters


def run_shape(n_rows: int, n_feat: int, max_bin: int, n_iters: int,
              copy_gbps: float, metric: str):
    """Train at one shape; return the anchored result dict."""
    from mmlspark_tpu.models.gbdt.boosting import BoostParams, fit_booster
    from mmlspark_tpu.ops import binning

    rng = np.random.default_rng(0)
    x = rng.normal(size=(n_rows, n_feat)).astype(np.float32)
    w = rng.normal(size=n_feat)
    y = (x @ w + rng.normal(scale=0.5, size=n_rows) > 0).astype(np.float32)
    params = BoostParams(objective="binary", num_iterations=n_iters,
                         num_leaves=31,
                         max_depth=int(os.environ.get("BENCH_DEPTH", 5)),
                         max_bin=max_bin, min_data_in_leaf=20)
    # stage data on device once (dataset binning + H2D copy are one-time
    # costs in any real pipeline); labels stage too — prebinned's third
    # element — so the timed region is the training loop itself
    # (BENCH_MODE=gbdt_e2e measures the full ingest->train path with the
    # copies included)
    import jax
    from mmlspark_tpu.reliability.metrics import reliability_metrics
    reliability_metrics.reset("gbdt.hist.")   # per-shape route counters
    mapper = binning.fit_bins(x, max_bin=params.max_bin, seed=0)
    d_bins = binning.apply_bins_device(mapper, x)
    d_y = jax.device_put(y)
    d_bins.block_until_ready()
    staged = (mapper, d_bins, d_y)
    # warmup with IDENTICAL shapes/params: compiles the fused boosting scan
    # (cached to .jax_cache for later rounds); the timed run is steady-state.
    # warmup-minus-steady is the compile+trace cost estimate the compile
    # telemetry rides into the output (zero-ish on cache-hot rounds).
    t0 = time.time()
    fit_booster(x, y, params, prebinned=staged)
    warmup_s = time.time() - t0
    # goodput/MFU accounting on the TIMED fit (telemetry/goodput.py):
    # the fused loop drives the clock per chunk and books the packed
    # fetch as the device phase. MFU degrades to None here — the fused
    # scan compiles through bare jit (no cost analysis recorded), and a
    # guessed flops denominator would be worse than an honest absence.
    from mmlspark_tpu.telemetry.goodput import StepClock
    clock = StepClock()
    t0 = time.time()
    booster, base, _ = fit_booster(x, y, params, prebinned=staged,
                                   step_clock=clock)
    elapsed = time.time() - t0

    from mmlspark_tpu.telemetry import perf as tperf
    rips = n_rows * n_iters / elapsed
    traffic = _hist_traffic_bytes(n_rows, n_feat, params.max_depth, n_iters)
    out = {
        "metric": metric, "value": round(rips, 1), "unit": "rows*iters/s",
        "vs_baseline": round(rips / BASELINE_ROWS_ITERS_PER_SEC, 4),
        **_device_stamp(),
        "shape": f"{n_rows}x{n_feat}x{max_bin + 1}bins x{n_iters}it",
        "elapsed_s": round(elapsed, 3),
        "warmup_s": round(warmup_s, 3),
        "compile_s_est": round(max(warmup_s - elapsed, 0.0), 3),
        "ns_per_row_level": round(
            elapsed * 1e9 / (n_rows * n_iters * params.max_depth), 3),
        "hist_bytes_per_sec": round(traffic / elapsed, 1),
        "bound": "vpu-onehot (see ops/histogram_pallas.py)",
    }
    # has_planes mirrors what THIS fit did (fit_booster builds the plan
    # when the env asks for it), so the claimed table matches the
    # routes-taken counters below on a planes run
    out["hist_routes"] = _hist_route_table(
        params.max_bin + 1, params.max_depth,
        has_planes=os.environ.get("MMLSPARK_TPU_HIST") == "planes")
    # routes ACTUALLY instantiated (trace-time gbdt.hist.route.* counters)
    # vs the table above — on a CPU run these say "xla" while the table
    # says what the TPU kernel family would pick
    out["hist_routes_taken"] = {
        k.rsplit(".", 1)[-1]: v
        for k, v in reliability_metrics.snapshot().items()
        if k.startswith("gbdt.hist.route.")}
    # per-phase breakdown (round 6): "bound" claims trace to a measured
    # line instead of a docstring assertion
    if os.environ.get("BENCH_PHASES", "1") != "0":
        phases = _phase_breakdown(d_bins, d_y, params)
        out["phases"] = phases
        keyed = {k: v for k, v in phases.items()
                 if k.endswith("_ms_per_iter") and isinstance(v, float)}
        if keyed:
            worst = max(keyed, key=keyed.get)
            out["bound"] = (f"{worst.replace('_ms_per_iter', '')} "
                            f"(measured per-phase, BENCH_EXTRA_r06.json)")
    # process-wide compile log (telemetry/perf.py): AOT compiles this
    # run recorded with cost analysis; recompiles must stay 0
    cstats = tperf.compile_stats()
    cstats["seconds"] = round(cstats["seconds"], 3)
    out["compile"] = cstats
    gsnap = clock.snapshot()
    out["goodput"] = round(gsnap["goodput"], 4)
    out["mfu"] = gsnap["mfu"]   # None: documented degrade (see above)
    if gsnap["mfu"] is None:
        out["mfu_note"] = ("no cost analysis for the bare-jit fused scan; "
                           "set flops_per_step/MMLSPARK_TPU_PEAK_TFLOPS "
                           "or compile via telemetry.perf to enable")
    out["step_phases"] = {k: round(v, 4)
                          for k, v in gsnap["phases"].items()}
    if copy_gbps > 0:
        out["measured_copy_gbps"] = round(copy_gbps, 1)
        out["hbm_utilization"] = round(
            tperf.hbm_utilization(traffic / elapsed, copy_gbps), 4)
    # per-region roofline block (telemetry/profiler.py): the measured
    # per-phase walls joined with the analytic histogram traffic against
    # the MEASURED copy bandwidth — the whole-fit hbm_utilization above
    # says "1.8% idle", this block says WHICH kernel owns the headroom
    # (ROADMAP item 1's honesty metric made per-kernel). FLOPs peaks come
    # from env/chip table and stay absent when unknown — never guessed.
    from mmlspark_tpu.telemetry import profiler as tprof
    peaks = None
    if copy_gbps > 0:
        peaks = {"hbm_bytes_per_s": copy_gbps * 1e9,
                 "source": "measured-copy"}
    ledger = tprof.RooflineLedger(peaks=peaks)
    phase_region = {"histogram": "gbdt.hist", "split": "gbdt.split",
                    "routing": "gbdt.route"}
    keyed = {k: v for k, v in out.get("phases", {}).items()
             if k.endswith("_ms_per_iter") and isinstance(v, float)}
    for phase, region in phase_region.items():
        ms = keyed.get(f"{phase}_ms_per_iter")
        if ms is not None and ms > 0.0:
            ledger.note_region(region, ms / 1000.0 * n_iters,
                               occurrences=n_iters,
                               source="bench-phase")
    # the analytic per-iteration histogram traffic is the hist
    # region's bytes cost; split/route carry no cost claim, so their
    # rows report measured time only (utilization absent, not 0)
    ledger.set_cost("gbdt.hist", bytes_accessed=traffic / n_iters)
    roofline = ledger.export()
    roofline.pop("ops", None)   # no capture ran: drop the empty table
    out["roofline"] = roofline
    return out, booster, x, y, staged


def _bench_gbdt_e2e():
    """End-to-end fit wall clock: RAW rows -> trained booster, stage by
    stage (round-4 verdict item 4 — the reference's user-visible number is
    whole-fit including dataset build, TrainUtils.scala:33-186). Two
    shapes: the 8M x 32 headline and the 1M x 128 x 255 wide regime; the
    wide shape also ingests from CSV through the native C++ parser.

    The loop-only number the headline reports stays valid alongside this
    one: the split shows WHERE end-to-end time goes. h2d_s is reported
    separately next to its byte count, and e2e_minus_h2d_s beside it."""
    import jax
    from mmlspark_tpu.models.gbdt.boosting import BoostParams, fit_booster
    from mmlspark_tpu.ops import binning
    from mmlspark_tpu.native import apply_bins_native

    for n_rows, n_feat, max_bin, n_iters, tag in (
            (8_000_000, 32, 63, 20, "8m_32f"),
            (1_000_000, 128, 254, 10, "wide_128f_255b")):
        rng = np.random.default_rng(0)
        stages = {}
        t0 = time.time()
        x = rng.normal(size=(n_rows, n_feat)).astype(np.float32)
        w = rng.normal(size=n_feat)
        y = (x @ w + rng.normal(scale=0.5, size=n_rows) > 0).astype(
            np.float32)
        stages["synth_data_s"] = round(time.time() - t0, 3)

        params = BoostParams(objective="binary", num_iterations=n_iters,
                             num_leaves=31, max_depth=5, max_bin=max_bin,
                             min_data_in_leaf=20)
        t0 = time.time()
        mapper = binning.fit_bins(x, max_bin=max_bin, seed=0)
        stages["fit_bins_s"] = round(time.time() - t0, 3)
        t0 = time.time()
        # same call shape test_native_apply_bins_matches_python pins
        bins_host = apply_bins_native(x, mapper.upper_bounds[:, :-1],
                                      mapper.upper_bounds.shape[1])
        if bins_host is None:
            raise SystemExit("bench: native/kernels.cpp failed to build; "
                             "apply_bins_native_s would time numpy")
        stages["apply_bins_native_s"] = round(time.time() - t0, 3)
        t0 = time.time()
        d_bins = jax.device_put(bins_host)
        d_y = jax.device_put(y)            # labels are part of the upload
        jax.block_until_ready((d_bins, d_y))
        stages["h2d_s"] = round(time.time() - t0, 3)
        stages["h2d_bytes"] = int(bins_host.nbytes + y.nbytes)

        staged = (mapper, d_bins, d_y)
        fit_booster(x, y, params, prebinned=staged)   # compile
        t0 = time.time()
        booster, _, _ = fit_booster(x, y, params, prebinned=staged)
        stages["train_loop_s"] = round(time.time() - t0, 3)

        e2e = (stages["fit_bins_s"] + stages["apply_bins_native_s"]
               + stages["h2d_s"] + stages["train_loop_s"])
        rips = n_rows * n_iters / e2e
        print(json.dumps({
            "metric": f"gbdt_e2e_fit_{tag}", "value": round(e2e, 3),
            "unit": "s", **_device_stamp(),
            "vs_baseline": round(rips / BASELINE_ROWS_ITERS_PER_SEC, 4),
            "rows_iters_per_sec_e2e": round(rips, 1),
            "e2e_minus_h2d_s": round(e2e - stages["h2d_s"], 3),
            "shape": f"{n_rows}x{n_feat}x{max_bin + 1}bins x{n_iters}it",
            "n_trees": booster.n_trees, **stages}))

    # CSV ingest through the native parser at a CSV-sized shape: the
    # reference's fit starts from a DataFrame that was itself read from
    # storage; this measures our equivalent front door (io/sources.py)
    import tempfile
    from mmlspark_tpu.io.sources import read_csv
    n_csv, f_csv = 200_000, 32
    rng = np.random.default_rng(1)
    xc = rng.normal(size=(n_csv, f_csv)).astype(np.float32)
    with tempfile.NamedTemporaryFile("w", suffix=".csv", delete=False) as f:
        f.write(",".join(f"c{j}" for j in range(f_csv)) + "\n")
        np.savetxt(f, xc, delimiter=",", fmt="%.6f")
        path = f.name
    t0 = time.time()
    table = read_csv(path)
    csv_s = time.time() - t0
    os.unlink(path)
    mat = np.stack([np.asarray(table[c]) for c in table.columns], axis=1)
    assert mat.shape == (n_csv, f_csv)
    print(json.dumps({
        "metric": "csv_ingest_native_rows_per_sec",
        "value": round(n_csv / csv_s, 1), "unit": "rows/s",
        "vs_baseline": 0.0, "cols": f_csv, **_device_stamp(),
        "mb_per_sec": round(xc.nbytes / csv_s / 1e6, 1)}))


def _bench_ingest():
    """Parallel host ingest pipeline (data/) vs the recorded single-core
    path: the round-5 verdict measured the 8M x 32 end-to-end fit as 9.7 s
    of host binning in front of 1.85 s of device training. This section
    times, at the same shape:

    - sequential_s: the legacy serial staging — host apply_bins (native
      C++ if the host has a compiler, else numpy) then ONE whole-matrix
      device_put, stages strictly in sequence;
    - pipeline_s: data.stage_binned — chunked apply_bins on the worker
      pool, each chunk's device_put overlapped with the next chunk's
      binning behind a bounded prefetch queue;

    asserts the parallel bin matrix is BIT-IDENTICAL to the sequential
    one, then trains the same short booster on both staged matrices so
    the artifact shows device step time unchanged. Queue/stage metrics
    from reliability.metrics ride along in the JSON."""
    import jax
    import jax.numpy as jnp
    from mmlspark_tpu.data import IngestOptions, stage_binned
    from mmlspark_tpu.models.gbdt.boosting import BoostParams, fit_booster
    from mmlspark_tpu.native import apply_bins_native
    from mmlspark_tpu.ops import binning
    from mmlspark_tpu.reliability.metrics import reliability_metrics

    n_rows, n_feat, max_bin = N_ROWS, N_FEATURES, 63
    n_iters = int(os.environ.get("BENCH_INGEST_ITERS", 5))
    rng = np.random.default_rng(0)
    x = rng.normal(size=(n_rows, n_feat)).astype(np.float32)
    w = rng.normal(size=n_feat)
    y = (x @ w + rng.normal(scale=0.5, size=n_rows) > 0).astype(np.float32)

    mapper = binning.fit_bins(x, max_bin=max_bin, seed=0)

    def sync(arr):
        arr.block_until_ready()

    # -- sequential recorded path -------------------------------------------
    t0 = time.time()
    bins_seq = apply_bins_native(x, mapper.upper_bounds[:, :-1],
                                 mapper.upper_bounds.shape[1])
    native = bins_seq is not None
    if bins_seq is None:
        bins_seq = binning.apply_bins(mapper, x)
    bin_seq_s = time.time() - t0
    t0 = time.time()
    d_seq = jax.device_put(bins_seq)
    sync(d_seq)
    h2d_seq_s = time.time() - t0
    sequential_s = bin_seq_s + h2d_seq_s

    # -- pipelined path ------------------------------------------------------
    opts = IngestOptions(num_workers=int(os.environ.get("BENCH_INGEST_WORKERS",
                                                        0)))
    n_workers = opts.pool().num_workers
    reliability_metrics.reset("data.")
    t0 = time.time()
    d_par = stage_binned(mapper, x, opts)
    sync(d_par)
    pipeline_s = time.time() - t0

    identical = bool(np.array_equal(np.asarray(d_par), bins_seq))

    # -- device step time on both staged matrices ---------------------------
    params = BoostParams(objective="binary", num_iterations=n_iters,
                         num_leaves=31, max_depth=5, max_bin=max_bin,
                         min_data_in_leaf=20)
    d_y = jax.device_put(y)
    fit_booster(x, y, params, prebinned=(mapper, d_seq, d_y))   # compile
    t0 = time.time()
    fit_booster(x, y, params, prebinned=(mapper, d_seq, d_y))
    train_seq_s = time.time() - t0
    t0 = time.time()
    fit_booster(x, y, params, prebinned=(mapper, d_par, d_y))
    train_par_s = time.time() - t0

    snap = reliability_metrics.snapshot()
    # what the host binning ADDS to the staging critical path once it
    # overlaps the transfer (vs the recorded 9.7 s where it strictly
    # PRECEDED it): on a transfer-bound link this approaches zero even on
    # a 1-core host; on a fast link it is the multi-worker binning time
    binning_added = max(pipeline_s - h2d_seq_s, 0.0)
    print(json.dumps({
        "metric": "ingest_host_binning_wall_s", "value": round(pipeline_s, 3),
        "unit": "s",
        # >1 means the pipeline beats the serial staging it replaces
        "vs_baseline": round(sequential_s / max(pipeline_s, 1e-9), 3),
        "shape": f"{n_rows}x{n_feat}x{max_bin + 1}bins",
        "sequential_s": round(sequential_s, 3),
        "sequential_bin_s": round(bin_seq_s, 3),
        "sequential_h2d_s": round(h2d_seq_s, 3),
        "pipeline_s": round(pipeline_s, 3),
        "speedup": round(sequential_s / max(pipeline_s, 1e-9), 3),
        "binning_wall_added_s": round(binning_added, 3),
        "binning_speedup_vs_serial": round(
            bin_seq_s / max(binning_added, 1e-9), 3),
        "bit_identical": identical,
        "num_workers": n_workers,
        "sequential_binner": "native_cpp" if native else "numpy",
        "train_loop_seq_staged_s": round(train_seq_s, 3),
        "train_loop_pipeline_staged_s": round(train_par_s, 3),
        "bin_chunk_seconds_total": round(
            snap.get("data.bin_chunk.seconds", 0.0), 3),
        "bin_chunks": snap.get("data.bin_chunk.count", 0),
        "prefetch_put_seconds_total": round(
            snap.get("data.prefetch.put.seconds", 0.0), 3),
        "prefetch_full_events": snap.get("data.prefetch.full", 0),
        "prefetch_stalls": snap.get("data.prefetch.stalls", 0)}))
    assert identical, "parallel binning diverged from the sequential path"


def _bench_oocore():
    """Out-of-core A/B (BENCH_MODE=oocore): the same fit staged in-core vs
    streamed through data/oocore.py under a residency budget of 1/8th the
    raw dataset, from a memory-mapped .npy source.

    Prints, in order (driver records the last line; benchdiff harvests
    them all):
    - comm.gbdt.vote.{ops,bytes}: measured all-reduce traffic of the
      voting_parallel distributed fit at BENCH_OOCORE_FEATURES (>= 64)
      next to the full data_parallel traffic it replaces, read from the
      AotCache compile records of the executables the fits actually ran —
      born lower_better + backend-stamped so CPU rounds can't pollute TPU
      trajectories; asserts the >= 4x byte reduction;
    - oocore_stage_wall_s: streaming vs in-core staging walls,
      bit-identity assert on the final model arrays, peak-RSS readout,
      and the staging-overlap counters (bin chunks, prefetch stalls).

    BENCH_OOCORE_ROWS is the one knob that scales this to the
    larger-than-budget smoke (tests/test_oocore.py runs the same path
    `slow`-marked at a capped max_resident_bytes)."""
    import resource
    import tempfile

    import jax
    from mmlspark_tpu.data import OocoreOptions
    from mmlspark_tpu.models.gbdt.boosting import BoostParams, fit_booster
    from mmlspark_tpu.models.gbdt.distributed import fit_booster_distributed
    from mmlspark_tpu.reliability.metrics import reliability_metrics
    from mmlspark_tpu.telemetry import names as tnames
    from mmlspark_tpu.telemetry import perf as tperf

    backend = jax.default_backend()
    n_rows = int(os.environ.get("BENCH_OOCORE_ROWS", 400_000))
    n_feat = int(os.environ.get("BENCH_OOCORE_FEATURES", 64))
    n_iters = int(os.environ.get("BENCH_OOCORE_ITERS", 5))
    rng = np.random.default_rng(0)
    x = rng.normal(size=(n_rows, n_feat)).astype(np.float32)
    w = rng.normal(size=n_feat)
    y = (x @ w + rng.normal(scale=0.5, size=n_rows) > 0).astype(np.float32)
    params = BoostParams(objective="binary", num_iterations=n_iters,
                         num_leaves=31, max_depth=5, max_bin=63,
                         min_data_in_leaf=20)

    # -- voting-vs-full distributed traffic (the perf headline) -------------
    def _fit_traffic(parallelism):
        fit_booster_distributed(x, y, params, parallelism=parallelism,
                                top_k=2)
        ops = bts = 0
        for r in tperf.get_compile_log().records():
            if str(r.get("label", "")).startswith("gbdt.") and \
                    str(r.get("label", "")).endswith(parallelism):
                ar = ((r.get("analysis") or {}).get("collectives")
                      or {}).get("all-reduce", {})
                ops += int(ar.get("ops", 0))
                bts += int(ar.get("bytes", 0))
        return ops, bts

    full_ops, full_bytes = _fit_traffic("data_parallel")
    vote_ops, vote_bytes = _fit_traffic("voting_parallel")
    reduction = full_bytes / max(vote_bytes, 1)
    print(json.dumps({"metric": tnames.COMM_GBDT_VOTE_OPS,
                      "value": float(vote_ops), "lower_better": True,
                      "backend": backend, "full_ops": full_ops,
                      "shape": f"{n_rows}x{n_feat}"}))
    print(json.dumps({"metric": tnames.COMM_GBDT_VOTE_BYTES,
                      "value": float(vote_bytes), "lower_better": True,
                      "backend": backend, "full_bytes": full_bytes,
                      "bytes_reduction_x": round(reduction, 2),
                      "shape": f"{n_rows}x{n_feat}"}))
    assert n_feat < 64 or reduction >= 4.0, (
        f"voting all-reduce bytes reduction {reduction:.2f}x < 4x at "
        f"F={n_feat}")

    # -- in-core vs streaming staging A/B -----------------------------------
    t0 = time.time()
    b_ref, base_ref, _ = fit_booster(x, y, params)
    in_core_s = time.time() - t0
    rss_in_core_kb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "x.npy")
        np.save(path, x)
        budget = max(x.nbytes // 8, 1 << 20)
        oo = OocoreOptions(max_resident_bytes=budget,
                           cache_path=os.path.join(d, "bins.npy"),
                           num_workers=int(os.environ.get(
                               "BENCH_INGEST_WORKERS", 0)))
        reliability_metrics.reset("data.")
        t0 = time.time()
        b_oo, base_oo, _ = fit_booster(path, y, params, oocore=oo)
        oocore_s = time.time() - t0
    identical = (base_ref == base_oo) and all(
        np.array_equal(np.asarray(getattr(b_ref, f)),
                       np.asarray(getattr(b_oo, f)))
        for f in b_ref._fields)
    snap = reliability_metrics.snapshot()
    peak_rss_kb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
    print(json.dumps({
        "metric": "oocore_stage_wall_s",
        "value": round(oocore_s, 3), "unit": "s", "backend": backend,
        "shape": f"{n_rows}x{n_feat}",
        "in_core_s": round(in_core_s, 3),
        "oocore_s": round(oocore_s, 3),
        "bit_identical": bool(identical),
        "max_resident_bytes": int(budget),
        "resident_bound_bytes": snap.get(
            tnames.DATA_OOCORE_RESIDENT_BYTES, 0),
        "staged_chunks": snap.get(tnames.DATA_OOCORE_CURSOR, 0),
        "raw_dataset_bytes": int(x.nbytes),
        "peak_rss_mb_in_core": round(rss_in_core_kb / 1024.0, 1),
        "peak_rss_mb": round(peak_rss_kb / 1024.0, 1),
        "bin_chunks": snap.get("data.bin_chunk.count", 0),
        "bin_chunk_seconds_total": round(
            snap.get("data.bin_chunk.seconds", 0.0), 3),
        "prefetch_stalls": snap.get("data.prefetch.stalls", 0),
        "prefetch_full_events": snap.get("data.prefetch.full", 0),
        "vote_bytes_reduction_x": round(reduction, 2)}))
    assert identical, "out-of-core staging diverged from the in-core fit"


def _bench_elastic():
    """Elastic kill-one-host run (BENCH_MODE=elastic): three simulated
    hosts fit out-of-core with fleet checkpointing; one host is killed
    (stops beating) mid-run and the survivors detect, shrink, and resume
    on the REAL monotonic clock this time — tests/test_elastic.py pins
    the same flow on an injected clock.

    Prints one headline record (born lower_better, benchdiff derives
    `elastic.{resume_s,lost_work_fraction}` gates from its fields):
    - elastic_detect_s: last beat of the dead host -> lease-expiry
      verdict on the observer (includes the lease budget by design);
    - resume_s: verdict -> resumed fit running on the shrunk mesh
      (dominated by the honest recompile for the survivor device set);
    - lost_work_fraction: boosting iterations finished at the kill but
      not covered by the committed fleet manifest, over iterations
      finished — the two-phase-commit cadence's price."""
    import tempfile

    import jax
    from mmlspark_tpu.data import ChunkPlanner, ChunkStager, OocoreOptions
    from mmlspark_tpu.models.gbdt.booster import Booster
    from mmlspark_tpu.models.gbdt.boosting import BoostParams
    from mmlspark_tpu.models.gbdt.distributed import fit_booster_distributed
    from mmlspark_tpu.ops import binning
    from mmlspark_tpu.parallel.cluster import Heartbeat
    from mmlspark_tpu.parallel.mesh import data_mesh
    from mmlspark_tpu.reliability import (ElasticPlan, FleetCheckpoint,
                                          HostLeases)
    from mmlspark_tpu.reliability.metrics import MetricsRegistry

    backend = jax.default_backend()
    dph = max(jax.device_count() // 3, 1)       # devices per simulated host
    n_rows = int(os.environ.get("BENCH_ELASTIC_ROWS", 120_000))
    n_rows -= n_rows % (6 * dph)                # divides both mesh widths
    n_feat = int(os.environ.get("BENCH_ELASTIC_FEATURES", 32))
    total_iters = int(os.environ.get("BENCH_ELASTIC_ITERS", 8))
    kill_at = 5                                 # iterations done at the kill
    commit_every = 3                            # manifest cadence
    lease_s = float(os.environ.get("BENCH_ELASTIC_LEASE_S", 0.5))
    rng = np.random.default_rng(0)
    x = rng.normal(size=(n_rows, n_feat)).astype(np.float32)
    wv = rng.normal(size=n_feat)
    y = (x @ wv + rng.normal(scale=0.5, size=n_rows) > 0).astype(np.float32)
    params = BoostParams(objective="binary", num_iterations=kill_at,
                         num_leaves=31, max_depth=5, max_bin=63,
                         min_data_in_leaf=20)

    with tempfile.TemporaryDirectory() as d:
        mapper = binning.fit_bins(x, max_bin=63)
        x_path = os.path.join(d, "x.npy")
        np.save(x_path, x)
        opts = OocoreOptions(max_resident_bytes=max(x.nbytes // 8, 1 << 20),
                             cache_path=os.path.join(d, "bins.npy"))
        n_chunks = len(ChunkStager(x_path, mapper, opts, only=set()).source)
        planner = ChunkPlanner(n_chunks, hosts=[0, 1, 2], faults=None)
        fleets = {i: FleetCheckpoint(os.path.join(d, "ck"), i, faults=None)
                  for i in range(3)}
        hb = {i: Heartbeat(os.path.join(d, "hb"), process_id=i)
              for i in range(3)}

        def stage_host(h):
            todo = set(planner.pending(h))
            if todo:
                ChunkStager(x_path, mapper, opts, only=todo).stage()
                for i in todo:
                    planner.mark_done(i)

        stage_host(0)
        stage_host(1)                           # host 2 dies mid-staging

        committed = {}

        def ck_fn(it, booster, fit_base, final=False, margin=None,
                  rng_key=None):
            if it % commit_every or final:
                return
            payload = {"booster": booster.save_model_string(),
                       "iteration": int(it), "base": float(fit_base),
                       "margin": np.asarray(margin, np.float32),
                       "rng_key": np.asarray(rng_key)}
            committed.clear()
            committed.update(payload)
            for pid in (0, 1, 2):
                fleets[pid].save_shard(it, payload)
            assert fleets[0].commit(it, [0, 1, 2])

        # "the killed fleet": runs kill_at of total_iters iterations
        fit_booster_distributed(x, y, params, mesh=data_mesh(3 * dph),
                                checkpoint_fn=ck_fn,
                                checkpoint_interval=commit_every)
        committed_it = int(committed["iteration"])

        for i in range(3):
            hb[i].beat(1)
        t_last_beat = time.monotonic()          # host 2's final beat
        leases = HostLeases(hb[0], lease_timeout_s=lease_s, faults=None,
                            metrics=MetricsRegistry())
        leases.check()
        dead = []
        while not dead:                         # the survivors' beat loop
            hb[0].beat(2)
            hb[1].beat(2)
            dead = leases.check()
            time.sleep(0.02)
        detect_s = time.monotonic() - t_last_beat
        assert dead == [2]

        t0 = time.monotonic()
        elastic = ElasticPlan(planner=planner, fleet=fleets[1],
                              devices_per_host=dph,
                              metrics=MetricsRegistry())
        elastic.shrink([2])
        stage_host(0)                           # re-stage inherited chunks
        stage_host(1)
        step, _manifest, payload = elastic.resume()
        p_rem = BoostParams(objective="binary",
                            num_iterations=total_iters - committed_it,
                            num_leaves=31, max_depth=5, max_bin=63,
                            min_data_in_leaf=20)
        resumed = fit_booster_distributed(
            x, y, p_rem, mesh=elastic.mesh(),
            init_booster=Booster.load_model_string(str(payload["booster"])),
            init_base=float(payload["base"]),
            init_margin=np.asarray(payload["margin"], np.float32),
            init_rng_key=np.asarray(payload["rng_key"]),
            iter_offset=committed_it)
        resume_s = time.monotonic() - t0
        assert step == committed_it
        assert resumed[0].n_trees == total_iters

    lost = (kill_at - committed_it) / float(kill_at)
    print(json.dumps({
        "metric": "elastic_detect_s", "value": round(detect_s, 3),
        "unit": "s", "lower_better": True, "backend": backend,
        "shape": f"{n_rows}x{n_feat}",
        "resume_s": round(resume_s, 3),
        "lost_work_fraction": round(lost, 4),
        "lease_timeout_s": lease_s,
        "committed_iteration": committed_it,
        "iterations_at_kill": kill_at,
        "total_iterations": total_iters,
        "survivor_mesh_devices": 2 * dph}))


def _bench_serving():
    """Serving hot path, closed-loop (round-4 verdict item 5 grown into the
    fast-path A/B): a REAL fitted GBDT booster behind `serve_pipeline`,
    measured by ONE harness (io/loadgen.run_load, N keep-alive clients each
    firing its next request when the previous answers) across:

    - legacy_*: the pre-overhaul transform (fast_path=False — per-row JSON
      dicts, per-batch Table + uncompiled model.transform) in coalesced
      microbatch mode: the baseline the >= 2x acceptance bar is against;
    - coalesced_*: the compiled-plan fast path, microbatch + batch_linger;
    - continuous_*: batch-of-1 continuous mode (the reference's sub-ms
      executor-local scenario, docs/mmlspark-serving.md:93,142-146), plus a
      serial single-request p50/p99.

    Each section also reports the serving.request.{queue,transform,reply,
    e2e} percentiles from reliability_metrics — the same numbers a
    production operator reads — and the plan-cache hit/miss counts
    (misses == distinct shape buckets: the zero-recompile invariant).
    Quiet-host numbers; tests/test_io_http.py pins the contended floors."""
    import json as _json
    from mmlspark_tpu.core import Table
    from mmlspark_tpu.models.gbdt.estimators import GBDTClassifier
    from mmlspark_tpu.io.loadgen import run_load
    from mmlspark_tpu.io.serving import serve_pipeline
    from mmlspark_tpu.reliability.metrics import reliability_metrics

    rng = np.random.default_rng(0)
    n, f = 20_000, 16
    x = rng.normal(size=(n, f)).astype(np.float32)
    y = (x[:, 0] + x[:, 1] > 0).astype(np.float32)
    model = GBDTClassifier(num_iterations=20, max_depth=5).fit(
        Table({"features": x, "label": y}))
    body = _json.dumps({"features": [0.1] * f})

    def closed_loop(tag, mode, fast_path, linger_ms=0.0, n_clients=16,
                    per_client=125):
        reliability_metrics.reset("serving.")
        server, q = serve_pipeline(model, input_cols=["features"],
                                   mode=mode, max_batch=256,
                                   batch_linger_ms=linger_ms,
                                   fast_path=fast_path)
        host, port = server._httpd.server_address[:2]
        try:
            res = run_load(host, port, body, n_clients=n_clients,
                           per_client=per_client)
            assert not res.errors, res.errors[:3]
        finally:
            q.stop()
            server.stop()
        snap = reliability_metrics.snapshot()
        sect = {f"{tag}_req_per_sec": round(res.req_per_sec, 1),
                f"{tag}_p50_ms": round(res.p50_ms, 2),
                f"{tag}_p99_ms": round(res.p99_ms, 2)}
        for stage in ("queue", "transform", "reply", "e2e"):
            sect[f"{tag}_{stage}_p50_ms"] = round(
                snap.get(f"serving.request.{stage}.p50", 0.0), 3)
            sect[f"{tag}_{stage}_p99_ms"] = round(
                snap.get(f"serving.request.{stage}.p99", 0.0), 3)
        if fast_path:
            sect[f"{tag}_plan_hits"] = snap.get("serving.plan.hits", 0)
            sect[f"{tag}_plan_misses"] = snap.get("serving.plan.misses", 0)
        return res.req_per_sec, sect

    def ab_round():
        """One back-to-back legacy/coalesced pair. Pairing keeps both
        sides of a ratio under the SAME host load; a drifting contended
        host then moves the pair together, not the ratio."""
        legacy_rps, legacy_sect = closed_loop("legacy", "microbatch",
                                              fast_path=False)
        # linger 0 = adaptive drain-available coalescing: under
        # closed-loop load arrivals accumulate while the worker scores,
        # so batches form without spending latency budget — on this
        # 1-core host a positive linger only adds tail latency (it buys
        # occupancy for device-bound stages; see docs/serving.md)
        fast_rps, fast_sect = closed_loop("coalesced", "microbatch",
                                          fast_path=True, linger_ms=0.0)
        return (fast_rps / max(legacy_rps, 1e-9),
                legacy_rps, fast_rps, {**legacy_sect, **fast_sect})

    def ab_set():
        return sorted((ab_round() for _ in range(3)), key=lambda r: r[0])

    def spread_of(runs):
        speeds = [r[0] for r in runs]
        return (speeds[-1] - speeds[0]) / max(speeds[1], 1e-9)

    # deflake: MEDIAN of 3 paired A/B rounds. A contended host shows up
    # as a wide spread across rounds (the 2.1-2.5x wobble this section
    # used to report as a single draw); one quiet-host retry after a
    # settle pause keeps whichever set is tighter. The spread rides the
    # output either way, so the artifact says how noisy the host was.
    runs = ab_set()
    retried = False
    if spread_of(runs) > 0.35:
        retried = True
        time.sleep(2.0)          # let transient load pass
        again = ab_set()
        if spread_of(again) < spread_of(runs):
            runs = again
    speedup, legacy_rps, fast_rps, sect = runs[1]   # the median pair
    out = dict(sect)
    out["speedup_runs"] = [round(r[0], 3) for r in runs]
    out["speedup_spread"] = round(spread_of(runs), 3)
    out["speedup_retried"] = retried
    cont_rps, sect = closed_loop("continuous", "continuous", fast_path=True,
                                 n_clients=4, per_client=250)
    out.update(sect)
    out["speedup_vs_legacy"] = round(speedup, 2)

    # -- serial single-request latency, continuous mode ---------------------
    import urllib.request
    server, q = serve_pipeline(model, input_cols=["features"],
                               mode="continuous")
    try:
        url = server.address
        req = urllib.request.Request(
            url, data=body.encode(),
            headers={"Content-Type": "application/json"})
        urllib.request.urlopen(req, timeout=10).read()   # warm
        lat1 = []
        for _ in range(100):
            t0 = time.perf_counter()
            urllib.request.urlopen(
                urllib.request.Request(
                    url, data=body.encode(),
                    headers={"Content-Type": "application/json"}),
                timeout=10).read()
            lat1.append(time.perf_counter() - t0)
        lat1.sort()
        out["single_req_p50_ms"] = round(lat1[50] * 1000, 2)
        out["single_req_p99_ms"] = round(lat1[99] * 1000, 2)
    finally:
        q.stop()
        server.stop()

    # content-addressed version stamp (telemetry/lineage.py): benchdiff
    # trajectories can then tell a perf regression from a model swap —
    # same metric name, different model content, different version id
    from mmlspark_tpu.telemetry.lineage import model_version
    print(json.dumps({
        "metric": "serving_gbdt_model_req_per_sec",
        "value": out["coalesced_req_per_sec"], "unit": "req/s",
        # reference bar: 5k req/s sustained (docs/mmlspark-serving.md)
        "vs_baseline": round(out["coalesced_req_per_sec"] / 5000.0, 3),
        "model": "GBDTClassifier 20 trees depth<=5, 16 features",
        "model_version": model_version(model).version,
        **out}))


def _bench_workloads():
    """Fleet workloads closed-loop A/B (BENCH_MODE=workloads): both
    ISSUE-20 estimators fitted for real and served behind `serve_pipeline`
    under the same io/loadgen harness as BENCH_MODE=serving, each measured
    twice back-to-back:

    - *_legacy_*: fast_path=False — per-row JSON dicts, per-batch Table +
      the uncompiled model.transform (the seed jit forest walk for
      iforest; the host affinity-gather + per-batch top_k re-upload for
      SAR): the pre-PR baseline;
    - headline: fast_path=True — the compiled serving plans (tree-parallel
      host descent / ONE sharded psum matmul + on-device top_k) through
      the bucketed zero-recompile path.

    One headline record, backend-stamped; benchdiff derives
    workloads.{iforest,sar}.req_per_sec (higher-better) and
    workloads.{iforest,sar}.p99_ms (born lower_better) gates from it.
    Quiet-host numbers; tests/test_workloads.py pins the invariants
    (parity, recompiles==0, zero-drop swap)."""
    import json as _json
    import jax
    from mmlspark_tpu.core import Table
    from mmlspark_tpu.io.loadgen import run_load
    from mmlspark_tpu.io.serving import serve_pipeline
    from mmlspark_tpu.reliability.metrics import reliability_metrics
    from mmlspark_tpu.telemetry.lineage import model_version
    from mmlspark_tpu.workloads import IsolationForestScorer, SARServing

    rng = np.random.default_rng(0)

    def closed_loop(model, input_cols, output_col, body, fast_path,
                    n_clients=8, per_client=100):
        reliability_metrics.reset("serving.")
        server, q = serve_pipeline(model, input_cols=input_cols,
                                   output_col=output_col, mode="microbatch",
                                   max_batch=256, fast_path=fast_path)
        host, port = server._httpd.server_address[:2]
        try:
            res = run_load(host, port, body, n_clients=n_clients,
                           per_client=per_client)
            assert not res.errors, res.errors[:3]
        finally:
            q.stop()
            server.stop()
        return res

    # -- IsolationForest: same rows the estimator profiles (5% shifted) ----
    n, f = 20_000, 16
    x = np.vstack([rng.normal(size=(n - n // 20, f)),
                   rng.normal(4.0, 1.0, size=(n // 20, f))]).astype(
                       np.float32)
    if_model = IsolationForestScorer(num_estimators=64, max_samples=256,
                                     seed=7).fit(Table({"features": x}))
    if_body = _json.dumps({"features": [0.1] * f})
    if_legacy = closed_loop(if_model, ["features"], "outlierScore",
                            if_body, fast_path=False)
    if_fast = closed_loop(if_model, ["features"], "outlierScore",
                          if_body, fast_path=True)

    # -- SAR: dense-ish catalog so the matmul is the cost ------------------
    n_users, n_items, n_ev = 256, 128, 20_000
    events = Table({"user": rng.integers(0, n_users, n_ev),
                    "item": rng.integers(0, n_items, n_ev),
                    "rating": rng.uniform(1.0, 5.0, n_ev),
                    "timestamp": rng.integers(0, 10**6, n_ev).astype(
                        np.float64)})
    sar_model = SARServing(support_threshold=2,
                           num_recommendations=10).fit(events)
    sar_body = _json.dumps({"user": 3})
    sar_legacy = closed_loop(sar_model, ["user"], "recommendations",
                             sar_body, fast_path=False)
    sar_fast = closed_loop(sar_model, ["user"], "recommendations",
                           sar_body, fast_path=True)

    print(json.dumps({
        "metric": "workloads_req_per_sec",
        # headline: combined compiled-path throughput; the per-workload
        # fields below are what benchdiff actually gates on
        "value": round(if_fast.req_per_sec + sar_fast.req_per_sec, 1),
        "unit": "req/s",
        "backend": jax.default_backend(),
        "iforest_req_per_sec": round(if_fast.req_per_sec, 1),
        "iforest_p99_ms": round(if_fast.p99_ms, 2),
        "iforest_legacy_req_per_sec": round(if_legacy.req_per_sec, 1),
        "iforest_legacy_p99_ms": round(if_legacy.p99_ms, 2),
        "iforest_speedup_vs_legacy": round(
            if_fast.req_per_sec / max(if_legacy.req_per_sec, 1e-9), 2),
        "iforest_model": "IsolationForestScorer 64 trees, 16 features",
        "iforest_model_version": model_version(if_model).version,
        "sar_req_per_sec": round(sar_fast.req_per_sec, 1),
        "sar_p99_ms": round(sar_fast.p99_ms, 2),
        "sar_legacy_req_per_sec": round(sar_legacy.req_per_sec, 1),
        "sar_legacy_p99_ms": round(sar_legacy.p99_ms, 2),
        "sar_speedup_vs_legacy": round(
            sar_fast.req_per_sec / max(sar_legacy.req_per_sec, 1e-9), 2),
        "sar_model": "SARServing 256 users x 128 items, k=10",
        "sar_model_version": model_version(sar_model).version}))


def _bench_telemetry():
    """Telemetry overhead A/B (ISSUE 5 satellite): the SAME closed-loop
    serving harness as BENCH_MODE=serving (real fitted GBDT booster,
    compiled fast path, coalesced microbatch) runs three times —

    - off:     span sampling 0% (the production default; one float compare
               per request is the whole cost),
    - sampled: 1% deterministic head sampling (the recommended always-on
               production setting),
    - full:    100% (every request minted a root span + transform child),

    — and reports req/s + p50 for each. BUDGET (asserted HERE, never in
    tier-1 tests — wall clock on a contended host is bench territory):
    sampled-mode throughput must stay within 20% of off (the stated
    overhead budget; quiet-host runs measure low single digits). The full
    run also scrapes GET /metrics once and sanity-checks the Prometheus
    exposition + span-ring stats so the artifact proves the exposition
    path live under load."""
    import urllib.request
    from mmlspark_tpu import telemetry
    from mmlspark_tpu.core import Table
    from mmlspark_tpu.models.gbdt.estimators import GBDTClassifier
    from mmlspark_tpu.io.loadgen import run_load
    from mmlspark_tpu.io.serving import serve_pipeline
    from mmlspark_tpu.reliability.metrics import reliability_metrics

    rng = np.random.default_rng(0)
    n, f = 20_000, 16
    x = rng.normal(size=(n, f)).astype(np.float32)
    y = (x[:, 0] + x[:, 1] > 0).astype(np.float32)
    model = GBDTClassifier(num_iterations=20, max_depth=5).fit(
        Table({"features": x, "label": y}))
    body = json.dumps({"features": [0.1] * f})

    out = {}
    expo_text = ""
    for tag, rate in (("off", 0.0), ("sampled", 0.01), ("full", 1.0)):
        telemetry.configure(sample=rate)
        telemetry.get_tracer().clear()
        reliability_metrics.reset("serving.")
        server, q = serve_pipeline(model, input_cols=["features"],
                                   mode="microbatch", max_batch=256,
                                   fast_path=True)
        host, port = server._httpd.server_address[:2]
        try:
            res = run_load(host, port, body, n_clients=16, per_client=125)
            assert not res.errors, res.errors[:3]
            if rate == 1.0:
                expo_text = urllib.request.urlopen(
                    f"http://{host}:{port}/metrics", timeout=10
                ).read().decode()
        finally:
            q.stop()
            server.stop()
        stats = telemetry.get_tracer().stats()
        out[f"{tag}_req_per_sec"] = round(res.req_per_sec, 1)
        out[f"{tag}_p50_ms"] = round(res.p50_ms, 2)
        out[f"{tag}_p99_ms"] = round(res.p99_ms, 2)
        out[f"{tag}_spans"] = stats["spans"] + stats["dropped"]
    telemetry.configure(sample=0.0)

    assert "serving_request_e2e_seconds_bucket" in expo_text, \
        "GET /metrics under load lost the e2e histogram"
    assert out["off_spans"] == 0 and out["full_spans"] > 0

    # windowed-vs-cumulative A/B (ISSUE 7 satellite): the full run's
    # traffic is still inside the default 300s shard ring — read the
    # last-60s percentiles next to the cumulative ones, and time both
    # snapshot paths. The windowed read merges every live shard
    # (~shards x buckets int adds), so it is strictly the slower one;
    # the budget asserts it stays cheap enough to sit on a poller/SLO
    # hot path (bench-side assert only — never wall clock in tier-1).
    win = reliability_metrics.window_snapshot(60.0)
    out["windowed_p50_ms"] = round(
        win.get("serving.request.e2e.p50", 0.0), 3)
    out["windowed_p99_ms"] = round(
        win.get("serving.request.e2e.p99", 0.0), 3)
    out["windowed_count"] = win.get("serving.request.e2e.count", 0)
    assert out["windowed_count"] > 0, "full run left no windowed samples"
    hist = reliability_metrics.histogram("serving.request.e2e")
    reps = 200
    t0 = time.perf_counter()
    for _ in range(reps):
        hist.snapshot()
    t1 = time.perf_counter()
    for _ in range(reps):
        hist.window.snapshot(60.0)
    t2 = time.perf_counter()
    out["snapshot_cumulative_us"] = round((t1 - t0) / reps * 1e6, 1)
    out["snapshot_windowed_us"] = round((t2 - t1) / reps * 1e6, 1)
    out["snapshot_windowed_budget_us"] = 5000.0
    assert out["snapshot_windowed_us"] <= out["snapshot_windowed_budget_us"], \
        (f"windowed snapshot cost {out['snapshot_windowed_us']}us — over "
         f"the {out['snapshot_windowed_budget_us']}us budget")
    out["sampled_overhead_pct"] = round(
        (1.0 - out["sampled_req_per_sec"]
         / max(out["off_req_per_sec"], 1e-9)) * 100.0, 1)
    out["full_overhead_pct"] = round(
        (1.0 - out["full_req_per_sec"]
         / max(out["off_req_per_sec"], 1e-9)) * 100.0, 1)
    out["sampled_overhead_budget_pct"] = 20.0
    assert out["sampled_overhead_pct"] <= out["sampled_overhead_budget_pct"], \
        (f"1% sampling cost {out['sampled_overhead_pct']}% throughput — "
         f"over the {out['sampled_overhead_budget_pct']}% budget")
    print(json.dumps({
        "metric": "serving_telemetry_sampled_req_per_sec",
        "value": out["sampled_req_per_sec"], "unit": "req/s",
        # >= ~1.0 means 1% sampling is throughput-free within noise
        "vs_baseline": round(out["sampled_req_per_sec"]
                             / max(out["off_req_per_sec"], 1e-9), 3),
        "exposition_bytes": len(expo_text), **out}))


def _bench_quality():
    """Model-quality tap overhead A/B (ISSUE 12 satellite): the SAME
    closed-loop serving harness as BENCH_MODE=serving (real fitted GBDT
    booster with its fit-time reference profile, compiled fast path,
    coalesced microbatch) runs three times —

    - off:     sketches and the label join disabled (monitor installed,
               sample 0 — the per-batch cost is one boolean test),
    - sampled: live sketches head-sampled at 10% by request id + the
               label-join prediction insert per request (the recommended
               always-on production setting),
    - full:    every request folded into the sketches,

    — and reports req/s + p50 per mode. BUDGET (asserted HERE, never in
    tier-1 — wall clock on a contended host is bench territory): the
    sampled mode must stay within 20% of off. The full run also scrapes
    GET /metrics once (drift gauges must publish) and GET /quality (the
    export must carry live sketch counts == requests served), so the
    artifact proves the quality exposition live under load. The record
    is stamped with `backend` so benchdiff gates it correctly."""
    import urllib.request
    import jax
    from mmlspark_tpu.core import Table
    from mmlspark_tpu.models.gbdt.estimators import GBDTClassifier
    from mmlspark_tpu.io.loadgen import run_load
    from mmlspark_tpu.io.serving import serve_pipeline
    from mmlspark_tpu.reliability.metrics import reliability_metrics
    from mmlspark_tpu.telemetry import quality as tquality

    rng = np.random.default_rng(0)
    n, f = 20_000, 16
    x = rng.normal(size=(n, f)).astype(np.float32)
    y = (x[:, 0] + x[:, 1] > 0).astype(np.float32)
    model = GBDTClassifier(num_iterations=20, max_depth=5).fit(
        Table({"features": x, "label": y}))
    body = json.dumps({"features": [0.1] * f})

    n_clients, per_client = 16, 125
    out = {}
    quality_payload = {}
    for tag, rate, labels in (("off", 0.0, False), ("sampled", 0.1, True),
                              ("full", 1.0, True)):
        tquality.reset_monitor()
        reliability_metrics.reset("serving.")
        reliability_metrics.reset("quality.")
        server, q = serve_pipeline(model, input_cols=["features"],
                                   mode="microbatch", max_batch=256,
                                   batch_linger_ms=0.2, fast_path=True)
        tquality.configure_quality(sample=rate, labels=labels)
        host, port = server._httpd.server_address[:2]
        try:
            res = run_load(host, port, body, n_clients=n_clients,
                           per_client=per_client)
            assert not res.errors, res.errors[:3]
            if tag == "full":
                expo = urllib.request.urlopen(
                    f"http://{host}:{port}/metrics", timeout=10
                ).read().decode()
                assert "quality_drift_max" in expo, \
                    "full run published no drift gauge on GET /metrics"
                quality_payload = json.loads(urllib.request.urlopen(
                    f"http://{host}:{port}/quality", timeout=10).read())
        finally:
            q.stop()
            server.stop()
        out[f"{tag}_req_per_sec"] = round(res.req_per_sec, 1)
        out[f"{tag}_p50_ms"] = round(res.p50_ms, 2)
        out[f"{tag}_p99_ms"] = round(res.p99_ms, 2)
        out[f"{tag}_sketch_rows"] = reliability_metrics.get(
            "quality.sketch.rows")
    tquality.reset_monitor()

    total = n_clients * per_client
    assert out["off_sketch_rows"] == 0
    assert out["full_sketch_rows"] == total, \
        (out["full_sketch_rows"], total)
    live = quality_payload.get("live", {}).get("columns", {})
    assert live.get("f0", {}).get("hist", {}).get("count") == total, \
        "GET /quality under load lost live sketch counts"
    out["sampled_overhead_pct"] = round(
        (1.0 - out["sampled_req_per_sec"]
         / max(out["off_req_per_sec"], 1e-9)) * 100.0, 1)
    out["full_overhead_pct"] = round(
        (1.0 - out["full_req_per_sec"]
         / max(out["off_req_per_sec"], 1e-9)) * 100.0, 1)
    out["sampled_overhead_budget_pct"] = 20.0
    assert out["sampled_overhead_pct"] <= out["sampled_overhead_budget_pct"], \
        (f"10% quality sampling cost {out['sampled_overhead_pct']}% "
         f"throughput — over the "
         f"{out['sampled_overhead_budget_pct']}% budget")
    print(json.dumps({
        "metric": "serving_quality_sampled_req_per_sec",
        "value": out["sampled_req_per_sec"], "unit": "req/s",
        # >= ~1.0 means the sampled tap is throughput-free within noise
        "vs_baseline": round(out["sampled_req_per_sec"]
                             / max(out["off_req_per_sec"], 1e-9), 3),
        "backend": jax.default_backend(), **out}))


class _PoisonModel:
    """A candidate whose artifact cannot score: `transform` raises on
    every batch (server-side -> 502s, the SLO error-budget numerator).
    The classic bad deploy the control loop must catch — it installs
    fine, versions fine (structural digest over `_get_state`), and only
    fails under traffic."""

    def transform(self, table):
        raise RuntimeError("bad candidate: artifact cannot score")

    def _get_state(self):
        return {"poison": np.asarray([1.0], np.float32)}


def _bench_fleet():
    """Closed-loop FLEET bench (ISSUE 16 tentpole acceptance): loadgen
    against N in-process workers behind the weighted routing tier, with
    the rollout control loop live.

    Phase A measures steady-state fleet req/s through `WeightedRouter`
    (registry-discovered targets, scrape-derived weights). Phase B
    injects a POISON candidate mid-load via `RolloutDriver` — the
    candidate's 502s burn the (short-windowed) error-budget objective,
    the driver auto-rolls-back to the incumbent, and the fleet `/slo`
    verdict returns to ok — while the load generator keeps every client
    alive across the burn. The emitted record carries the acceptance
    numbers: `requests_dropped` (MUST be 0 — every request sent got an
    answer, even mid-rollback) and `rollback_window_p99_ms` (tail latency
    over the whole chaos window), both born lower-is-better for
    benchdiff gating."""
    from mmlspark_tpu.control import (RolloutConfig, RolloutDriver,
                                      WeightedRouter)
    from mmlspark_tpu.core import Table
    from mmlspark_tpu.models.gbdt.estimators import GBDTClassifier
    from mmlspark_tpu.io.loadgen import run_load
    from mmlspark_tpu.io.registry import (ServiceRegistry,
                                          report_server_to_registry)
    from mmlspark_tpu.io.serving import serve_pipeline
    from mmlspark_tpu.reliability.metrics import reliability_metrics
    from mmlspark_tpu.telemetry import lineage as tlineage
    from mmlspark_tpu.telemetry import slo as tslo
    from mmlspark_tpu.telemetry.exposition import scrape_cluster

    rng = np.random.default_rng(0)
    n, f = 8_000, 16
    x = rng.normal(size=(n, f)).astype(np.float32)
    y = (x[:, 0] + x[:, 1] > 0).astype(np.float32)
    incumbent = GBDTClassifier(num_iterations=10, max_depth=4).fit(
        Table({"features": x, "label": y}))
    body = json.dumps({"features": [0.1] * f})

    # short SLO windows so the candidate's burn — and the post-rollback
    # recovery — both land inside the bench run (2 s short / 4 s long)
    tslo.configure(objectives=[tslo.Objective(
        name="serving.error_rate", kind=tslo.ERROR_RATE,
        metric="serving.request.errors",
        total_metric="serving.request.total",
        budget=0.02, window_s=2.0)], long_factor=2.0)
    reliability_metrics.reset()
    tlineage.reset_version_registry()

    n_workers = 3
    registry = ServiceRegistry(ttl_s=30.0).start()
    fleet = []     # (server, query)
    try:
        for i in range(n_workers):
            server, q = serve_pipeline(incumbent, input_cols=["features"],
                                       mode="microbatch", max_batch=128,
                                       fast_path=True)
            host, port = server._httpd.server_address[:2]
            report_server_to_registry(registry.address, "serving", host,
                                      port, process_id=i,
                                      version=q.transform_fn.version)
            fleet.append((server, q))
        router = WeightedRouter(registry.address, "serving")

        # -- phase A: steady-state fleet throughput through the router ---
        res_a = run_load("", 0, body, n_clients=8, per_client=150,
                         post=lambda b: router.post(b.encode()))
        assert not res_a.errors, res_a.errors[:3]
        router.update_from_scrape(
            scrape_cluster(registry.address, window=30.0))

        # -- phase B: poison candidate mid-load, auto-rollback -----------
        driver = RolloutDriver(
            workers={f"w{i}": q.transform_fn
                     for i, (_, q) in enumerate(fleet)},
            incumbent=incumbent, candidate=_PoisonModel(),
            registry_address=registry.address,
            config=RolloutConfig(traffic_steps=(1.0 / n_workers, 1.0),
                                 step_polls=2, soak_polls=2,
                                 poll_interval_s=0.3,
                                 scrape_window_s=10.0, recover_polls=40))
        status = {}
        rollout = threading.Thread(
            target=lambda: status.update(driver.run()), daemon=True)
        any_answer = lambda s, p: None   # noqa: E731 - 502s are answers
        t0 = time.perf_counter()
        rollout.start()
        res_b = run_load("", 0, body, n_clients=8, per_client=400,
                         check=any_answer,
                         post=lambda b: router.post(b.encode()))
        rollout.join(timeout=60)
        chaos_wall = time.perf_counter() - t0
        snap = scrape_cluster(registry.address, slo=True)
    finally:
        for server, q in fleet:
            q.stop()
            server.stop()
        registry.stop()
        tslo.configure()   # restore default objectives

    assert status.get("state") == "rolled_back", status
    assert res_b.n_dropped == 0, \
        f"{res_b.n_dropped} of {res_b.n_sent} requests dropped in rollback"
    assert snap.slo is not None and snap.slo["ok"] and \
        not snap.slo["burning"], "fleet /slo never recovered"
    errs_502 = res_b.n_by_status.get(502, 0)
    assert errs_502 > 0, "poison candidate never produced a 502 burn"

    print(json.dumps({
        "metric": "fleet_req_per_sec",
        "value": round(res_a.req_per_sec, 1), "unit": "req/s",
        "vs_baseline": 0.0,
        "workers": n_workers,
        "rollback_window_p99_ms": round(res_b.p99_ms, 2),
        "requests_dropped": res_b.n_dropped,
        "rollback_state": status.get("state"),
        "chaos_wall_s": round(chaos_wall, 2),
        "chaos_answered": res_b.n_answered,
        "chaos_502": errs_502,
        "router_weights": router.weights}))


def _bench_online():
    """Continuous learning on the serving stream (ISSUE 17 tentpole).

    Three phases, one JSON line:

    - serving A/B: the SAME fitted VW sparse-pair model behind
      `serve_pipeline`, scored through the compiled sparse fast path
      (kernel route: (n, k)-bucketed idx/val pairs, zero recompiles)
      vs the legacy per-row Table route (the pre-PR path for hashed
      sparse models — dense-style row assembly + uncompiled
      model.transform). Headline `online_sparse_req_per_sec`,
      `dense_baseline_req_per_sec` rides along.
    - online updates/sec: `OnlineLearner.partial_fit` minibatches at
      the fixed (rows, k) bucket — ONE compiled executable after the
      warm-up chunk; reported as live examples folded per second.
    - adaptation latency: the self-healing window — wall seconds from
      the FIRST request of a seeded 5-sigma covariate shift on the live
      worker to the refit candidate PROMOTED by the canary gate (drift
      trip -> LabelFeed refit -> install -> promote), with zero dropped
      requests. Born lower-is-better for benchdiff gating
      (`requests_dropped` too: any drop is a regression)."""
    import jax
    from mmlspark_tpu.control import (Observation, RolloutConfig,
                                      RolloutDriver)
    from mmlspark_tpu.control import rollout as ctl
    from mmlspark_tpu.core import Table
    from mmlspark_tpu.io.loadgen import run_load
    from mmlspark_tpu.io.serving import serve_pipeline
    from mmlspark_tpu.models.vw.estimators import VowpalWabbitClassifier
    from mmlspark_tpu.models.vw.learner import VWParams
    from mmlspark_tpu.online import ContinuousLearner, LabelFeed, OnlineConfig
    from mmlspark_tpu.online import OnlineLearner
    from mmlspark_tpu.reliability.metrics import reliability_metrics
    from mmlspark_tpu.telemetry import lineage as tlineage
    from mmlspark_tpu.telemetry import quality as tquality

    rng = np.random.default_rng(0)
    n, k, bits = 20_000, 16, 16
    slots = rng.integers(0, 1 << bits, size=k).astype(np.int32)
    idx = np.tile(slots, (n, 1))
    val = rng.normal(size=(n, k)).astype(np.float32)
    beta = rng.normal(size=k).astype(np.float32)
    y = (val @ beta > 0).astype(np.float32)
    incumbent = VowpalWabbitClassifier(
        features_col="features", label_col="label", num_bits=bits,
        num_passes=4).fit(
            Table({"features_idx": idx, "features_val": val, "label": y}))
    body = json.dumps({"features_idx": slots.tolist(),
                       "features_val": [0.1] * k})

    def closed_loop(fast_path):
        reliability_metrics.reset()
        tquality.reset_monitor()
        tlineage.reset_version_registry()
        server, q = serve_pipeline(
            incumbent, input_cols=["features_idx", "features_val"],
            mode="microbatch", max_batch=128, fast_path=fast_path)
        host, port = server._httpd.server_address[:2]
        try:
            res = run_load(host, port, body, n_clients=16, per_client=125)
            assert not res.errors, res.errors[:3]
        finally:
            q.stop()
            server.stop()
        return res

    res_sparse = closed_loop(fast_path=True)
    recompiles = reliability_metrics.get("plan.recompiles")
    res_dense = closed_loop(fast_path=False)

    # -- online updates/sec at the one compiled bucket -------------------
    lrn = OnlineLearner(VWParams(loss_function="logistic", num_bits=bits),
                        warm_start=incumbent, rows=256, k=k)
    lrn.partial_fit(idx[:256], val[:256], y[:256])      # warm-up compile
    chunks, t0 = 64, time.perf_counter()
    for c in range(chunks):
        lo = (c * 256) % (n - 256)
        lrn.partial_fit(idx[lo:lo + 256], val[lo:lo + 256],
                        y[lo:lo + 256])
    upd_wall = time.perf_counter() - t0
    updates_per_sec = chunks * 256 / upd_wall

    # -- shift-to-promoted adaptation latency ----------------------------
    reliability_metrics.reset()
    tquality.reset_monitor()
    tlineage.reset_version_registry()
    shift = (5.0 * beta / np.linalg.norm(beta)).astype(np.float32)
    server, q = serve_pipeline(
        incumbent, input_cols=["features_idx", "features_val"],
        mode="continuous")
    statuses = []
    try:
        mon = tquality.get_monitor()
        mon.configure(sample=1.0, min_live=24)
        feed = LabelFeed(evaluator=mon.evaluator)
        lrn2 = OnlineLearner(VWParams(loss_function="logistic",
                                      num_bits=bits),
                             warm_start=incumbent, rows=64, k=k)

        import urllib.request as _rq

        def post(row_idx, row_val, label):
            data = json.dumps({
                "features_idx": row_idx.tolist(),
                "features_val": row_val.tolist()}).encode()
            req = _rq.Request(server.address, data=data,
                              headers={"Content-Type": "application/json"})
            resp = _rq.urlopen(req, timeout=15)
            resp.read()
            statuses.append(resp.status)
            rid = resp.headers["X-Request-Id"]
            feed.record_features([rid], row_idx[None, :], row_val[None, :])
            tquality.record_label(rid, float(label))

        def deploy(candidate):
            sched = iter([Observation()] * 10)
            drv = RolloutDriver(
                {"w0": q.transform_fn}, incumbent, lambda: candidate,
                observe=lambda: next(sched),
                config=RolloutConfig(traffic_steps=(1.0,), step_polls=1,
                                     soak_polls=1, poll_interval_s=0.0),
                sleep=lambda s: None)
            return drv.run()["state"] == ctl.PROMOTED

        loop = ContinuousLearner(
            lrn2, feed, deploy=deploy,
            config=OnlineConfig(min_pairs=32, max_drift=0.5,
                                poll_interval_s=0.0),
            sleep=lambda s: None)
        shifted = val + shift
        y_shift = (shifted @ beta > 0).astype(np.float32)
        t0 = time.perf_counter()
        for i in range(72):
            post(idx[i], shifted[i], y_shift[i])
        status = loop.run_once()
        adapt_latency = time.perf_counter() - t0
    finally:
        q.stop()
        server.stop()
    assert status.get("outcome") == "promoted", status
    dropped = sum(1 for s in statuses if s != 200)

    print(json.dumps({
        "metric": "online_sparse_req_per_sec",
        "value": round(res_sparse.req_per_sec, 1), "unit": "req/s",
        "vs_baseline": round(
            res_sparse.req_per_sec / max(res_dense.req_per_sec, 1e-9), 2),
        "backend": jax.default_backend(),
        "dense_baseline_req_per_sec": round(res_dense.req_per_sec, 1),
        "sparse_p99_ms": round(res_sparse.p99_ms, 2),
        "plan_recompiles": recompiles,
        "online_updates_per_sec": round(updates_per_sec, 1),
        "adapt_latency_s": round(adapt_latency, 3),
        "requests_dropped": dropped}))


def _bench_ckpt():
    """Checkpoint stall per training step, sync vs async (ISSUE 4
    tooling satellite): the SAME LM stream-training loop runs (a) with no
    checkpointing, (b) checkpointing every step SYNCHRONOUSLY on the step
    thread (CheckpointManager.save inline — the pre-supervisor behavior),
    and (c) through the TrainingSupervisor's AsyncCheckpointWriter
    (snapshot on the step thread, write on the background thread). The
    emitted deltas are the per-step wall-clock stall each mode adds over
    the no-checkpoint baseline; checkpoint.{submit,snapshot,write} metric
    stats ride along so the zero-blocking-writes claim is auditable across
    future PRs. vs_baseline = sync_stall / async_stall (>1: async wins)."""
    import shutil
    import tempfile
    import jax
    from mmlspark_tpu.models.dnn.lm_training import (ShardedLMTrainer,
                                                     lm_state_payload)
    from mmlspark_tpu.reliability.metrics import reliability_metrics
    from mmlspark_tpu.utils.checkpoint import CheckpointManager

    rng = np.random.default_rng(0)
    n_batches = int(os.environ.get("BENCH_CKPT_BATCHES", 16))
    batches = [rng.integers(0, 1024, size=(8, 128)).astype(np.int32)
               for _ in range(n_batches)]

    def trainer():
        return ShardedLMTrainer(vocab_size=1024, d_model=256, n_heads=8,
                                n_layers=2, d_ff=512, max_len=128, seed=0)

    # -- (a) no checkpointing ------------------------------------------------
    t = trainer()
    t.run_stream(batches)                      # compile + warm
    t0 = time.time()
    t.run_stream(batches)
    off_s = time.time() - t0

    # -- (b) synchronous save on the step thread -----------------------------
    # same prefetched feed as (a)/(c) — the measured delta must be the
    # inline CheckpointManager.save alone, not lost transfer overlap
    from mmlspark_tpu.data import DevicePrefetcher
    d_sync = tempfile.mkdtemp()
    mgr = CheckpointManager(d_sync, max_to_keep=2)
    t0 = time.time()
    with DevicePrefetcher(batches, depth=2, put=t._to_device) as pf:
        for k, tok_dev in enumerate(pf):
            t.params, t.opt_state, _loss = t._step(t.params, t.opt_state,
                                                   tok_dev)
            mgr.save(k, lm_state_payload(t.params, t.opt_state, t.meta))
    sync_s = time.time() - t0
    shutil.rmtree(d_sync, ignore_errors=True)

    # -- (c) async supervisor checkpointing ----------------------------------
    reliability_metrics.reset(prefix="checkpoint.")
    d_async = tempfile.mkdtemp()
    t0 = time.time()
    t.run_stream(batches, checkpoint_dir=d_async, checkpoint_every=1,
                 resume=False, handle_signals=False)
    async_s = time.time() - t0
    shutil.rmtree(d_async, ignore_errors=True)

    snap = reliability_metrics.snapshot()
    n_params = sum(int(np.prod(a.shape))
                   for a in jax.tree_util.tree_leaves(t.params))
    stall_sync = (sync_s - off_s) / n_batches * 1000
    stall_async = (async_s - off_s) / n_batches * 1000
    # timing noise can land async at/below the baseline (stall <= 0); a
    # 1e-9 denominator would then emit an absurd 1e9-style ratio into a
    # record meant for cross-PR regression tracking — floor both at 0.1ms
    # (any stall under that is indistinguishable from noise here anyway)
    ratio = max(stall_sync, 0.1) / max(stall_async, 0.1)
    print(json.dumps({
        "metric": "ckpt_async_stall_ms_per_step",
        "value": round(stall_async, 3), "unit": "ms/step",
        "vs_baseline": round(ratio, 3),
        "sync_stall_ms_per_step": round(stall_sync, 3),
        "off_ms_per_step": round(off_s / n_batches * 1000, 3),
        "sync_ms_per_step": round(sync_s / n_batches * 1000, 3),
        "async_ms_per_step": round(async_s / n_batches * 1000, 3),
        "model_params": n_params, "n_steps": n_batches,
        "submit_p99_ms": round(snap.get("checkpoint.submit.p99", 0.0), 3),
        "snapshot_p50_ms": round(snap.get("checkpoint.snapshot.p50", 0.0), 3),
        "write_p50_ms": round(snap.get("checkpoint.write.p50", 0.0), 3),
        "writes": snap.get("checkpoint.write.count", 0),
        "coalesced": snap.get("checkpoint.write.coalesced", 0),
        "write_errors": snap.get("checkpoint.write.errors", 0)}))


def _bench_hist():
    """Standalone per-(m, B, route) histogram-kernel grid (round 6): the
    measurement that refreshes ops/histogram_pallas's routing table. Every
    route the family can express runs at every (m, B) point — direct,
    joint at each LO <= B, and the precomputed-plane route where LO | B —
    with in-graph lax.scan repetition and one value fetch. A point that
    fails to compile fails the mode (chip_smoke.py's kernel phase is where
    a layout Mosaic rejects is found). Prints one JSON line; the grid dict
    is the artifact."""
    import jax
    import jax.numpy as jnp
    from mmlspark_tpu.ops import histogram_pallas as hp

    n = int(os.environ.get("BENCH_HIST_ROWS", 1_000_000))
    F = int(os.environ.get("BENCH_HIST_FEATURES", 32))
    reps = int(os.environ.get("BENCH_HIST_REPS", 10))
    rng = np.random.default_rng(0)
    grid = {}
    for B in (64, 256):
        bins = jnp.asarray(rng.integers(0, B, size=(n, F)).astype(np.uint8))
        grad = jnp.asarray(rng.normal(size=n).astype(np.float32))
        hess = jnp.asarray(rng.uniform(0.1, 1, size=n).astype(np.float32))
        base = jnp.asarray(rng.integers(0, 1 << 20, size=n).astype(np.int32))
        plane_lo = hp.plan_lo_bins(B)
        planes = hp.build_hist_plan(bins, B) if plane_lo else None
        for m in (1, 2, 4, 8, 16):
            routes = [("direct", B)]
            routes += [("joint", lo) for lo in (16, 32, 64, 128) if lo < B]
            if planes is not None:
                routes.append(("planes", plane_lo))

            for route in routes:
                kind, lo = route
                use_planes = kind == "planes"

                def make(route=route, m=m, B=B, bins=bins,
                         use_planes=use_planes):
                    @jax.jit
                    def run():
                        def body(c, i):
                            nd = jax.lax.rem(base + i, m)
                            hg, hh, hc = hp.pallas_hist(
                                bins, grad, hess, nd, nd >= 0, m, B,
                                route=route,
                                lo_planes=planes if use_planes else None,
                                plane_lo=plane_lo if use_planes else 0)
                            return c + hg.sum() + hh.sum() + hc.sum(), None
                        s, _ = jax.lax.scan(body, jnp.float32(0),
                                            jnp.arange(reps))
                        return s
                    return run

                fn = make()
                float(fn())              # compile + warm
                t0 = time.time()
                float(fn())
                grid[f"B{B}_m{m}_{kind}_lo{lo}"] = round(
                    (time.time() - t0) / reps * 1000, 3)
    print(json.dumps({
        "metric": "hist_kernel_grid_ms", "unit": "ms/call",
        "value": grid["B64_m1_direct_lo64"], **_device_stamp(),
        "vs_baseline": 0.0, "rows": n, "features": F, "reps": reps,
        # ms/call regresses by GROWING: benchdiff gates this record
        # lower-is-better without a CLI flag (like MULTICHIP synthesis)
        "lower_better": True,
        "grid": grid}))


def _bf16_peak_flops() -> float:
    """This chip's bf16 peak from the repo's one peaks table
    (telemetry/profiler.CHIP_PEAKS, keyed by device_kind). A device that
    is not in the table is an error, not a default."""
    import jax
    from mmlspark_tpu.telemetry.profiler import chip_peaks
    chip = chip_peaks()
    if chip is None:
        raise SystemExit(
            f"bench: no bf16 peak for device kind "
            f"{jax.devices()[0].device_kind!r} in "
            f"telemetry/profiler.CHIP_PEAKS")
    return chip[0]


def _bench_flash():
    """16k-token causal flash attention (README flash row's source):
    fwd and fwd+bwd timings + TFLOP/s + fraction of bf16 peak, against a
    dense-XLA fwd baseline on identical inputs. vs_baseline is the
    flash-over-dense forward speedup (>1 means flash wins).

    TWO head dims, one line each: d=64 (round-3/4 continuity) and d=128 —
    the head dim the flagship LM trainer actually uses (BENCH_LM_HEADS=8 x
    d_model=1024), where the MXU's 128-lane contraction is fully fed. The
    round-4 verdict flagged the d=128 number as prose-only; these rows are
    its artifact."""
    import jax
    import jax.numpy as jnp
    from mmlspark_tpu.ops.flash_attention import (flash_attention,
                                                  _xla_reference_shd)
    rng = np.random.default_rng(0)
    reps_n = 25

    def timed(fn, *args):
        float(fn(*args))                # compile + warm
        t0 = time.time()
        float(fn(*args))
        # 25 in-graph reps amortize the fixed dispatch+fetch cost
        return (time.time() - t0) / reps_n * 1000

    for s, h, d in ((16384, 8, 64), (16384, 8, 128)):
        # useful causal FLOPs: 2 matmuls x 2*S^2*D*H, halved by causality;
        # backward re-does ~2.5x the forward matmul work (dq + dk/dv)
        flops_fwd = 2 * 2 * s * s * d * h / 2
        out = {}
        for name, dt in (("f32", jnp.float32), ("bf16", jnp.bfloat16)):
            q = jnp.asarray(rng.normal(size=(s, h, d)), dt)
            k = jnp.asarray(rng.normal(size=(s, h, d)), dt)
            v = jnp.asarray(rng.normal(size=(s, h, d)), dt)

            @jax.jit
            def fwd(q, k, v):
                def body(c, i):
                    o = flash_attention(q * (1 + i * 1e-6), k, v,
                                        causal=True)
                    return c + o.astype(jnp.float32).sum(), None
                s_, _ = jax.lax.scan(body, jnp.float32(0),
                                     jnp.arange(reps_n))
                return s_

            @jax.jit
            def fwdbwd(q, k, v):
                def loss(q, k, v):
                    return flash_attention(q, k, v, causal=True).astype(
                        jnp.float32).sum()

                def body(c, i):
                    l, gs = jax.value_and_grad(loss, argnums=(0, 1, 2))(
                        q * (1 + i * 1e-6), k, v)
                    return c + l + sum(g.astype(jnp.float32).sum()
                                       for g in gs), None
                s_, _ = jax.lax.scan(body, jnp.float32(0),
                                     jnp.arange(reps_n))
                return s_

            out[name + "_ms"] = round(timed(fwd, q, k, v), 1)
            out[name + "_fwdbwd_ms"] = round(timed(fwdbwd, q, k, v), 1)

        # dense XLA forward on the SAME inputs (bf16): the "just let XLA
        # do it" alternative; 16k is near its HBM ceiling (the (S,S) f32
        # score matrix alone is 1 GiB x reads+writes per rep)
        q = jnp.asarray(rng.normal(size=(s, h, d)), jnp.bfloat16)
        k = jnp.asarray(rng.normal(size=(s, h, d)), jnp.bfloat16)
        v = jnp.asarray(rng.normal(size=(s, h, d)), jnp.bfloat16)

        @jax.jit
        def dense(q, k, v):
            def body(c, i):
                o = _xla_reference_shd(
                    jnp.moveaxis(q * (1 + i * 1e-6), 1, 0),
                    jnp.moveaxis(k, 1, 0), jnp.moveaxis(v, 1, 0),
                    True, 1.0 / np.sqrt(d))
                return c + o.astype(jnp.float32).sum(), None
            s_, _ = jax.lax.scan(body, jnp.float32(0), jnp.arange(reps_n))
            return s_
        out["dense_xla_bf16_ms"] = round(timed(dense, q, k, v), 1)

        tflops = flops_fwd / out["bf16_ms"] / 1e9
        print(json.dumps({
            "metric": f"flash_attention_16k_causal_d{d}",
            "value": out["bf16_ms"], "unit": "ms",
            "vs_baseline": round(out["dense_xla_bf16_ms"] / out["bf16_ms"],
                                 2),
            "tflops_fwd": round(tflops, 1),
            "fraction_of_bf16_peak": round(
                tflops * 1e12 / _bf16_peak_flops(), 3),
            **_device_stamp(),
            "tflops_fwdbwd": round(
                3.5 * flops_fwd / out["bf16_fwdbwd_ms"] / 1e9, 1),
            **out}))


def _bench_resnet():
    """ResNet-50 bf16 inference imgs/sec (README resnet row's source)."""
    import jax
    import jax.numpy as jnp
    from mmlspark_tpu.models.dnn.resnet import init_resnet, resnet50
    model = resnet50(dtype=jnp.bfloat16)
    params = init_resnet(model, seed=0)
    batch = 128
    x = jnp.asarray(np.random.default_rng(0).normal(
        size=(batch, 224, 224, 3)), jnp.bfloat16)

    @jax.jit
    def reps(x):
        def body(c, i):
            y = model.apply(params, x * (1 + i * 1e-6))
            return c + y.astype(jnp.float32).sum(), None
        s_, _ = jax.lax.scan(body, jnp.float32(0), jnp.arange(10))
        return s_
    float(reps(x))
    t0 = time.time()
    float(reps(x))
    dt = (time.time() - t0) / 10
    print(json.dumps({"metric": "resnet50_bf16_imgs_per_sec",
                      "value": round(batch / dt, 1), "unit": "imgs/s",
                      "vs_baseline": 0.0, **_device_stamp()}))


def _bench_resnet_onnx():
    """Foreign-model inference imgs/sec/chip (round-4 verdict item 6): a
    ResNet-18 graph EXPORTED BY TORCH, imported through the hand-rolled
    ONNX reader (models/dnn/onnx_import.py), cast bf16, batch-128
    inference at 224x224 — the ImageFeaturizer foreign-model path's
    throughput (reference scores downloaded CNTK graphs the same way,
    ImageFeaturizer.scala:40-215). Parity vs torch asserted at f32
    before timing."""
    import sys as _sys
    import tempfile
    import jax
    import jax.numpy as jnp
    _sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "tests", "data"))
    from torch_resnet import export_resnet18_onnx
    from mmlspark_tpu.models.dnn.onnx_import import load_onnx

    with tempfile.NamedTemporaryFile(suffix=".onnx", delete=False) as f:
        path = f.name
    try:
        _, x_np, y_torch = export_resnet18_onnx(path, seed=0, spatial=224)
        apply_fn, params = load_onnx(path)
    finally:
        os.unlink(path)
    # parity at HIGHEST precision: TPU's default f32 matmul/conv path
    # multiplies in bf16 (~3e-3 rel), which is the right speed choice for
    # the throughput row below but not for a correctness gate
    with jax.default_matmul_precision("highest"):
        y = np.asarray(jax.jit(apply_fn)(params, x_np))
    rel = float(np.abs(y - y_torch).max()
                / (np.abs(y_torch).max() + 1e-9))
    assert rel < 1e-4, rel

    batch = 128
    x = jnp.asarray(np.random.default_rng(0).normal(
        size=(batch, 3, 224, 224)), jnp.bfloat16)
    p16 = {k: jnp.asarray(v, jnp.bfloat16)
           if v.dtype == np.float32 else jnp.asarray(v)
           for k, v in params.items()}

    @jax.jit
    def reps(x):
        def body(c, i):
            out = apply_fn(p16, x * (1 + i * 1e-6))
            return c + out.astype(jnp.float32).sum(), None
        s_, _ = jax.lax.scan(body, jnp.float32(0), jnp.arange(10))
        return s_
    float(reps(x))
    t0 = time.time()
    float(reps(x))
    dt = (time.time() - t0) / 10
    print(json.dumps({
        "metric": "resnet18_onnx_import_bf16_imgs_per_sec",
        "value": round(batch / dt, 1), "unit": "imgs/s",
        "vs_baseline": 0.0, "parity_rel_err_f32": rel, **_device_stamp(),
        "note": "torch-exported ONNX -> hand-rolled importer -> jit; "
                "north-star config[1] tracks imgs/sec/chip for the "
                "foreign-model featurizer path"}))


def _bench_lm_long_context():
    """16k-context causal LM training step (README long-context row's
    source): a ~220M-param GPT-2-medium-class model (12L, d=1024, 8 heads
    of d_head=128, ff=4096, 32k vocab), bf16 mixed precision + remat +
    flash fwd/bwd through the pipelined trainer, one chip. Prints
    tokens/s, model FLOPs per step, and MFU against the chip's bf16 peak.

    MFU accounting (standard: model FLOPs only, remat recompute NOT
    credited): fwd matmul FLOPs = 2*T*P_matmul + 2*T*d*V (logits)
    + L*2*S*S*d (causal attention, QK^T and PV at half the S^2 square),
    training = 3x fwd. Override shape via BENCH_LM_* env vars."""
    import jax
    from mmlspark_tpu.parallel import DATA_AXIS, PIPE_AXIS, grid_mesh
    from mmlspark_tpu.models.dnn.pp_training import PipelinedLMTrainer
    L = int(os.environ.get("BENCH_LM_LAYERS", 12))
    D = int(os.environ.get("BENCH_LM_DMODEL", 1024))
    H = int(os.environ.get("BENCH_LM_HEADS", 8))
    FF = int(os.environ.get("BENCH_LM_DFF", 4096))
    V = int(os.environ.get("BENCH_LM_VOCAB", 32768))
    S = int(os.environ.get("BENCH_LM_SEQ", 16384))
    mesh_kind = os.environ.get("BENCH_LM_MESH", "2d")
    if mesh_kind == "4d":
        # round-3 verdict item 9: the FULL sharded 4D program — GPipe
        # ticks + Megatron f/g psums + ring attention with the flash
        # stats backward — compiled and executed at realistic shape on
        # the real chip via a degenerate 1x1x1x1 mesh (axis PRESENCE
        # activates every code path; singleton collectives are identity).
        # Proves the 4D composition fits HBM/VMEM at d>=1024 / 16k ctx,
        # which the d=32 dryrun could not.
        from mmlspark_tpu.parallel import MODEL_AXIS, SEQ_AXIS
        mesh = grid_mesh((1, 1, 1, 1),
                         (DATA_AXIS, PIPE_AXIS, MODEL_AXIS, SEQ_AXIS))
    else:
        mesh = grid_mesh((1, 1), (DATA_AXIS, PIPE_AXIS))
    remat = os.environ.get("BENCH_LM_REMAT", "save_attn")
    if remat not in ("full", "save_attn"):
        # silent coercion would attribute the wrong mode's numbers to
        # the requested one — the record must say what actually ran
        raise SystemExit(f"BENCH_LM_REMAT must be full|save_attn, "
                         f"got {remat!r}")
    t = PipelinedLMTrainer(
        vocab_size=V, mesh=mesh,
        n_microbatches=1, d_model=D, n_heads=H, n_layers=L, d_ff=FF,
        max_len=S, attention="flash", seed=0,
        compute_dtype="bfloat16", remat=remat)
    n_params = sum(int(np.prod(a.shape))
                   for a in jax.tree_util.tree_leaves(t.params))
    toks = np.random.default_rng(0).integers(
        0, V, size=(1, S)).astype(np.int32)
    l1 = t.step(toks)                      # compile + first step
    # chain steps WITHOUT a per-step loss fetch (each fetch is a host
    # round trip); one sync at the end. One more UNTIMED step first: the
    # donated outputs of step 1 carry steady-state buffer layouts, and the
    # first call on them compiles a second executable (~seconds) that must
    # not land inside the timed region.
    import jax.numpy as jnp
    tok_dev = jax.device_put(jnp.asarray(toks, jnp.int32),
                             t._batch_sharding)
    t.params, t.opt_state, loss = t._step(t.params, t.opt_state, tok_dev)
    float(loss)                            # drain the queue before timing
    mm_params = L * (4 * D * D + 2 * D * FF)
    flops_fwd = 2 * S * mm_params + 2 * S * D * V + L * 2 * S * S * D
    flops_step = 3 * flops_fwd
    # the StepClock rides the timed loop: per-rep host dispatch, device
    # time surfacing at the end-of-chain fetch, goodput/MFU from the same
    # analytic flops the headline MFU uses (telemetry/goodput.py)
    from mmlspark_tpu.telemetry.goodput import StepClock
    peak_flops = _bf16_peak_flops()
    clock = StepClock(flops_per_step=flops_step, peak_flops=peak_flops)
    reps = 5
    t0 = time.time()
    for k in range(reps):
        with clock.step(k):
            t.params, t.opt_state, loss = t._step(t.params, t.opt_state,
                                                  tok_dev)
    l2 = clock.device_block(lambda: float(loss))
    dt = (time.time() - t0) / reps
    mfu = flops_step / dt / peak_flops
    gsnap = clock.snapshot()
    print(json.dumps({
        "metric": "lm_train_step_16k_tokens_s", "value": round(dt, 3),
        "unit": "s/step", "vs_baseline": round(mfu, 4), **_device_stamp(),
        "tokens_per_sec": round(S / dt, 1),
        "model_params": n_params,
        "model_flops_per_step": flops_step,
        "mfu_vs_bf16_peak": round(mfu, 4),
        "goodput": round(gsnap["goodput"], 4),
        "mfu": (round(gsnap["mfu"], 4)
                if gsnap["mfu"] is not None else None),
        "step_phases": {k: round(v, 4)
                        for k, v in gsnap["phases"].items()},
        "loss_step1": round(float(l1), 3), "loss_last": round(float(l2), 3),
        "mesh": mesh_kind,
        "remat": remat,
        "model": f"{L}L d={D} {H}h ff={FF} V={V} bf16+remat[{remat}]"
                 f"+flash"}))


# modes that A/B host code (serving, ingest, checkpoints, telemetry) and
# stamp their records with the backend they ran on. Every other mode's
# numbers are device numbers: it refuses to run without the chip.
_HOST_MODES = ("ingest", "oocore", "elastic", "serving", "workloads",
               "ckpt", "telemetry", "quality", "fleet", "online")


def _device_stamp() -> dict:
    """What every device record says about where it was measured."""
    import jax
    dev = jax.devices()[0]
    return {"backend": dev.platform, "device_kind": dev.device_kind,
            "device_count": len(jax.devices())}


def main():
    import jax
    from mmlspark_tpu.utils.hostcache import enable_compile_cache
    # persistent compilation cache: later runs skip the multi-minute XLA
    # compile of the fused boosting scan (shared helper with the tests)
    enable_compile_cache()

    mode = os.environ.get("BENCH_MODE", "")
    if mode not in _HOST_MODES and jax.default_backend() != "tpu":
        raise SystemExit(
            f"bench: BENCH_MODE={mode or 'default'!r} measures the device "
            f"and JAX found platform {jax.default_backend()!r}, not a TPU "
            f"chip; nothing was measured")
    if mode == "flash":
        return _bench_flash()
    if mode == "resnet":
        return _bench_resnet()
    if mode == "resnet_onnx":
        return _bench_resnet_onnx()
    if mode == "lm":
        return _bench_lm_long_context()
    if mode == "gbdt_e2e":
        return _bench_gbdt_e2e()
    if mode == "ingest":
        return _bench_ingest()
    if mode == "oocore":
        return _bench_oocore()
    if mode == "elastic":
        return _bench_elastic()
    if mode == "serving":
        return _bench_serving()
    if mode == "workloads":
        return _bench_workloads()
    if mode == "ckpt":
        return _bench_ckpt()
    if mode == "telemetry":
        return _bench_telemetry()
    if mode == "quality":
        return _bench_quality()
    if mode == "fleet":
        return _bench_fleet()
    if mode == "online":
        return _bench_online()
    if mode == "hist":
        return _bench_hist()
    # predict/shap modes never print the bandwidth fields — don't spend the
    # ~40 timed 1 GiB copy passes measuring one
    copy_gbps = (0.0 if mode in ("predict", "shap")
                 else measure_copy_bandwidth_gbps())
    wide_rows = []
    if os.environ.get("BENCH_SHAPES") == "wide":
        # verdict round-2 item 1: more shapes so the headline isn't a
        # single-point claim. Printed BEFORE the canonical line (the driver
        # parses the last line only).
        for nr, nf, mb, it in ((1_000_000, 32, 63, N_ITERS),
                               (1_000_000, 128, 254, 10)):
            res, _, _, _, _ = run_shape(nr, nf, mb, it, copy_gbps,
                                        "gbdt_train_rows_iters_per_sec")
            wide_rows.append(res)
            print(json.dumps(res))

    res, booster, x, y, staged = run_shape(N_ROWS, N_FEATURES, 63, N_ITERS,
                                           copy_gbps,
                                           "gbdt_train_rows_iters_per_sec")

    # BENCH_EXTRA_r06.json (round 6): the per-phase breakdown, the kernel
    # route table, and the planes-vs-routed A/B, auto-emitted so every
    # "bound" claim traces to a measured line in a committed artifact
    extra = {
        "comment": (
            "Auto-emitted by bench.py (round 6). Headline carries the "
            "in-graph chained-prefix per-phase breakdown (objective / "
            "histogram kernel / split search / row routing, "
            "ms per iteration) and the kernel route chosen per level "
            "(ops/histogram_pallas.kernel_route). planes_ab is the "
            "level-invariant precomputed one-hot plane route "
            "(MMLSPARK_TPU_HIST=planes) A/B that decides next round's "
            "default. Reproduce: python bench.py; BENCH_SHAPES=wide "
            "adds the wide rows; BENCH_MODE=hist prints the "
            "per-(m, B, LO) kernel grid."),
        **_device_stamp(),
        "gbdt_train_headline_8m_32f": res,
    }
    if wide_rows:
        extra["wide_shapes"] = wide_rows
    depth = int(os.environ.get("BENCH_DEPTH", 5))
    extra["hist_route_table"] = {
        "64bins": _hist_route_table(64, depth),
        "64bins_planes": _hist_route_table(64, depth, has_planes=True),
        "255bins": _hist_route_table(255, depth),
    }
    if (mode not in ("predict", "shap")
            and os.environ.get("BENCH_PLANES_AB") != "0"):
        from mmlspark_tpu.models.gbdt.boosting import BoostParams
        p_ab = BoostParams(objective="binary", num_iterations=5,
                           num_leaves=31,
                           max_depth=depth, max_bin=63,
                           min_data_in_leaf=20)
        extra["planes_ab"] = _planes_ab(staged, x, y, p_ab)
        res["planes_ab"] = extra["planes_ab"]
    extra_path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "BENCH_EXTRA_r06.json")
    with open(extra_path, "w") as f:
        json.dump(extra, f, indent=1)

    if os.environ.get("BENCH_MODE") == "shap":
        # exact path-dependent TreeSHAP on device (shap_device.py): the
        # host DFS oracle is O(4^depth) Python recursion per tree — at this
        # scale it is not runnable; the device number is the deliverable
        import time as _t
        n_shap = int(os.environ.get("BENCH_SHAP_ROWS", 100_000))
        t0 = _t.time()
        phi = booster.feature_contributions(x[:n_shap], backend="device")
        dt = _t.time() - t0
        add_err = float(np.abs(phi.sum(1)
                               - booster.raw_score(x[:n_shap])[:, 0]).max())
        print(json.dumps({
            "metric": "gbdt_shap_rows_per_sec", "value": round(n_shap / dt, 1),
            "unit": "rows/s", "vs_baseline": 0.0, **_device_stamp(),
            "trees": booster.n_trees, "depth": booster.max_depth,
            "additivity_err": add_err}))
        return

    if os.environ.get("BENCH_MODE") == "predict":
        # inference throughput (VERDICT weak #4 asked for this number):
        # N_ROWS rows through the full trained ensemble, gather-free descent
        import jax.numpy as jnp
        from mmlspark_tpu.models.gbdt import trainer
        xd = jnp.asarray(x)
        args = (jnp.asarray(booster.split_feature),
                jnp.asarray(booster.threshold),
                jnp.asarray(booster.leaf_value),
                jnp.asarray(booster.tree_class))

        @jax.jit
        def score5(xd):
            def body(c, i):
                # genuinely distinct inputs per rep: the scaling keeps the
                # call loop-variant even under algebraic simplification
                out = trainer.predict_raw(xd * (1.0 + i * 1e-7), *args,
                                          booster.max_depth,
                                          booster.n_classes)
                return c + out.sum(), None
            s, _ = jax.lax.scan(body, jnp.float32(0.0), jnp.arange(5))
            return s
        float(score5(xd))
        t0 = time.time()
        float(score5(xd))
        dt = (time.time() - t0) / 5
        rps = N_ROWS / dt
        # LightGBM CPU predicts ~1e6 rows/s at this tree count (estimate)
        print(json.dumps({
            "metric": "gbdt_predict_rows_per_sec", "value": round(rps, 1),
            "unit": "rows/s", "vs_baseline": round(rps / 1.0e6, 4),
            **_device_stamp()}))
        return

    print(json.dumps(res))


if __name__ == "__main__":
    sys.exit(main())
