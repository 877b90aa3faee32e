#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives both engines that carry Pallas kernels once, through the entry
points a user calls, at the full width of the shapes the benchmark uses:

  kernels  every histogram route `kernel_route(m, B)` selects and every
           flash-attention kernel (forward, backward, the ring "stats"
           pair), at a small row count, each against the repo's own
           reference;
  gbdt     GBDTClassifier().fit(Table) -> fused boosting scan -> routed
           Pallas histograms -> transform, 8M rows x 32 features x 64 bins;
  lm       PipelinedLMTrainer.step x3, 12 layers x d_model 1024, 16,384
           tokens, bf16, flash forward/backward inside shard_map.

One process, phases in sequence, no child that needs the chip; any phase
that raises ends the run non-zero. The script never selects a platform: it
exits non-zero when JAX's default platform is not `tpu`, and when any
`MMLSPARK_TPU_HIST*` variable is set (those re-route the histogram). The
last line of stdout is `{"ok": true, "device": {...}}`.

The phases are functions of their sizes so that tests/test_chip_smoke.py
can drive the same control flow at toy sizes on the CPU (kernels in
interpret mode); `main()` fixes the full-width sizes. Wall time, compile
seconds and peak device bytes per phase are set-up facts, not metrics.
"""
import contextlib
import functools
import json
import os
import sys
import time

import numpy as np

MOSAIC_CALL = "tpu_custom_call"
# ops/histogram_pallas.py PRECISION CONTRACT: grad/hess operands round to
# bfloat16 (8 significand bits, "~0.4% per value"), accumulation stays f32 —
# so a bin's error is bounded by 2^-8 of the bin's sum of |values|; the
# {0,1} count operands are exact
HIST_REL_TOL = 2.0 ** -8
# ops/flash_attention.py PRECISION CONTRACT: products follow the input
# dtype (one bf16 pass for bf16, Mosaic's fp32 contract precision for f32);
# softmax and accumulation are f32. Bands are max error over max
# |reference|. bf16: an operand rounds by 2^-8 = 3.9e-3; the chip reads
# 7e-4..2.4e-3 forward and 3e-3..6e-3 on gradients (PR 21). f32: the
# forward band is the one tests/test_flash_attention.py pins in interpret
# mode; the chip reads 1.3e-7..2.4e-7 forward, 3e-7..5e-5 on gradients.
# An f32 row that came back with bf16 products (2e-3 and up) fails.
FLASH_TOL = {"bfloat16": {"fwd": 1e-2, "grad": 2e-2},
             "float32": {"fwd": 2e-5, "grad": 2e-4}}

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileMeter:
    """Process-wide tally of JAX's own compile events (jax.monitoring):
    backend (XLA + Mosaic) compile seconds, which include the time to
    fetch a persistent-cache hit, the number of backend compilations, and
    persistent-cache hits and misses. Tracing and lowering are not in it
    (their events nest, so they do not add up)."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.compiles = self.cache_hits = self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == _COMPILE_EVENT:
            self.seconds += duration
            self.compiles += 1

    def _event(self, event, **_):
        self.cache_hits += event == "/jax/compilation_cache/cache_hits"
        self.cache_misses += event == "/jax/compilation_cache/cache_misses"

    def snapshot(self):
        return (self.seconds, self.compiles, self.cache_hits,
                self.cache_misses)


_METER = None


def meter() -> CompileMeter:
    global _METER
    if _METER is None:
        _METER = CompileMeter()
    return _METER


@contextlib.contextmanager
def phase(name: str, report: dict):
    """Time one phase and print its set-up facts as one JSON line.
    `peak_bytes_in_use` is the runtime's high-water mark per device since
    the process started, read at the end of the phase: it is this phase's
    own peak only where it rose above the phase before."""
    import jax
    before, t0 = meter().snapshot(), time.perf_counter()
    try:
        yield
    finally:   # a failing phase still says how far it got
        after = meter().snapshot()
        stats = [d.memory_stats() for d in jax.devices()]
        report.update(
            phase=name, wall_s=round(time.perf_counter() - t0, 2),
            compile_s=round(after[0] - before[0], 2),
            compiles=after[1] - before[1],
            cache_hits=after[2] - before[2],
            cache_misses=after[3] - before[3],
            peak_bytes_in_use=[s and s.get("peak_bytes_in_use")
                               for s in stats])
        print(json.dumps(report), flush=True)


def _rel_err(got, ref) -> float:
    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    if not np.isfinite(got).all():
        return float("inf")
    return float(np.abs(got - ref).max() / (np.abs(ref).max() + 1e-9))


# ---------------------------------------------------------------- kernels
def _hist_row(m: int, n_bins: int, n_rows: int, n_features: int) -> dict:
    """One (m, B) point through the normal entry (no route override):
    the route taken must be the table's, and every bin must sit inside
    the bf16 contract of the XLA scatter reference."""
    import jax
    import jax.numpy as jnp
    from mmlspark_tpu.ops.histogram import (_xla_hist,
                                            node_feature_histograms)
    from mmlspark_tpu.ops.histogram_pallas import kernel_route
    from mmlspark_tpu.reliability.metrics import reliability_metrics
    from mmlspark_tpu.telemetry import names as tnames

    rng = np.random.default_rng(1000 * n_bins + m)
    bins = jnp.asarray(rng.integers(0, n_bins, (n_rows, n_features)),
                       jnp.uint8)
    grad = jnp.asarray(rng.normal(size=n_rows), jnp.float32)
    hess = jnp.asarray(rng.uniform(0.1, 1.0, n_rows), jnp.float32)
    node = jnp.asarray(rng.integers(-1, m, n_rows), jnp.int32)  # -1: dropped
    active = node >= 0
    cw = jnp.asarray(rng.integers(0, 2, n_rows), jnp.float32)   # bagging

    kind, lo = kernel_route(m, n_bins)
    counter = tnames.gbdt_hist_route(kind)
    before = reliability_metrics.snapshot().get(counter, 0)
    got = jax.jit(lambda *a: node_feature_histograms(
        *a, m, n_bins, count_w=cw))(bins, grad, hess, node, active)
    jax.block_until_ready(got)
    if reliability_metrics.snapshot().get(counter, 0) != before + 1:
        raise AssertionError(f"route {kind} not taken")
    with jax.default_matmul_precision("highest"):
        # the reference, and the per-bin sums of |values| that scale it
        ref, mag = jax.jit(lambda g: tuple(
            _xla_hist(bins, v, hess, node, active, m, n_bins, count_w=cw)
            for v in (g, jnp.abs(g))))(grad)
    worst = 0.0
    for g, r, a in zip(got[:2], ref[:2], mag[:2]):
        err = np.abs(np.asarray(g) - np.asarray(r))
        bound = HIST_REL_TOL * np.asarray(a) + 1e-6
        worst = max(worst, float((err / bound).max()))
    if not np.array_equal(np.asarray(got[2]), np.asarray(ref[2])):
        raise AssertionError("count histogram is not exact")
    if not worst <= 1.0:
        raise AssertionError(f"error is {worst:.2f}x the bf16 bound")
    return {"route": f"{kind}:lo{lo}", "err_over_bound": round(worst, 3)}


def _flash_rows(d_head: int, dtype_name: str, seq: int, heads: int):
    """(name, thunk) per flash kernel configuration at one head dim and
    dtype: forward, forward+backward, and the stats pair the ring merges
    (normalized here by acc / l, a shift-invariant readout). A thunk
    returns its verdict or raises."""
    import jax
    import jax.numpy as jnp
    from mmlspark_tpu.ops.flash_attention import (flash_attention,
                                                  flash_attention_stats)
    from mmlspark_tpu.parallel.ring_attention import reference_attention

    dt = jnp.dtype(dtype_name)
    rng = np.random.default_rng(d_head)
    q, k, v = (jnp.asarray(rng.normal(size=(seq, heads, d_head)), dt)
               for _ in range(3))
    w = jnp.asarray(rng.normal(size=(seq, heads, d_head)), jnp.float32)
    # the reference sees the SAME (already rounded) inputs in f32
    qf, kf, vf = (a.astype(jnp.float32) for a in (q, k, v))
    scale = 1.0 / float(np.sqrt(d_head))

    def stats_out(q, k, v):
        acc, _m, l = flash_attention_stats(q, k, v, 0, 0, causal=True,
                                           scale=scale)
        return acc / jnp.moveaxis(l, 0, 1)[..., None]

    def loss(fn):
        return lambda q, k, v: (fn(q, k, v).astype(jnp.float32) * w).sum()

    flash = lambda q, k, v: flash_attention(q, k, v, causal=True)
    dense = lambda q, k, v: reference_attention(q, k, v, causal=True)

    def reference(fn, *args):
        with jax.default_matmul_precision("highest"):
            return jax.jit(fn)(*args)

    def verdict(err, tol):
        if not err <= tol:
            raise AssertionError(f"rel_err {err:.3g} > tol {tol}")
        return {"rel_err": float(f"{err:.3g}"), "tol": tol}

    def fwd(fn):
        return lambda: verdict(
            _rel_err(jax.jit(fn)(q, k, v), reference(dense, qf, kf, vf)),
            FLASH_TOL[dtype_name]["fwd"])

    def grad(fn):
        def run():
            got = jax.jit(jax.grad(loss(fn), argnums=(0, 1, 2)))(q, k, v)
            ref = reference(jax.grad(loss(dense), argnums=(0, 1, 2)),
                            qf, kf, vf)
            return verdict(max(_rel_err(g, r) for g, r in zip(got, ref)),
                           FLASH_TOL[dtype_name]["grad"])
        return run

    tag = f"flash d{d_head} {dtype_name}"
    return [(f"{tag} fwd", fwd(flash)), (f"{tag} fwd+bwd", grad(flash)),
            (f"{tag} stats fwd", fwd(stats_out)),
            (f"{tag} stats bwd", grad(stats_out))]


def kernel_phase(hist_rows: int, hist_features: int, hist_nodes, hist_bins,
                 flash_seq: int, flash_heads: int, flash_head_dims,
                 flash_dtypes) -> dict:
    """Compile and run every kernel row, each against its reference. All
    rows run (one verdict per row is what a bring-up needs); the phase
    raises at the end if any row failed."""
    rows = [(f"hist m{m} B{n_bins}",
             functools.partial(_hist_row, m, n_bins, hist_rows, hist_features))
            for n_bins in hist_bins for m in hist_nodes]
    for d_head in flash_head_dims:
        for dtype_name in flash_dtypes:
            rows += _flash_rows(d_head, dtype_name, flash_seq, flash_heads)
    report, failed = {}, []
    with phase("kernels", report):
        for name, run in rows:
            try:
                verdict = run()
            except Exception as e:  # noqa: BLE001 - re-raised below
                verdict = f"FAILED {type(e).__name__}: {e}"[:600]
                failed.append(name)
            print(f"  {name}: {verdict}", flush=True)
        report.update(rows=len(rows), failed=failed)
    if failed:
        raise RuntimeError(f"{len(failed)} of {len(rows)} kernel rows "
                           f"failed: {failed}")
    return report


# ------------------------------------------------------------------- gbdt
def _hist_routes_taken() -> dict:
    from mmlspark_tpu.reliability.metrics import reliability_metrics
    prefix = "gbdt.hist.route."
    return {k[len(prefix):]: v
            for k, v in reliability_metrics.snapshot().items()
            if k.startswith(prefix) and v}


def gbdt_phase(n_rows: int, n_features: int, n_iters: int, max_depth: int,
               n_score_rows: int, auc_floor: float) -> dict:
    """Seeded synthetic Table -> GBDTClassifier().fit -> transform on a
    batch large enough for the device scorer, through the normal entry
    points (no prebinned staging, no route override)."""
    import jax
    import jax.numpy as jnp
    from mmlspark_tpu import Table, native
    from mmlspark_tpu.models.gbdt import GBDTClassifier
    from mmlspark_tpu.models.gbdt.booster import _HOST_PREDICT_MAX_ROWS
    from mmlspark_tpu.ops.histogram import node_feature_histograms
    from mmlspark_tpu.reliability.metrics import reliability_metrics
    from mmlspark_tpu.train.metrics import auc

    if n_score_rows < _HOST_PREDICT_MAX_ROWS:
        raise ValueError("n_score_rows must reach the device scorer")
    report = {}
    with phase("gbdt", report):
        if not native.available():
            raise RuntimeError("native/kernels.cpp failed to build")
        rng = np.random.default_rng(0)
        # w first: the same problem at every n_rows, so an AUC floor
        # calibrated at a small size holds at the full one
        w = rng.standard_normal(n_features, dtype=np.float32)
        x = rng.standard_normal((n_rows, n_features), dtype=np.float32)
        noise = 0.5 * rng.standard_normal(n_rows, dtype=np.float32)
        y = (x @ w + noise > 0).astype(np.float32)
        params = dict(num_iterations=n_iters, max_bin=63,
                      max_depth=max_depth, num_leaves=31)

        def fit_and_score(**kw):
            reliability_metrics.reset("gbdt.hist.")
            model = GBDTClassifier(**params, **kw).fit(
                Table({"features": x, "label": y}))
            routes = _hist_routes_taken()
            if not routes or set(routes) - {"direct", "joint"}:
                raise AssertionError(
                    f"fit took histogram routes {routes}; expected only "
                    f"the compiled direct/joint kernels")
            scored = model.transform(Table({"features": x[:n_score_rows]}))
            proba = np.asarray(scored["probabilities"])
            if proba.shape != (n_score_rows, 2) or \
                    not np.isfinite(proba).all():
                raise AssertionError("probabilities not finite (n, 2)")
            return model, routes, auc(y[:n_score_rows], proba[:, 1])

        model, routes, fit_auc = fit_and_score()
        report.update(routes=routes, auc=round(fit_auc, 5),
                      trees=model.booster.n_trees)
        if model.booster.n_trees != n_iters:
            raise AssertionError(f"{model.booster.n_trees} trees grown")
        if not fit_auc >= auc_floor:
            raise AssertionError(f"AUC {fit_auc:.4f} < floor {auc_floor}")
        # host numpy descent vs device scan on a slice
        xs = x[:_HOST_PREDICT_MAX_ROWS]
        np.testing.assert_allclose(
            model.booster.raw_score(xs, backend="device"),
            model.booster.raw_score(xs, backend="host"), rtol=0, atol=1e-5)
        n_dev = jax.device_count()
        if n_dev > 1:
            # partition-as-device: the default estimator sharded the fit
            # over every device; it must agree with the one-device fit
            stats = [d.memory_stats() for d in jax.devices()]
            if all(stats):
                shard_bytes = n_rows * n_features // n_dev
                low = [i for i, s in enumerate(stats)
                       if s["peak_bytes_in_use"] < shard_bytes]
                if low:
                    raise AssertionError(
                        f"devices {low} never held a bins shard")
            _, _, one_auc = fit_and_score(num_tasks=1)
            report["auc_one_device"] = round(one_auc, 5)
            if abs(one_auc - fit_auc) > 1e-3:
                raise AssertionError(
                    f"sharded AUC {fit_auc:.5f} vs one-device {one_auc:.5f}")
        # the lowered program of one histogram call, as the fit traces it
        args = (jnp.zeros((1024, n_features), jnp.uint8),
                jnp.zeros(1024, jnp.float32), jnp.ones(1024, jnp.float32),
                jnp.zeros(1024, jnp.int32), jnp.ones(1024, bool))
        report["mosaic_calls"] = jax.jit(
            lambda *a: node_feature_histograms(*a, 1, 64)).lower(
                *args).as_text().count(MOSAIC_CALL)
    return report


# --------------------------------------------------------------------- lm
def lm_mesh(n_devices: int):
    """1 device: the (data 1, pipe 1) mesh of the benchmark's LM cells; 4k
    devices: data k x pipe 2 x model 2."""
    from mmlspark_tpu.parallel import (DATA_AXIS, MODEL_AXIS, PIPE_AXIS,
                                       grid_mesh)
    if n_devices == 1:
        return grid_mesh((1, 1), (DATA_AXIS, PIPE_AXIS))
    if n_devices % 4:
        raise ValueError(f"no LM mesh for {n_devices} devices (1 or 4k)")
    return grid_mesh((n_devices // 4, 2, 2),
                     (DATA_AXIS, PIPE_AXIS, MODEL_AXIS))


def lm_phase(n_layers: int, d_model: int, n_heads: int, d_ff: int,
             vocab: int, seq: int, n_devices: int) -> dict:
    """Three PipelinedLMTrainer.step calls on one repeated batch of `seq`
    tokens per data shard: the loss is finite and falls, and nothing
    compiles after the second step.

    The tokens are cut into one microbatch per pipeline stage (GPipe needs
    M >= P to be a pipeline at all): one chip trains 1 x seq, pipe 2 trains
    2 x seq/2 through the same model. At M=1 a two-stage pipeline makes
    every stage hold both ticks' residuals and logits, and the full-size
    step then needs 16.76 GB of a v5e's 15.75 GB at compile time (PR 21)."""
    import jax
    from mmlspark_tpu.models.dnn.pp_training import PipelinedLMTrainer
    from mmlspark_tpu.parallel import DATA_AXIS, PIPE_AXIS

    report = {}
    with phase("lm", report):
        mesh = lm_mesh(n_devices)
        n_micro = mesh.shape[PIPE_AXIS]
        trainer = PipelinedLMTrainer(
            vocab_size=vocab, mesh=mesh, n_microbatches=n_micro,
            d_model=d_model, n_heads=n_heads, n_layers=n_layers, d_ff=d_ff,
            max_len=seq, attention="flash", seed=0,
            compute_dtype="bfloat16", remat="save_attn")
        tokens = np.random.default_rng(0).integers(
            0, vocab, size=(mesh.shape[DATA_AXIS] * n_micro,
                            seq // n_micro)).astype(np.int32)
        losses, compiles = [], []
        for _ in range(3):
            before = meter().compiles
            losses.append(trainer.step(tokens))
            compiles.append(meter().compiles - before)
        report.update(mesh=dict(mesh.shape), batch=list(tokens.shape),
                      losses=losses, compiles_per_step=compiles)
        if not np.isfinite(losses).all():
            raise AssertionError(f"loss not finite: {losses}")
        if not losses[0] > losses[1] > losses[2]:
            raise AssertionError(f"loss not falling: {losses}")
        if compiles[2]:
            raise AssertionError(f"step 3 compiled {compiles[2]} programs")
        report["mosaic_calls"] = trainer._step.lower(
            trainer.params, trainer.opt_state,
            trainer._to_device(tokens)).as_text().count(MOSAIC_CALL)
    return report


# ------------------------------------------------------------------- main
def preflight() -> dict:
    """Print what JAX found; refuse anything but an unmodified TPU run."""
    import jax
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"jax {jax.__version__} platform={dev.platform} "
          f"device_kind={dev.device_kind!r} count={device['count']}",
          flush=True)
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: JAX found platform {dev.platform!r}, not a "
                 f"TPU chip; nothing was run")
    pinned = sorted(k for k in os.environ if k.startswith("MMLSPARK_TPU_HIST"))
    if pinned:
        sys.exit(f"chip_smoke: unset {', '.join(pinned)}: the smoke run "
                 f"takes the default histogram routing")
    return device


def main() -> int:
    t0 = time.perf_counter()
    device = preflight()
    from mmlspark_tpu import native
    from mmlspark_tpu.utils.hostcache import CACHE_DIR_ENV, enable_compile_cache
    cache = enable_compile_cache() or os.environ[CACHE_DIR_ENV]
    print(f"compile cache: {cache}  native.available()={native.available()}",
          flush=True)
    meter()

    kernel_phase(hist_rows=20_000, hist_features=40,
                 hist_nodes=(1, 2, 4, 8, 16, 32, 64), hist_bins=(64, 255),
                 flash_seq=2048, flash_heads=2, flash_head_dims=(64, 128),
                 flash_dtypes=("bfloat16", "float32"))
    gbdt = gbdt_phase(n_rows=8_000_000, n_features=32, n_iters=5,
                      max_depth=5, n_score_rows=262_144, auc_floor=0.77)
    lm = lm_phase(n_layers=12, d_model=1024, n_heads=8, d_ff=4096,
                  vocab=32768, seq=16384, n_devices=device["count"])
    for name, rep in (("gbdt", gbdt), ("lm", lm)):
        if not rep["mosaic_calls"]:
            raise AssertionError(
                f"{name}: no {MOSAIC_CALL} in the lowered program — the "
                f"kernels were interpreted or replaced")
    seconds, compiles, hits, misses = meter().snapshot()
    print(json.dumps({"total_wall_s": round(time.perf_counter() - t0, 1),
                      "compile_s": round(seconds, 1), "compiles": compiles,
                      "cache_hits": hits, "cache_misses": misses}))
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
