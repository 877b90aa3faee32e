#!/usr/bin/env python3
"""record_lm_scoped.py - how `data/lm_scoped.xplane.pb` and
`data/lm_scoped_scopes.json` were made (PR 26), and the on-chip check of the
program's own trace parse against the benchmark's readers. Run on the chip,
from the root of a checkout:

    python3 benchmark/tests/record_lm_scoped.py record
    python3 benchmark/tests/record_lm_scoped.py check
    python3 benchmark/tests/record_lm_scoped.py rehearse   (anywhere)

`record` trains the toy LM of `toy.TOY_GPT` (2 layers, d 64, 2 x 64 tokens,
flash attention, bfloat16, remat "save_attn") through `PipelinedLMTrainer`,
traces two steps as the harness traces (`bench.window`, `bench.make_batch`,
`bench.lm_step`) and keeps the `.xplane.pb` and the step program's scope map
under `chiprun_out/`. `check` runs gpt2-medium at the cell's batch: 20 steps
untraced, then 5 inside `utils.tracing.trace`, and prints, for each region,
the self time by `telemetry.profiler.parse_trace` beside the one by
`readers/scope_sum.py` on the same capture, the step wall traced and
untraced, what the three step spans and a registration cost on this host,
and what `scope_maps()` costs when first asked. With a checkout from before
the scope map on `sys.path` (the parent), `check` prints the step walls
alone."""
import glob
import json
import os
import shutil
import statistics
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [os.getcwd(), BENCH, HERE]
OUT = os.path.join(os.getcwd(), "chiprun_out")


def trainer_for(cfg, opts, seed=0):
    from mmlspark_tpu.models.dnn.pp_training import PipelinedLMTrainer
    from mmlspark_tpu.parallel import DATA_AXIS, PIPE_AXIS, grid_mesh
    return PipelinedLMTrainer(
        vocab_size=cfg["vocab_size"],
        mesh=grid_mesh((1, 1), (DATA_AXIS, PIPE_AXIS)),
        n_microbatches=opts["n_microbatches"], d_model=cfg["n_embd"],
        n_heads=cfg["n_head"], n_layers=cfg["n_layer"], d_ff=cfg["n_inner"],
        max_len=cfg["n_positions"], lr=3e-4, attention=opts["attention"],
        seed=seed, optimizer=opts["optimizer"],
        compute_dtype=opts["compute_dtype"], remat=opts["remat"])


def steps(trainer, rng, batch, seq, vocab, n):
    import jax
    walls = []
    for _ in range(n):
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.make_batch"):
            tokens = rng.integers(0, vocab, (batch, seq)).astype("int32")
        with jax.profiler.TraceAnnotation("bench.lm_step"):
            trainer.step(tokens)
        walls.append(time.perf_counter() - t0)
    return walls


def harness_trace(out):
    """The profiler as `harness.Bench.traced` starts it."""
    import jax
    shutil.rmtree(out, ignore_errors=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(out, profiler_options=options)


def record():
    import jax
    import numpy as np
    import toy
    from mmlspark_tpu.telemetry.perf import scope_maps
    with open(os.path.join(BENCH, "configs", "gpt2-medium.json")) as f:
        opts = json.load(f)["trainer"]
    cfg = toy.TOY_GPT
    trainer = trainer_for(cfg, opts)
    rng = np.random.default_rng(0)
    steps(trainer, rng, 2, 64, cfg["vocab_size"], 3)
    out = os.path.join(OUT, "lm_scoped_trace")
    harness_trace(out)
    with jax.profiler.TraceAnnotation("bench.window"):
        steps(trainer, rng, 2, 64, cfg["vocab_size"], 2)
    jax.profiler.stop_trace()
    written = sorted(glob.glob(os.path.join(out, "plugins", "profile", "*",
                                            "*")))
    print("the profiler wrote:", [(os.path.basename(p), os.path.getsize(p))
                                  for p in written])
    xplane = [p for p in written if p.endswith(".xplane.pb")][-1]
    shutil.copy(xplane, os.path.join(OUT, "lm_scoped.xplane.pb"))
    with open(os.path.join(OUT, "lm_scoped_scopes.json"), "w") as f:
        json.dump({"step": {k: list(v) for k, v in
                            next(iter(scope_maps().values())).items()}}, f,
                  separators=(",", ":"))
    shutil.rmtree(out, ignore_errors=True)
    print("device:", jax.devices()[0].device_kind)


def check(rehearse=False):
    import jax
    import numpy as np
    with open(os.path.join(BENCH, "configs", "gpt2-medium.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(BENCH, "traffic", "steps-8x1024-zipf.json")) as f:
        mix = json.load(f)
    if rehearse:        # the control flow, at the toy size, off the chip
        import toy
        cfg.update(toy.TOY_GPT)
        mix.update(batch=2, seq=64)
    batch, seq, vocab = mix["batch"], mix["seq"], cfg["vocab_size"]
    trainer = trainer_for(cfg, cfg["trainer"])
    rng = np.random.default_rng(0)
    steps(trainer, rng, batch, seq, vocab, 3)
    result = {"device": jax.devices()[0].device_kind}
    untraced = steps(trainer, rng, batch, seq, vocab, 20)
    result["step_median_ms_untraced"] = statistics.median(untraced) * 1e3
    out = os.path.join(OUT, "lm_check_trace")
    try:
        from mmlspark_tpu.telemetry import perf, profiler
        from mmlspark_tpu.utils import tracing
        perf.scope_maps
    except (ImportError, AttributeError):
        harness_trace(out)
        with jax.profiler.TraceAnnotation("bench.window"):
            traced = steps(trainer, rng, batch, seq, vocab, 5)
        jax.profiler.stop_trace()
        result["step_median_ms_traced"] = statistics.median(traced) * 1e3
        print(json.dumps(result))
        return
    shutil.rmtree(out, ignore_errors=True)
    t0 = time.perf_counter()
    with tracing.trace(out):
        t_in = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.window"):
            traced = steps(trainer, rng, batch, seq, vocab, 5)
        t_steps = time.perf_counter() - t_in
    # trace() parses on exit, and the parse asks for the scope maps: the
    # first ask lowers and compiles the step program again
    result["trace_exit_s"] = time.perf_counter() - t0 - t_steps
    result["step_median_ms_traced"] = statistics.median(traced) * 1e3
    t0 = time.perf_counter()
    maps = perf.scope_maps()
    result["scope_maps_again_s"] = time.perf_counter() - t0
    for entry in perf._programs.values():
        entry[1] = None         # forget the maps: time a first ask alone
    t0 = time.perf_counter()
    perf.scope_maps()
    result["scope_maps_first_s"] = time.perf_counter() - t0
    result["scoped_instructions"] = {k: len(v) for k, v in maps.items()}
    records = profiler.parse_trace(out)
    mine = {r: v["self_time_us"] / 1e3 / 5
            for r, v in profiler.region_totals(records).items()}
    if records:         # none off the chip: no device plane
        import harness
        import xplane
        scope_sum = harness.load_module("readers", "scope_sum")
        trace = xplane.Trace.from_file(xplane.find_xplane(out))
        own = scope_sum.self_times(trace)
        placed, conflicts = perf.merged_scope_map()
        theirs = {}
        for region in list(mine):
            want = None if region == profiler.UNSCOPED else region
            ns, _n = scope_sum.sum_scoped(own, placed, {"region": want})
            theirs[region] = ns / 1e6 / 5
        result["ms_per_step_by_region"] = {
            r: {"parse_trace": mine[r], "scope_sum": theirs[r]}
            for r in mine}
        result["busy_ms_per_step"] = trace.busy_s() * 1e3 / 5
        result["conflicts"] = len(conflicts)
    result["unscoped_top"] = [
        [r["op"], r["self_time_us"] / 1e3 / 5] for r in records
        if r["region"] == profiler.UNSCOPED][:12]
    result["roofline_rows"] = {
        k: v for k, v in profiler.get_roofline().rows().items()
        if k.startswith("lm.")}
    written = glob.glob(os.path.join(out, "plugins", "profile", "*", "*"))
    result["the_profiler_wrote"] = sorted(
        (os.path.basename(p), os.path.getsize(p)) for p in written)
    # what the instrumentation costs this host with no capture running
    n = 2000
    t0 = time.perf_counter()
    for _ in range(n):
        with tracing.annotate("lm.step.h2d"):
            pass
        with tracing.annotate("lm.step.dispatch"):
            pass
        with tracing.annotate("lm.step.wait"):
            pass
    result["three_spans_us"] = (time.perf_counter() - t0) / n * 1e6
    t0 = time.perf_counter()
    for _ in range(n):
        perf.register_program("overhead.probe", record)
    result["register_program_us"] = (time.perf_counter() - t0) / n * 1e6
    shutil.rmtree(out, ignore_errors=True)
    print(json.dumps(result))


if __name__ == "__main__":
    os.makedirs(OUT, exist_ok=True)
    {"record": record, "check": check,
     "rehearse": lambda: check(rehearse=True)}[sys.argv[1]]()
