"""The step-record reader (`readers/step_record.py`, ISSUE 37) on a toy run
shaped like the LM drivers' loop (warm-up, a window of `lm_step` spans, then
traced steps), the region reader for a program that lacks a region
(`readers/region_sum.py`), and the manifest with the nine new metrics."""
import collections
import json
import os
import time

import numpy as np
import pytest

import check_manifest
import harness

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
CELLS = ["gpt2m-train-8x1024", "qwen3next-train-2x8192",
         "lfm2moe-train-4x8192", "phi4flash-train-2x8192"]
RECORD = ["lm_step_gap_ms", "lm_step_slow_steps", "lm_step_lost_ms",
          "lm_step_lost_wait_ms", "lm_step_lost_gap_ms",
          "lm_step_slow_gc_ms", "lm_step_slow_preemptions"]
REGION = {"lm_layer_scan_ms_per_step": "lm.layers",
          "lm_tick_scan_ms_per_step": "lm.ticks"}
WINDOW, TRACED = 24, 3


def _spec(metric):
    with open(os.path.join(BENCH, "metrics", metric + ".json")) as f:
        return json.load(f)


def _read(metric, ctx):
    spec = _spec(metric)
    return harness.load_module("readers", spec["reader"]).read(spec, ctx)


@pytest.fixture(scope="module")
def trainer():
    from mmlspark_tpu.models.dnn.pp_training import PipelinedLMTrainer
    from mmlspark_tpu.parallel import DATA_AXIS, PIPE_AXIS, grid_mesh
    return PipelinedLMTrainer(
        vocab_size=61, mesh=grid_mesh((1, 1), (DATA_AXIS, PIPE_AXIS)),
        n_microbatches=1, d_model=32, n_heads=2, n_layers=1, d_ff=64,
        max_len=16)


def toy_run(trainer, stall_before=None, stall_s=0.2):
    """The drivers' loop at toy size: three warm-up steps, a window of
    `WINDOW` steps each inside an `lm_step` span, `TRACED` more as the
    traced steps; `stall_before` sleeps in the driver's part of that window
    step's gap. A step of this toy takes a millisecond, so the clock the
    records read is slowed by a steady 100 ms a phase reading: the host's
    jitter stays far under the rule's 3%."""
    from mmlspark_tpu.telemetry import profiler
    profiler.get_roofline().clear()
    tokens = np.zeros((2, 16), np.int32)
    spans = collections.defaultdict(list)
    padded = [0.0]

    def clock():
        padded[0] += 0.1
        return time.perf_counter() + padded[0]

    trainer._record.clock = clock
    try:
        for _ in range(3):
            trainer.step(tokens)
        for i in range(WINDOW + TRACED):
            if i == stall_before:
                time.sleep(stall_s)
            t0 = time.perf_counter()
            trainer.step(tokens)
            spans["lm_step"].append(time.perf_counter() - t0)
    finally:
        trainer._record.clock = time.perf_counter
    return {"trace": object(), "spans": spans, "program": {},
            "facts": {"traced_steps": TRACED}}


def test_reader_aligns_to_the_window_and_reads_zero_without_a_stall(trainer):
    ctx = toy_run(trainer)
    reader = harness.load_module("readers", "step_record")
    records = reader.window_records(ctx)
    from mmlspark_tpu.telemetry.profiler import step_records
    kept = step_records("lm.step")
    # the window alone: not the warm-up before it, not the traced steps
    assert len(kept) == 3 + WINDOW + TRACED and len(records) == WINDOW
    assert records[1:] == kept[4:3 + WINDOW]
    # the first record's gap holds the end of set-up: judged without it
    assert records[0].gap is None and kept[3].gap is not None
    values = {m: _read(m, ctx) for m in RECORD}
    assert values["lm_step_gap_ms"] == pytest.approx(100.0, abs=10.0)
    for m in RECORD[1:]:
        assert values[m] == 0, (m, values)


def test_reader_finds_a_sleep_in_the_drivers_gap_as_one_slow_step(trainer):
    ctx = toy_run(trainer, stall_before=10)
    values = {m: _read(m, ctx) for m in RECORD}
    assert values["lm_step_slow_steps"] == 1
    assert values["lm_step_lost_ms"] == pytest.approx(200.0, abs=30.0)
    # all of it in the gap, none in the wait
    assert values["lm_step_lost_gap_ms"] == pytest.approx(
        values["lm_step_lost_ms"], abs=2.0)
    assert values["lm_step_lost_wait_ms"] == pytest.approx(0.0, abs=2.0)
    assert values["lm_step_slow_gc_ms"] == 0
    # a stall in the traced steps is outside the window
    ctx = toy_run(trainer, stall_before=WINDOW + 1)
    assert _read("lm_step_slow_steps", ctx) == 0
    assert _read("lm_step_lost_ms", ctx) == 0


def test_a_program_without_records_makes_every_metric_absent(trainer,
                                                             monkeypatch):
    ctx = toy_run(trainer)
    ctx["spans"] = {}
    assert all(_read(m, ctx) is None for m in RECORD)
    # a program from before the records
    from mmlspark_tpu.telemetry import profiler
    monkeypatch.delattr(profiler, "step_records")
    assert all(_read(m, toy_run(trainer)) is None for m in RECORD)


@pytest.mark.parametrize("metric,region", sorted(REGION.items()))
def test_region_reader_is_absent_before_the_region_and_scope_sum_after(
        metric, region, monkeypatch):
    import xplane
    from mmlspark_tpu.telemetry import names, perf
    data = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
    trace = xplane.Trace.from_file(os.path.join(data, "lm_scoped.xplane.pb"))
    with open(os.path.join(data, "lm_scoped_scopes.json")) as f:
        scopes = json.load(f)
    monkeypatch.setattr(perf, "_programs", collections.OrderedDict())
    perf.register_program("recorded", lambda: scopes["step"])
    ctx = {"trace": trace, "facts": {"traced_steps": 2}}
    assert _spec(metric)["region"] == region
    assert region in names.DEVICE_REGIONS
    # the recorded program (PR 26) has no instruction in the region and
    # the program declares it: scope_sum's error, never a zero
    with pytest.raises(LookupError):
        _read(metric, ctx)
    # a program that does not declare the region: absent, no error
    monkeypatch.setattr(names, "DEVICE_REGIONS", {
        k: v for k, v in names.DEVICE_REGIONS.items() if k != region})
    assert _read(metric, ctx) is None
    # and where the region has events it is scope_sum's number
    moved = {k: ([region, v[1]] if v[0] == "lm.mlp" else v)
             for k, v in scopes["step"].items()}
    monkeypatch.undo()
    monkeypatch.setattr(perf, "_programs", collections.OrderedDict())
    perf.register_program("recorded", lambda: moved)
    mlp = dict(_spec("lm_mlp_ms_per_step"), region=region)
    assert _read(metric, ctx) == pytest.approx(
        harness.load_module("readers", "scope_sum").read(mlp, ctx))
    assert _read(metric, ctx) > 0


def test_manifest_passes_with_the_nine_new_metrics():
    manifest = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    assert check_manifest.check(manifest, ROOT) == []
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    tail = [m["name"] for m in manifest["per_layer"][-9:]]
    assert sorted(tail) == sorted(RECORD + list(REGION))
    for name in RECORD + list(REGION):
        m = by_name[name]
        assert m["layer"] == "lm_trainer"
        assert m["moves"] == "lm_tokens_per_s" and m["better"] == "lower"
        assert set(m["workloads"]) <= set(CELLS)
        spec = _spec(name)
        assert os.path.isfile(os.path.join(BENCH, "readers",
                                           spec["reader"] + ".py"))
    for name in RECORD:
        assert by_name[name]["workloads"] == CELLS
        assert _spec(name)["reader"] == "step_record"
    assert by_name["lm_step_slow_steps"]["source"] == "program_counter"
    assert by_name["lm_step_lost_ms"]["source"] == "program_span"
    for name in REGION:
        assert by_name[name]["source"] == "device_trace"
        assert _spec(name)["reader"] == "region_sum"
