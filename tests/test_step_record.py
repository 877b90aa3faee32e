"""The LM step's record of itself (ISSUE 37): `PipelinedLMTrainer.step` keeps
one `telemetry.profiler.StepRecord` a call (the four host phases gap / h2d /
dispatch / wait, which add up to the step's period, and what the host did
meanwhile), `slow_steps` is the one slow-step rule over them, and the two
scan shells of the step program trace the regions `lm.layers` / `lm.ticks`.
Every clock here is injected; nothing sleeps."""
import gc
import os

import numpy as np
import pytest

from mmlspark_tpu.reliability.metrics import reliability_metrics
from mmlspark_tpu.telemetry import names as tnames
from mmlspark_tpu.telemetry import perf as tperf
from mmlspark_tpu.telemetry import profiler as tprof
from mmlspark_tpu.telemetry.profiler import StepRecord

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DATA = os.path.join(REPO, "benchmark", "tests", "data")


class Clock:
    """A clock that reads what a script says: `tick(dt, ...)` queues the
    steps between readings."""

    def __init__(self, t=100.0):
        self.t, self.steps = t, []

    def tick(self, *dts):
        self.steps.extend(dts)

    def __call__(self):
        self.t += self.steps.pop(0)
        return self.t


def run_step(recorder, clock, gap, h2d, dispatch, wait, compiled=0):
    """One step of `recorder` whose phases take what is given."""
    clock.tick(gap, h2d, dispatch, wait)
    recorder.start()
    recorder.mark()
    recorder.mark()
    return recorder.stop(compiled)


@pytest.fixture
def recorder():
    tprof.get_roofline().clear()
    rec = tprof.StepRecorder()
    rec.clock = Clock()
    return rec


def toy_trainer(n_microbatches, **kw):
    from mmlspark_tpu.models.dnn.pp_training import PipelinedLMTrainer
    from mmlspark_tpu.parallel import DATA_AXIS, PIPE_AXIS, grid_mesh
    return PipelinedLMTrainer(
        vocab_size=61, mesh=grid_mesh((1, 1), (DATA_AXIS, PIPE_AXIS)),
        n_microbatches=n_microbatches, d_model=32, n_heads=2, n_layers=2,
        d_ff=64, max_len=16, remat="save_attn", **kw)


# --------------------------------------------------------------- the record
def test_the_four_phases_add_up_to_the_period(recorder):
    first = run_step(recorder, recorder.clock, 0.0, 0.5, 1.5, 3.0,
                     compiled=1)
    assert first == StepRecord(None, 0.5, 1.5, 3.0, 1, first.preemptions,
                               first.faults, first.gc_s)
    ends = [recorder.clock.t]
    for _ in range(3):
        r = run_step(recorder, recorder.clock, 0.25, 0.5, 1.0, 2.0)
        ends.append(recorder.clock.t)
        assert (r.gap, r.h2d, r.dispatch, r.wait) == (0.25, 0.5, 1.0, 2.0)
        # nothing on the host lies outside the four
        assert r.period == pytest.approx(ends[-1] - ends[-2])
    assert tprof.step_records() == tprof.step_records(tnames.LM_STEP)
    assert len(tprof.step_records()) == 4


def test_trainer_step_keeps_a_record_a_call_and_a_gap_from_the_second():
    tprof.get_roofline().clear()
    trainer = toy_trainer(1)
    clock = trainer._record.clock = Clock()
    tokens = np.zeros((2, 16), np.int32)
    clock.tick(0.0, 0.125, 4.0, 0.5)
    trainer.step(tokens)
    (first,) = tprof.step_records()
    assert first.gap is None and first.compiled >= 1
    assert (first.h2d, first.dispatch, first.wait) == (0.125, 4.0, 0.5)
    # no gap before the first call: the span is absent until the second
    assert tprof.region_stats(tnames.LM_STEP_GAP) is None
    clock.tick(0.75, 0.125, 0.25, 0.5)
    trainer.step(tokens)
    second = tprof.step_records()[-1]
    assert second.gap == 0.75 and second.period == 1.625
    gap = tprof.region_stats(tnames.LM_STEP_GAP)
    assert gap["count"] == 1 and gap["median"] == 0.75
    # the gap is a timing label like the other three spans
    assert tnames.LM_STEP_GAP in tnames.TIMINGS
    assert reliability_metrics.snapshot()[
        tnames.LM_STEP_GAP + ".count"] >= 1


def test_ring_keeps_the_last_records_oldest_first():
    led = tprof.RooflineLedger()
    for i in range(tprof.RING + 10):
        led.note_step("lm.step", StepRecord(0.0, 0.0, 0.0, float(i),
                                            0, 0, 0, 0.0))
    ring = led.step_records("lm.step")
    assert len(ring) == tprof.RING
    assert [r.wait for r in ring[:2]] == [10.0, 11.0]
    assert ring[-1].wait == tprof.RING + 9.0
    assert led.step_records("lm.never") == []
    led.clear()
    assert led.step_records("lm.step") == []


# ------------------------------------------------------------ the slow rule
def even(n, gap=0.1):
    return [StepRecord(gap, 0.1, 0.1, 0.7, 0, 0, 0, 0.0) for _ in range(n)]


@pytest.mark.parametrize("phase", tprof.PHASES)
def test_a_stretched_step_among_thirty_is_slow_in_its_phase(phase):
    records = even(30)
    # 1.5 times the period, all of it in one phase
    records[17] = records[17]._replace(**{phase: getattr(records[17],
                                                         phase) + 0.5})
    (slow,) = tprof.slow_steps(records)
    assert slow["index"] == 17 and slow["record"] is records[17]
    assert slow["median"] == pytest.approx(1.0)
    assert slow["period"] == pytest.approx(1.5)
    assert slow["loss"] == pytest.approx(0.5)
    assert slow["lost"][phase] == pytest.approx(0.5)
    assert sum(slow["lost"].values()) == pytest.approx(slow["loss"])


def test_a_compiling_step_is_never_slow_and_an_even_run_has_none():
    records = even(30)
    assert tprof.slow_steps(records) == []
    records[3] = records[3]._replace(dispatch=40.0, compiled=1)
    assert tprof.slow_steps(records) == []
    # 1.02 of the median is inside the rule's 1.03, 1.04 is not
    records[5] = records[5]._replace(wait=0.72)
    assert tprof.slow_steps(records) == []
    records[5] = records[5]._replace(wait=0.74)
    assert [s["index"] for s in tprof.slow_steps(records)] == [5]
    # too few steady records judge nothing
    assert tprof.slow_steps(records[4:4 + tprof.SLOW_MIN_STEADY - 1]) == []


def test_a_record_without_its_gap_is_judged_by_its_other_phases():
    """A window's first record: its gap holds the end of set-up."""
    records = even(20)
    records[0] = records[0]._replace(gap=None)
    assert tprof.slow_steps(records) == []
    records[0] = records[0]._replace(wait=1.2)
    (slow,) = tprof.slow_steps(records)
    assert slow["lost"]["wait"] == pytest.approx(0.5)
    assert slow["lost"]["gap"] == 0.0


def test_slow_steps_raise_the_two_counters_when_they_are_recorded(recorder):
    def counters():
        snap = reliability_metrics.snapshot()
        return (snap.get(tnames.LM_STEP_SLOW, 0),
                snap.get(tnames.LM_STEP_LOST_SECONDS, 0.0))

    slow0, lost0 = counters()
    run_step(recorder, recorder.clock, 0.0, 0.1, 9.0, 0.7, compiled=1)
    for _ in range(12):
        run_step(recorder, recorder.clock, 0.1, 0.1, 0.1, 0.7)
    assert counters() == (slow0, lost0)
    run_step(recorder, recorder.clock, 0.6, 0.1, 0.1, 0.7)
    slow1, lost1 = counters()
    assert slow1 == slow0 + 1 and lost1 - lost0 == pytest.approx(0.5)
    run_step(recorder, recorder.clock, 0.1, 0.1, 0.1, 0.7)
    assert counters() == (slow1, lost1)
    assert tnames.LM_STEP_SLOW in tnames.COUNTERS
    assert tnames.LM_STEP_LOST_SECONDS in tnames.COUNTERS


# ------------------------------------------------- what stood beside a step
def test_a_collection_inside_a_step_is_its_own_and_none_outside(recorder):
    enabled = gc.isenabled()
    gc.disable()            # no collection but the forced ones
    try:
        gc.collect()        # before the first step: nobody's
        quiet = run_step(recorder, recorder.clock, 0.0, 0.1, 0.1, 0.1)
        recorder.clock.tick(0.1, 0.1, 0.1, 0.1)
        recorder.start()
        gc.collect()        # generation 2, inside the step
        gc.collect(0)       # a young one returns at once
        recorder.mark()
        recorder.mark()
        loud = recorder.stop()
        after = run_step(recorder, recorder.clock, 0.1, 0.1, 0.1, 0.1)
    finally:
        if enabled:
            gc.enable()
    assert quiet.gc_s == 0.0 and after.gc_s == 0.0
    assert loud.gc_s > 0.0
    assert tprof._on_gc in gc.callbacks
    assert gc.callbacks.count(tprof._on_gc) == 1
    # the thread's counts are differences, never negative
    assert min(quiet.preemptions, quiet.faults, loud.preemptions) >= 0


# ------------------------------------------------- the two shells' regions
@pytest.fixture(scope="module")
def shells():
    """Step programs of one toy model at one and at two microbatches:
    their optimized HLO text."""
    out = {}
    for micro in (1, 2):
        trainer = toy_trainer(micro)
        trainer.step(np.zeros((2, 16), np.int32))
        out[micro] = tperf._programs[f"lm.step#{id(trainer):x}"][0]()()
    return out


@pytest.mark.parametrize("micro,region,direction", [
    (1, tnames.LM_LAYERS, "fwd"), (1, tnames.LM_LAYERS, "bwd"),
    (2, tnames.LM_LAYERS, "fwd"), (2, tnames.LM_LAYERS, "bwd"),
    (2, tnames.LM_TICKS, "fwd"), (2, tnames.LM_TICKS, "bwd"),
])
def test_the_scan_shells_have_instructions_of_their_own(shells, micro,
                                                        region, direction):
    assert (region, direction) in set(tperf.scope_map(shells[micro])
                                      .values())


@pytest.mark.parametrize("micro", [1, 2])
def test_the_shells_regions_take_from_the_unscoped_share(shells, micro):
    """The same program read with and without the two regions: a sublayer
    keeps every instruction it had (the innermost region wins) and what
    had no region has less."""
    with_shells = tperf.scope_map(shells[micro])
    without = tperf.scope_map(shells[micro], regions=[
        r for r in tnames.DEVICE_REGIONS
        if r not in (tnames.LM_LAYERS, tnames.LM_TICKS)])
    named = len(tperf._SCOPED_INSTRUCTION_RE.findall(shells[micro]))
    assert all(with_shells[k] == v for k, v in without.items())
    new = {k: v for k, v in with_shells.items() if k not in without}
    assert new and {r for r, _ in new.values()} <= {tnames.LM_LAYERS,
                                                    tnames.LM_TICKS}
    assert (named - len(with_shells)) / named \
        < (named - len(without)) / named


def test_both_shells_are_device_regions_with_a_row_in_the_docs():
    with open(os.path.join(REPO, "docs", "observability.md")) as f:
        doc = f.read()
    for name in (tnames.LM_LAYERS, tnames.LM_TICKS):
        assert name in tnames.DEVICE_REGIONS and name in tprof.REGIONS
        assert f"| `{name}` | device" in doc
    assert f"| `{tnames.LM_STEP_GAP}` | host" in doc


# --------------------------------------------- a capture read by instruction
def test_by_instruction_reads_a_recorded_capture(capsys):
    """The v5e capture PR 26 recorded (two steps of a toy LM) with the
    scope maps kept beside it: read, not edited."""
    import json
    pb = os.path.join(DATA, "lm_scoped.xplane.pb")
    before = os.path.getmtime(pb), os.path.getsize(pb)
    with open(os.path.join(DATA, "lm_scoped_scopes.json")) as f:
        scopes = json.load(f)
    lines = tprof.by_instruction(pb, scopes, steps=2, top=5)
    assert lines[0].startswith("--- 5 largest of ")
    assert "flash_fwd" in lines[1] and "lm.attn.flash" in lines[1] \
        and " fwd " in lines[1]
    assert len(lines[1:lines.index("--- every sort / gather / scatter")]) \
        == 5
    totals = lines[lines.index("--- regions by direction") + 1:]
    assert totals[-1].endswith("busy")
    busy = float(totals[-1].split()[0])
    assert busy == pytest.approx(
        sum(float(t.split()[0]) for t in totals[:-1]), abs=0.02)
    assert any("lm.mlp" in t and t.endswith("remat") for t in totals)
    # the same through the entry point
    assert tprof.main([pb, "--scopes",
                       os.path.join(DATA, "lm_scoped_scopes.json"),
                       "--steps", "2", "--top", "5"]) == 0
    assert capsys.readouterr().out.splitlines() == lines
    assert (os.path.getmtime(pb), os.path.getsize(pb)) == before
    # a capture that is not there prints an empty table and raises nothing
    assert tprof.by_instruction(os.path.join(DATA, "none"))[0] == \
        "--- 0 largest of 0 instructions"
