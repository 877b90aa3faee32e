"""The trace reduction on two small traces recorded on a v5e chip in PR 25
(`data/gbdt_tiny.xplane.pb`: one 3-iteration fit of 262,144 x 32 rows;
`data/lm_toy.xplane.pb`: two steps of a 2-layer d=256 LM, 2 x 1024 tokens).
They were taken before the harness existed, so they have `bench.*` spans but
no `bench.window`: the window falls back to first-to-last device event."""
import json
import os

import pytest

import xplane

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def gbdt():
    return xplane.Trace.from_file(os.path.join(DATA, "gbdt_tiny.xplane.pb"))


@pytest.fixture(scope="module")
def lm():
    return xplane.Trace.from_file(os.path.join(DATA, "lm_toy.xplane.pb"))


def _pattern(metric):
    with open(os.path.join(BENCH, "metrics", metric + ".json")) as f:
        return json.load(f)["pattern"]


def test_device_plane_and_spans_are_found(gbdt, lm):
    assert sorted(gbdt.devices) == [0] and len(gbdt.devices[0]) > 500
    assert {s.name for s in gbdt.spans} == {
        "bench.fit", "bench.train_loop", "bench.transform"}
    assert [s.name for s in lm.spans].count("bench.lm_step") == 2


def test_busy_is_a_union_inside_the_window(gbdt, lm):
    for t in (gbdt, lm):
        busy, window = t.busy_s(), t.window_s()
        assert 0 < busy <= window
        # nested events (a while and its body) are not counted twice
        assert busy <= sum(e.dur_ns for e in t.devices[0]) / 1e9
    # the two jit_train_step programs ran 0.714 ms each
    assert lm.busy_s() == pytest.approx(2 * 0.7136e-3, rel=0.02)


def test_histogram_kernels_are_summed_by_the_shipped_pattern(gbdt):
    seconds, n = gbdt.sum_matching(_pattern("hist_ms_per_iter"))
    assert n == 15              # 5 levels x 3 iterations
    assert seconds == pytest.approx(5.585e-3, rel=0.01)
    assert gbdt.sum_matching(_pattern("flash_ms_per_step"))[1] == 15


def test_flash_kernels_are_summed_by_the_shipped_pattern(lm):
    seconds, n = lm.sum_matching(_pattern("flash_ms_per_step"))
    assert n == 12              # (forward, dq, dk/dv) x 2 layers x 2 steps
    assert seconds == pytest.approx(0.465e-3, rel=0.02)
    assert lm.sum_matching(_pattern("hist_ms_per_iter")) == (0.0, 0)


def test_breakdown_self_times_and_gaps(gbdt, lm):
    ops = dict(gbdt.device_ops())
    # self time: the while wrapper keeps only what its body does not cover
    assert ops["pallas_hist custom-call"] == pytest.approx(5.585e-3, rel=0.01)
    assert ops.get("while while", 0.0) < 1e-3
    assert sum(ops.values()) <= gbdt.busy_s() * 1.001
    assert len(ops) <= 10
    gaps = dict(lm.idle_gaps())
    assert set(gaps) <= {"lm_step", "make_batch", "outside_spans"}
    assert sum(gaps.values()) == pytest.approx(
        lm.window_s() - lm.busy_s(), rel=1e-6)
    # the gap between the two steps is the host inside lm_step/make_batch
    assert gaps["lm_step"] > 1e-3


def test_short_name():
    assert xplane.short_name(
        '%pallas_hist.24 = (f32[32,8,32]{2,1,0}, f32[1]) custom-call(u8[3] '
        '%x), custom_call_target="tpu_custom_call"') == \
        "pallas_hist custom-call"
    assert xplane.short_name(
        "%fusion.1 = f32[2048]{0} fusion(f32[2] %a), kind=kLoop") == \
        "fusion fusion kLoop"


def test_trace_sum_reader_fails_on_no_match(gbdt):
    import harness
    reader = harness.load_module("readers", "trace_sum")
    ctx = {"trace": gbdt, "facts": {"traced_iterations": 3}}
    got = reader.read({"pattern": _pattern("hist_ms_per_iter"),
                       "per": "traced_iterations", "scale": 1000}, ctx)
    assert got == pytest.approx(5.585 / 3, rel=0.01)
    with pytest.raises(LookupError):
        reader.read({"pattern": "no_such_kernel", "per": "traced_iterations"},
                    ctx)


def test_a_trace_without_a_device_plane_fails(tmp_path):
    """A CPU trace has host planes only: the reduction refuses it."""
    import jax
    import jax.numpy as jnp
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        jnp.ones(8).sum().block_until_ready()
    jax.profiler.stop_trace()
    with pytest.raises(xplane.TraceError, match="no device plane"):
        xplane.Trace.from_file(xplane.find_xplane(str(tmp_path)))
    with pytest.raises(xplane.TraceError, match="no .xplane.pb"):
        xplane.find_xplane(str(tmp_path / "nothing"))
