"""The scope readers on a trace recorded on a v5e chip in PR 26
(`data/lm_scoped.xplane.pb`: two steps of the toy LM of `toy.TOY_GPT`, 2 x 64
tokens, with the program's scopes, kernel names and step spans in; made by
`record_lm_scoped.py record`) and on the step program's scope map kept beside
it (`data/lm_scoped_scopes.json`). The toy manifest of this file
(`data/toy_scopes_manifest.json`) lists every metric PR 26 added, the GBDT
ones that wait for their cells too; the one PR 25 left is not edited."""
import json
import os

import pytest

import check_manifest
import harness
import toy
import xplane

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
STEPS = 2
LM_REGIONS = ["lm_attn_ms_per_step", "lm_mlp_ms_per_step",
              "lm_head_ms_per_step", "lm_opt_ms_per_step",
              "lm_embed_cast_ms_per_step"]
FLASH = ["flash_fwd_ms_per_step", "flash_dq_ms_per_step",
         "flash_dkv_ms_per_step"]
SPANS = ["lm_step_h2d_ms", "lm_step_dispatch_ms", "lm_step_wait_ms"]


def _spec(metric):
    with open(os.path.join(BENCH, "metrics", metric + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def trace():
    return xplane.Trace.from_file(os.path.join(DATA, "lm_scoped.xplane.pb"))


@pytest.fixture
def program(monkeypatch):
    """The program's registry holding the recorded step program's map, as
    it would after the traced steps; the region rings start empty."""
    import collections
    from mmlspark_tpu.telemetry import perf, profiler
    with open(os.path.join(DATA, "lm_scoped_scopes.json")) as f:
        scopes = json.load(f)
    monkeypatch.setattr(perf, "_programs", collections.OrderedDict())
    perf.register_program("recorded", lambda: scopes["step"])
    profiler.get_roofline().clear()
    return perf


def _read(metric, trace):
    spec = _spec(metric)
    reader = harness.load_module("readers", spec["reader"])
    return reader.read(spec, {"trace": trace,
                              "facts": {"traced_steps": STEPS}})


def test_regions_kernels_and_the_unscoped_rest_add_up_to_busy(trace,
                                                               program):
    parts = {m: _read(m, trace)
             for m in LM_REGIONS + FLASH + ["lm_unscoped_ms_per_step"]}
    assert all(v > 0 for v in parts.values()), parts
    busy_ms = 1e3 * trace.busy_s() / STEPS
    assert sum(parts.values()) == pytest.approx(busy_ms, rel=0.01)
    # what no region claims is reported, and is the smaller part
    assert parts["lm_unscoped_ms_per_step"] < 0.5 * busy_ms


@pytest.mark.parametrize("metric", FLASH)
def test_each_flash_kernel_is_found_by_its_own_name(trace, program, metric):
    seconds, n = trace.sum_matching(_spec(metric)["pattern"])
    assert n == 2 * STEPS           # one call a layer, two layers
    assert _read(metric, trace) == pytest.approx(1e3 * seconds / STEPS)


def test_the_three_kernels_are_the_step_programs_custom_calls(trace,
                                                              program):
    whole = harness.load_module("readers", "trace_sum").read(
        _spec("flash_ms_per_step"),
        {"trace": trace, "facts": {"traced_steps": STEPS}})
    assert sum(_read(m, trace) for m in FLASH) == pytest.approx(whole,
                                                                rel=0.01)


def test_remat_is_a_cut_across_the_regions(trace, program):
    remat = _read("lm_remat_ms_per_step", trace)
    assert 0 < remat < _read("lm_mlp_ms_per_step", trace)


def test_a_named_region_with_no_event_is_an_error_and_unscoped_is_not(
        trace, program):
    scope_sum = harness.load_module("readers", "scope_sum")
    ctx = {"trace": trace, "facts": {"traced_steps": STEPS}}
    with pytest.raises(LookupError, match="gbdt.hist"):
        scope_sum.read({"region": "gbdt.hist", "per": "traced_steps"}, ctx)
    # no program registered: everything is unscoped, nothing is in a region
    program._programs.clear()
    assert scope_sum.read({"region": None}, ctx) == pytest.approx(
        trace.busy_s(), rel=0.01)
    with pytest.raises(LookupError):
        scope_sum.read({"region": "lm.mlp"}, ctx)
    assert scope_sum.read({"region": "lm.mlp"}, {"trace": None}) is None


def test_an_instruction_two_programs_place_differently_is_left_out(trace,
                                                                   program):
    scope_sum = harness.load_module("readers", "scope_sum")
    ctx = {"trace": trace, "facts": {"traced_steps": STEPS}}
    before = scope_sum.read({"region": "lm.mlp"}, ctx)
    with open(os.path.join(DATA, "lm_scoped_scopes.json")) as f:
        other = {k: ["lm.head", "fwd"] for k, v in
                 json.load(f)["step"].items() if v[0] == "lm.mlp"}
    program.register_program("other", lambda: other)
    with pytest.raises(LookupError, match=f"{len(other)} that two programs"):
        scope_sum.read({"region": "lm.mlp"}, ctx)
    unscoped = scope_sum.read({"region": None}, ctx)
    program._programs.pop("other")
    assert unscoped == pytest.approx(
        scope_sum.read({"region": None}, ctx) + before)


def test_the_programs_step_spans_lie_inside_the_benchmarks(trace):
    """`lm.step.h2d/dispatch/wait` are TraceAnnotations on the host plane,
    on the device trace's clock, each inside one `bench.lm_step`."""
    import jax
    data = jax.profiler.ProfileData.from_file(
        os.path.join(DATA, "lm_scoped.xplane.pb"))
    mine = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for plane in data.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith("lm.step.")]
    steps = [(s.start_ns, s.start_ns + s.dur_ns) for s in trace.spans
             if s.name == "bench.lm_step"]
    assert sorted(n for n, _a, _b in mine) == sorted(
        ["lm.step.h2d", "lm.step.dispatch", "lm.step.wait"] * STEPS)
    assert len(steps) == STEPS
    for _name, a, b in mine:
        assert sum(s0 <= a and b <= s1 for s0, s1 in steps) == 1
    # the three spans account for the step's wall (3.92 of 4.12 ms here)
    inside = sum(b - a for _n, a, b in mine)
    assert 0.9 * sum(b - a for a, b in steps) < inside <= sum(
        b - a for a, b in steps)


@pytest.mark.parametrize("metric", SPANS)
def test_program_span_reads_the_ring_or_nothing(program, metric):
    from mmlspark_tpu.telemetry import profiler
    spec = _spec(metric)
    reader = harness.load_module("readers", "program_span")
    assert reader.read(spec, {}) is None
    for s in (0.004, 0.002, 0.100):
        profiler.note_region(spec["region"], s)
    assert reader.read(spec, {}) == pytest.approx(4.0)
    assert reader.read(dict(spec, stat="p95"), {}) == pytest.approx(100.0)


def test_toy_manifest_lists_every_new_metric_and_the_harness_reads_them(
        tmp_path, trace, program, monkeypatch):
    """What a later PR does to list the metrics in a cell: entries and
    files. The harness then reads them all from one traced run."""
    manifest_path = toy.build(str(tmp_path))
    with open(os.path.join(DATA, "toy_scopes_manifest.json")) as f:
        manifest = json.load(f)
    assert check_manifest.check(manifest, str(tmp_path)) == []
    listed = {m["name"] for m in manifest["per_layer"]}
    shipped = {f[:-5] for f in os.listdir(os.path.join(BENCH, "metrics"))}
    assert shipped <= listed
    from mmlspark_tpu.telemetry import profiler
    for span in ("lm.step.h2d", "lm.step.dispatch", "lm.step.wait"):
        profiler.note_region(span, 0.001)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                       str(tmp_path / "jax_cache"))
    bench = harness.Bench(manifest, "toy-lm", 3, 1.0, 1, 0.0,
                          os.path.dirname(manifest_path),
                          os.path.join(str(tmp_path), "benchmark"),
                          require_tpu=False)
    bench.trace = trace
    bench.peaks = harness.peaks_for("TPU v5 lite", bench.bench_dir)
    line = bench.per_layer({"facts": {"traced_steps": STEPS}},
                           {"lm_tokens_per_s": 1000.0})
    assert set(LM_REGIONS + FLASH + SPANS + [
        "lm_unscoped_ms_per_step", "lm_remat_ms_per_step", "lm_mfu",
        "flash_ms_per_step", "flash_roofline"]) <= set(line)
    assert all(line[m]["unit"] == "ms" for m in LM_REGIONS + FLASH + SPANS)


@pytest.mark.parametrize("metric,timer", [
    ("bin_dispatch_s", "gbdt.fit.bin_dispatch"),
    ("assemble_s", "gbdt.fit.assemble"),
    ("init_score_s", "gbdt.fit.init_score"),
    ("profile_s", "gbdt.estimator.profile")])
def test_gbdt_timer_metrics_name_spans_the_program_declares(metric, timer):
    from mmlspark_tpu.telemetry import names
    spec = _spec(metric)
    assert spec == {"reader": "timer", "timer": timer}
    assert timer in names.HOST_REGIONS and timer in names.TIMINGS
    reader = harness.load_module("readers", "timer")
    program = {timer + ".seconds": 3.0, timer + ".count": 2}
    assert reader.read(spec, {"program": program}) == 1.5
    assert reader.read(spec, {"program": {}}) is None


@pytest.mark.parametrize("metric,region", [
    ("split_ms_per_iter", "gbdt.split"), ("route_ms_per_iter", "gbdt.route"),
    ("objective_ms_per_iter", "gbdt.objective"),
    ("bin_device_s", "gbdt.bin")])
def test_gbdt_scope_metrics_name_regions_the_program_declares(metric,
                                                               region):
    from mmlspark_tpu.telemetry import names
    spec = _spec(metric)
    assert spec["reader"] == "scope_sum" and spec["region"] == region
    assert region in names.DEVICE_REGIONS
