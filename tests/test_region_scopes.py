"""The region vocabulary from host span to HLO instruction (ISSUE 26):
`telemetry.perf.scope_map` reads `jax.named_scope` regions out of a compiled
program's `op_name` metadata, by instruction name; the program registry
holds no owner alive and compiles nothing until asked; `utils.tracing.
annotate` keeps a bounded ring of durations per region; and
`PipelinedLMTrainer.step` records its three spans and its own compiles."""
import gc
import weakref

import numpy as np
import pytest

from mmlspark_tpu.reliability.metrics import reliability_metrics
from mmlspark_tpu.telemetry import names as tnames
from mmlspark_tpu.telemetry import perf as tperf
from mmlspark_tpu.telemetry import profiler as tprof
from mmlspark_tpu.utils import tracing

_HLO = """
HloModule jit_f, entry_computation_layout={()->f32[]}

%fused_computation (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  ROOT %multiply.1 = f32[8]{0} multiply(%p, %p), metadata={op_name="jit(f)/jvp()/while/body/vmap(lm.mlp)/mul"}
}

ENTRY %main.9 () -> f32[] {
  %fusion.1 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(f)/jvp()/while/body/closed_call/vmap(lm.mlp)/dot_general" source_file="x.py"}
  %fusion.2 = f32[8]{0} fusion(%a), kind=kLoop, metadata={op_name="jit(f)/transpose(jvp())/while/body/closed_call/checkpoint/vmap(lm.mlp)/dot_general"}
  %fusion.3 = f32[8]{0} fusion(%a), kind=kLoop, metadata={op_name="jit(f)/transpose(jvp())/while/body/checkpoint/rematted_computation/vmap(lm.mlp)/dot_general"}
  %flash_fwd.4 = bf16[2,8]{1,0} custom-call(%q), custom_call_target="tpu_custom_call", metadata={op_name="jit(f)/jvp()/while/body/vmap(lm.attn)/lm.attn.flash/flash_fwd/pallas_call"}
  %dot.5 = f32[8]{0} dot(%a, %b), metadata={op_name="jit(f)/jvp()/while/body/vmap(lm.attn)/dot_general"}
  %copy.6 = f32[8]{0} copy(%a)
  %add.7 = f32[8]{0} add(%a, %b), metadata={op_name="jit(f)/jit(main)/add"}
  ROOT %reduce.8 = f32[] reduce(%a), metadata={op_name="jit(f)/lm.opt/reduce_sum"}
}
"""


@pytest.mark.parametrize("instruction,where", [
    ("fusion.1", ("lm.mlp", "fwd")),
    ("fusion.2", ("lm.mlp", "bwd")),
    ("fusion.3", ("lm.mlp", "remat")),
    # longest token wins: lm.attn.flash inside vmap(lm.attn)
    ("flash_fwd.4", ("lm.attn.flash", "fwd")),
    ("dot.5", ("lm.attn", "fwd")),
    ("reduce.8", ("lm.opt", "fwd")),       # a ROOT instruction
    ("multiply.1", ("lm.mlp", "fwd")),     # inside a fused computation
    ("copy.6", None),                      # no metadata: maps to nothing
    ("add.7", None),                       # metadata, no region token
])
def test_scope_map_on_hlo_text(instruction, where):
    assert tperf.scope_map(_HLO).get(instruction) == where


def test_region_of_prefers_innermost_and_the_longer_of_two_at_one_place():
    assert tperf.region_of("a/lm.attn/b/lm.attn.flash/c") == "lm.attn.flash"
    assert tperf.region_of("a/vmap(lm.attn.flash)/c") == "lm.attn.flash"
    assert tperf.region_of("a/gbdt.hist/b/gbdt.split/c") == "gbdt.split"
    # scopes nest: an outer region with a longer name does not outrank
    assert tperf.region_of("a/gbdt.objective/b/gbdt.hist/c") == "gbdt.hist"
    assert tperf.region_of("jit(f)/jit(main)/mul") is None


def test_merged_scope_map_leaves_out_what_two_programs_place_differently():
    merged, conflicts = tperf.merged_scope_map({
        "a": {"fusion.1": ("lm.mlp", "fwd"), "fusion.2": ("lm.attn", "fwd")},
        "b": {"fusion.1": ["lm.mlp", "fwd"], "fusion.2": ["lm.head", "fwd"],
              "fusion.3": ["gbdt.hist", "fwd"]}})
    assert merged == {"fusion.1": ("lm.mlp", "fwd"),
                      "fusion.3": ("gbdt.hist", "fwd")}
    assert conflicts == ["fusion.2"]


# ------------------------------------------------------------- the registry
class _Owner:
    calls = 0

    def text(self):
        type(self).calls += 1
        return _HLO


@pytest.fixture
def registry(monkeypatch):
    import collections
    monkeypatch.setattr(tperf, "_programs", collections.OrderedDict())
    _Owner.calls = 0
    return tperf._programs


def test_registry_compiles_nothing_until_asked_and_then_once(registry):
    owner = _Owner()
    tperf.register_program("toy", owner.text)
    assert _Owner.calls == 0 and list(registry) == ["toy"]
    assert tperf.scope_maps()["toy"]["fusion.1"] == ("lm.mlp", "fwd")
    tperf.scope_maps()
    assert _Owner.calls == 1
    # registering the label again replaces the program and its map
    tperf.register_program("toy", owner.text)
    tperf.scope_maps()
    assert _Owner.calls == 2


def test_registry_holds_no_owner_alive(registry):
    owner = _Owner()
    gone = weakref.ref(owner)
    tperf.register_program("toy", owner.text)
    del owner
    gc.collect()
    assert gone() is None
    assert tperf.scope_maps() == {} and not registry


def test_registry_is_bounded_and_never_raises(registry):
    for i in range(tperf._MAX_PROGRAMS + 4):
        tperf.register_program(f"p{i}", lambda: _HLO)
    assert len(registry) == tperf._MAX_PROGRAMS and "p0" not in registry

    def broken():
        raise RuntimeError("cannot lower again")
    tperf.register_program("broken", broken)
    tperf.register_program("gone", lambda: None)
    tperf.register_program("ready", lambda: {"f.1": ["lm.mlp", "fwd"]})
    maps = tperf.scope_maps()
    assert "broken" not in maps and "gone" not in maps
    assert maps["ready"] == {"f.1": ["lm.mlp", "fwd"]}
    assert "broken" not in registry and "gone" not in registry


def test_aot_cache_registers_what_it_holds_weakly(registry):
    import jax
    import jax.numpy as jnp

    def fn(x):
        with jax.named_scope(tnames.GBDT_SPLIT):
            return jnp.sin(x) * 2.0
    cache = tperf.AotCache(fn, label="toy.aot", log=tperf.CompileLog())
    cache(jnp.ones((4, 3)))
    assert list(registry) == ["toy.aot[4x3]"]
    regions = {r for r, _d in tperf.scope_maps()["toy.aot[4x3]"].values()}
    assert regions == {tnames.GBDT_SPLIT}
    # a second signature is a second program; a dropped cache reads as gone
    cache(jnp.ones((8, 3)))
    assert set(registry) == {"toy.aot[4x3]", "toy.aot[8x3]"}
    del cache
    gc.collect()
    assert set(tperf.scope_maps()) == {"toy.aot[4x3]"}   # its map was kept


# ------------------------------------------------------- the ring of a region
def test_note_region_ring_is_bounded_and_its_median_is_right():
    led = tprof.RooflineLedger()
    for i in range(tprof.RING + 44):
        led.note_region("lm.step.wait", float(i))
    ring = led.durations("lm.step.wait")
    assert len(ring) == tprof.RING and ring[0] == 44.0
    assert led.rows()["lm.step.wait"]["occurrences"] == tprof.RING + 44
    # a note of several occurrences is a total, not a duration
    led.note_region("gbdt.hist", 3.0, occurrences=6, source="bench-phase")
    assert led.durations("gbdt.hist") == []


def test_region_stats_and_the_timing_label_of_a_host_region():
    tprof.get_roofline().clear()
    before = reliability_metrics.snapshot().get(
        tnames.LM_STEP_H2D + ".count", 0)
    for s in (0.001, 0.003, 0.002, 0.010, 0.004):
        tprof.note_region(tnames.LM_STEP_H2D, s)
    stats = tprof.region_stats(tnames.LM_STEP_H2D)
    assert stats["count"] == 5 and stats["median"] == 0.003
    assert stats["p95"] == 0.010
    assert stats["seconds"] == pytest.approx(0.020)
    assert tprof.region_stats("lm.step.never") is None
    snap = reliability_metrics.snapshot()
    assert snap[tnames.LM_STEP_H2D + ".count"] == before + 5
    # a region that is no timing label stays out of the registry
    tprof.note_region(tnames.TRAIN_STEP_SPAN, 0.5)
    assert tnames.TRAIN_STEP_SPAN + ".count" not in \
        reliability_metrics.snapshot()


def test_annotate_is_a_region_with_attributes_and_propagates_errors():
    tprof.get_roofline().clear()
    with tracing.annotate(tnames.GBDT_FIT_BOOST, iterations=20):
        assert tprof.current_region() == tnames.GBDT_FIT_BOOST
    assert tprof.current_region() is None
    with pytest.raises(KeyError):
        with tracing.annotate(tnames.GBDT_FIT_BOOST):
            raise KeyError("x")
    assert tprof.region_stats(tnames.GBDT_FIT_BOOST)["count"] == 2


# ------------------------------------------------------------ the LM trainer
@pytest.fixture(scope="module")
def lm():
    """A toy `PipelinedLMTrainer` as the benchmark's cell builds it (flash,
    bfloat16, remat "save_attn"), stepped five times with one change of
    shape; what each step recorded, and its step program's scope map."""
    from mmlspark_tpu.models.dnn.pp_training import PipelinedLMTrainer
    from mmlspark_tpu.parallel import DATA_AXIS, PIPE_AXIS, grid_mesh
    trainer = PipelinedLMTrainer(
        vocab_size=257, mesh=grid_mesh((1, 1), (DATA_AXIS, PIPE_AXIS)),
        n_microbatches=1, d_model=64, n_heads=2, n_layers=2, d_ff=128,
        max_len=64, attention="flash", compute_dtype="bfloat16",
        remat="save_attn")
    tokens = np.random.default_rng(0).integers(0, 257, (2, 64)).astype(
        np.int32)
    spans = (tnames.LM_STEP_H2D, tnames.LM_STEP_DISPATCH,
             tnames.LM_STEP_WAIT)

    def counts():
        snap = reliability_metrics.snapshot()
        return ([snap.get(s + ".count", 0) for s in spans],
                snap.get(tnames.LM_STEP_COMPILES, 0))

    deltas = []
    for batch in (tokens, tokens, tokens, tokens[:, :32], tokens[:, :32]):
        (s0, c0) = counts()
        assert np.isfinite(trainer.step(batch))
        (s1, c1) = counts()
        deltas.append(([b - a for a, b in zip(s0, s1)], c1 - c0))
    label = f"lm.step#{id(trainer):x}"
    return {"trainer": trainer, "deltas": deltas, "label": label,
            "scopes": tperf.scope_maps()[label]}


def test_step_records_one_of_each_span_and_its_own_compiles(lm):
    assert [d[0] for d in lm["deltas"]] == [[1, 1, 1]] * 5
    compiles = [d[1] for d in lm["deltas"]]
    # the first step compiles; the second may once more (its inputs now
    # carry the program's own output layouts); then none until the shape
    # changes, and none after that
    assert compiles[0] == 1 and compiles[1] in (0, 1)
    assert compiles[2] == 0 and compiles[3] == 1 and compiles[4] == 0


@pytest.mark.parametrize("region,direction", [
    (tnames.LM_EMBED, "fwd"), (tnames.LM_EMBED, "bwd"),
    (tnames.LM_ATTN, "fwd"), (tnames.LM_ATTN, "bwd"),
    (tnames.LM_ATTN_FLASH, "fwd"), (tnames.LM_ATTN_FLASH, "bwd"),
    (tnames.LM_MLP, "fwd"), (tnames.LM_MLP, "bwd"),
    (tnames.LM_MLP, "remat"),
    (tnames.LM_HEAD, "fwd"), (tnames.LM_HEAD, "bwd"),
    (tnames.LM_CAST, "fwd"), (tnames.LM_CAST, "bwd"),
    (tnames.LM_OPT, "fwd"),
])
def test_lm_step_program_carries_every_region(lm, region, direction):
    assert (region, direction) in set(lm["scopes"].values())


def test_lm_step_program_recomputes_only_the_mlp(lm):
    """`remat="save_attn"` checkpoints the feed-forward sublayer alone."""
    again = {r for r, d in lm["scopes"].values() if d == "remat"}
    assert again == {tnames.LM_MLP}
    assert (tnames.LM_OPT, "bwd") not in set(lm["scopes"].values())


def test_lm_step_program_is_for_the_last_shape_and_holds_no_trainer():
    """The registered thunk keeps the jitted step and shapes: the trainer
    can go (a benchmark reads after its driver returned) and the program
    of the LAST shape is still there to lower."""
    from mmlspark_tpu.models.dnn.pp_training import PipelinedLMTrainer
    from mmlspark_tpu.parallel import DATA_AXIS, PIPE_AXIS, grid_mesh
    trainer = PipelinedLMTrainer(
        vocab_size=61, mesh=grid_mesh((1, 1), (DATA_AXIS, PIPE_AXIS)),
        n_microbatches=1, d_model=32, n_heads=2, n_layers=1, d_ff=64,
        max_len=16)
    tokens = np.zeros((2, 16), np.int32)
    trainer.step(tokens)
    trainer.step(tokens[:, :8])
    label = f"lm.step#{id(trainer):x}"
    gone = weakref.ref(trainer)
    del trainer
    gc.collect()
    assert gone() is None
    thunk = tperf._programs[label][0]()
    text = thunk()
    assert "s32[2,8]" in text and "s32[2,16]" not in text
    counts = tperf.region_instruction_counts(tperf.scope_map(text))
    # dense attention: every LM region but the flash call (and no cast
    # in float32), with the two scan shells' own (PR 37)
    assert set(counts) == {tnames.LM_EMBED, tnames.LM_ATTN, tnames.LM_MLP,
                           tnames.LM_HEAD, tnames.LM_OPT, tnames.LM_LAYERS,
                           tnames.LM_TICKS}


def test_executable_analysis_counts_instructions_per_region(lm):
    import jax
    import jax.numpy as jnp
    trainer = lm["trainer"]
    compiled = trainer._step.lower(
        trainer.params, trainer.opt_state,
        jax.ShapeDtypeStruct((2, 32), jnp.int32,
                             sharding=trainer._batch_sharding)).compile()
    regions = tperf.executable_analysis(compiled)["regions"]
    assert regions[tnames.LM_MLP] > 0 and regions[tnames.LM_OPT] > 0
    plain = jax.jit(lambda x: x * 2.0).lower(jnp.ones(4)).compile()
    assert "regions" not in tperf.executable_analysis(plain)


# ---------------------------------------------------------------- the GBDT fit
@pytest.fixture(scope="module")
def gbdt():
    """A toy default-path fit and a transform; the scope maps of the
    programs the fit registered and the spans it recorded."""
    from mmlspark_tpu import Table
    from mmlspark_tpu.models.gbdt import GBDTClassifier
    rng = np.random.default_rng(0)
    x = rng.normal(size=(2048, 8)).astype(np.float32)
    y = (x[:, 0] + x[:, 1] > 0).astype(np.float32)
    before = reliability_metrics.snapshot()
    # num_tasks=1: one device's default path (the suite's eight virtual
    # devices would take the distributed one, whose AotCache is below)
    model = GBDTClassifier(num_iterations=3, max_depth=3, max_bin=15,
                           num_tasks=1).fit(
        Table({"features": x, "label": y}))
    model.transform(Table({"features": x[:256]}))
    after = reliability_metrics.snapshot()
    maps = tperf.scope_maps()
    return {"chunk": maps["gbdt.chunk[2048x8/3]"],
            "bin": maps["gbdt.bin[2048x8]"],
            "spans": {k[:-len(".count")]: after[k] - before.get(k, 0)
                      for k in after if k.startswith("gbdt.")
                      and k.endswith(".count")}}


@pytest.mark.parametrize("region", [
    tnames.GBDT_HIST, tnames.GBDT_SPLIT, tnames.GBDT_ROUTE,
    tnames.GBDT_OBJECTIVE])
def test_boost_chunk_separates_its_regions(gbdt, region):
    """The attribution that had no reader: split search, routing and the
    objective are told apart inside the fused chunk, by instruction."""
    names = {n for n, (r, _d) in gbdt["chunk"].items() if r == region}
    assert names
    others = {n for n, (r, _d) in gbdt["chunk"].items() if r != region}
    assert not names & others


def test_binning_program_is_one_region(gbdt):
    assert {r for r, _d in gbdt["bin"].values()} == {tnames.GBDT_BIN}
    assert tnames.GBDT_BIN not in {r for r, _d in gbdt["chunk"].values()}


@pytest.mark.parametrize("span,count", [
    (tnames.GBDT_FIT_FIT_BINS, 1), (tnames.GBDT_FIT_BIN_DISPATCH, 1),
    (tnames.GBDT_FIT_INIT_SCORE, 1), (tnames.GBDT_FIT_BOOST, 1),
    (tnames.GBDT_FIT_FETCH, 1), (tnames.GBDT_FIT_ASSEMBLE, 1),
    (tnames.GBDT_ESTIMATOR_PROFILE, 1),
    # the fit-time profile scores a head sample, then the transform
    (tnames.GBDT_TRANSFORM_SCORE, 2),
])
def test_fit_records_its_spans_as_timers(gbdt, span, count):
    assert gbdt["spans"][span] == count
