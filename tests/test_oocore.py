"""Out-of-core multi-host GBDT (ISSUE 18): streaming chunked binning under
a residency budget, durable mid-dataset resume, voting-parallel split
finding, and straggler-actuated chunk re-assignment.

The load-bearing invariant everywhere here is BIT-identity
(`np.array_equal` on every model array): out-of-core staging, a resumed
staging pass, and a mid-drain chunk re-assignment are pure data-movement
changes — any model difference is a bug, not noise.
"""
import json
import os
import signal
import subprocess
import sys
import textwrap
import time

import numpy as np
import pytest

from mmlspark_tpu.data import ChunkPlanner, ChunkStager, OocoreOptions
from mmlspark_tpu.models.gbdt.boosting import BoostParams, fit_booster
from mmlspark_tpu.ops import binning
from mmlspark_tpu.reliability.faults import FaultInjector, InjectedFault
from mmlspark_tpu.reliability.metrics import MetricsRegistry
from mmlspark_tpu.telemetry import names as tnames
from mmlspark_tpu.telemetry.spans import Tracer


def _dataset(n=1536, f=8, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, f)).astype(np.float32)
    w = rng.normal(size=f)
    y = (x @ w + rng.normal(scale=0.5, size=n) > 0).astype(np.float32)
    return x, y


def _same_booster(a, b):
    """base + every Booster array field bit-identical."""
    ba, base_a, _ = a
    bb, base_b, _ = b
    assert base_a == base_b
    for field in ba._fields:
        va, vb = getattr(ba, field), getattr(bb, field)
        assert np.array_equal(np.asarray(va), np.asarray(vb)), field


def _params(**kw):
    base = dict(objective="binary", num_iterations=6, num_leaves=15,
                max_depth=4, max_bin=31, min_data_in_leaf=5)
    base.update(kw)
    return BoostParams(**base)


# ------------------------------------------------------------ bit-identity
def test_oocore_thread_bit_identity_with_weights(tmp_path):
    """Streaming staging (thread workers, budget << dataset, .npy source)
    fits bit-identically to the in-core path — with sample weights riding
    along, since weighted statistics see the same uint8 bins."""
    x, y = _dataset()
    w = np.random.default_rng(3).uniform(0.5, 2.0, size=len(y)) \
        .astype(np.float32)
    p = _params()
    path = str(tmp_path / "x.npy")
    np.save(path, x)
    oo = OocoreOptions(max_resident_bytes=x.nbytes // 8,
                       cache_path=str(tmp_path / "bins.npy"),
                       num_workers=2, mode="thread")
    ref = fit_booster(x, y, p, weights=w)
    oos = fit_booster(path, y, p, weights=w, oocore=oo)
    _same_booster(ref, oos)


def test_oocore_goss_bit_identity(tmp_path):
    """GOSS sampling is seeded from the binned matrix shape, not the raw
    floats — gradient one-sided sampling must survive the staging swap."""
    x, y = _dataset(seed=1)
    p = _params(boosting="goss", top_rate=0.3, other_rate=0.2)
    path = str(tmp_path / "x.npy")
    np.save(path, x)
    oo = OocoreOptions(max_resident_bytes=x.nbytes // 8,
                       cache_path=str(tmp_path / "bins.npy"))
    _same_booster(fit_booster(x, y, p), fit_booster(path, y, p, oocore=oo))


def test_oocore_process_workers_bit_identity(tmp_path):
    """Process-mode binning (grouped shared-memory batches instead of the
    thread stream) lands the identical matrix, hence the identical fit."""
    x, y = _dataset(n=768)
    p = _params(num_iterations=4)
    path = str(tmp_path / "x.npy")
    np.save(path, x)
    # window = workers+3+prefetch = 7, so this budget stages ~15 chunks
    # in 3 spawn rounds — enough to cross group boundaries while keeping
    # the spawn bill (fresh workers per round) off the tier-1 clock
    oo = OocoreOptions(max_resident_bytes=x.nbytes // 2,
                       num_workers=2, mode="process")
    _same_booster(fit_booster(x, y, p), fit_booster(path, y, p, oocore=oo))


def test_oocore_residency_bound_and_cursor_gauges(tmp_path):
    """The published residency bound stays under the budget and the cursor
    gauge lands at n_chunks once staging drains."""
    x, _ = _dataset()
    reg = MetricsRegistry()
    mapper = binning.fit_bins(x, max_bin=31)
    path = str(tmp_path / "x.npy")
    np.save(path, x)
    budget = x.nbytes // 4
    stager = ChunkStager(path, mapper, OocoreOptions(
        max_resident_bytes=budget, num_workers=1), metrics=reg)
    assert stager.resident_bound <= budget
    assert len(stager.source) > 1          # the budget actually chunked it
    assert reg.peek_gauge(tnames.DATA_OOCORE_RESIDENT_BYTES) \
        == float(stager.resident_bound)
    d = stager.stage()
    assert np.array_equal(np.asarray(d), binning.apply_bins(mapper, x))
    assert stager.cursor == len(stager.source)
    assert reg.peek_gauge(tnames.DATA_OOCORE_CURSOR) \
        == float(len(stager.source))


# ------------------------------------------------------------------ resume
def test_oocore_fault_abort_then_resume_bit_identical(tmp_path):
    """An injected error mid-staging leaves a durable cursor; the next
    stager resumes from the cached prefix and the assembled matrix — and a
    fit riding the same cache — is bit-identical to an uninterrupted run."""
    x, y = _dataset()
    mapper = binning.fit_bins(x, max_bin=31)
    path = str(tmp_path / "x.npy")
    np.save(path, x)
    cache = str(tmp_path / "bins.npy")
    opts = OocoreOptions(max_resident_bytes=x.nbytes // 8, cache_path=cache)
    inj = FaultInjector(seed=7, rules=[
        {"site": "data.oocore.stage2", "kind": "error", "at": [0]}])
    stager = ChunkStager(path, mapper, opts, faults=inj)
    n_chunks = len(stager.source)
    assert n_chunks > 3
    with pytest.raises(InjectedFault):
        stager.stage()
    side = json.loads(open(cache + ".cursor.json").read())
    assert side["cursor"] == 2            # chunks 0,1 committed in order
    resumed = ChunkStager(path, mapper, opts)      # no faults this time
    assert resumed.resumed_from == 2
    d = resumed.stage()
    assert resumed.cursor == n_chunks
    assert np.array_equal(np.asarray(d), binning.apply_bins(mapper, x))
    # and the fit path over that same durable cache matches in-core
    p = _params()
    oo = OocoreOptions(max_resident_bytes=x.nbytes // 8, cache_path=cache)
    _same_booster(fit_booster(x, y, p), fit_booster(path, y, p, oocore=oo))


def test_oocore_stale_fingerprint_invalidates_cursor(tmp_path):
    """A cache written under different bin boundaries must NOT be resumed
    from — splicing differently-binned prefixes is silent corruption."""
    x, _ = _dataset()
    path = str(tmp_path / "x.npy")
    np.save(path, x)
    cache = str(tmp_path / "bins.npy")
    opts = OocoreOptions(max_resident_bytes=x.nbytes // 8, cache_path=cache)
    m31 = binning.fit_bins(x, max_bin=31)
    ChunkStager(path, m31, opts).stage()
    m15 = binning.fit_bins(x, max_bin=15)
    stager = ChunkStager(path, m15, opts)
    assert stager.resumed_from == 0       # full restage, cursor distrusted
    d = stager.stage()
    assert np.array_equal(np.asarray(d), binning.apply_bins(m15, x))


_SIGTERM_FIT = """
import numpy as np
from mmlspark_tpu.data import OocoreOptions
from mmlspark_tpu.models.gbdt.boosting import BoostParams, fit_booster

x = np.load({x_path!r}, mmap_mode="r")
y = np.load({y_path!r})
oo = OocoreOptions(max_resident_bytes=x.nbytes // 8,
                   cache_path={cache!r})
p = BoostParams(objective="binary", num_iterations=6, num_leaves=15,
                max_depth=4, max_bin=31, min_data_in_leaf=5)
print("FITTING", flush=True)
fit_booster({x_path!r}, y, p, oocore=oo)
print("DONE", flush=True)
"""


@pytest.mark.chaos
def test_oocore_sigterm_midepoch_resume_bit_identical(tmp_path):
    """The acceptance chaos drill: SIGTERM lands mid-dataset (injected
    per-chunk delays stretch staging so the window is wide), the sidecar
    cursor survives strictly inside (0, n_chunks), and the resumed fit is
    bit-identical to an undisturbed in-core fit."""
    x, y = _dataset()
    x_path, y_path = str(tmp_path / "x.npy"), str(tmp_path / "y.npy")
    cache = str(tmp_path / "bins.npy")
    np.save(x_path, x)
    np.save(y_path, y)
    script = tmp_path / "fit.py"
    script.write_text(textwrap.dedent(_SIGTERM_FIT.format(
        x_path=x_path, y_path=y_path, cache=cache)))
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"
    # every chunk sleeps 0.15 s before committing: staging takes seconds,
    # the parent's poll-then-SIGTERM cannot miss the middle
    env["MMLSPARK_TPU_FAULTS"] = json.dumps({"seed": 0, "rules": [
        {"site": "data.oocore.stage*", "kind": "delay", "prob": 1.0,
         "param": 0.15}]})
    child = subprocess.Popen([sys.executable, str(script)],
                             stdout=subprocess.PIPE, text=True, env=env)
    try:
        assert child.stdout.readline().startswith("FITTING")
        sidecar = cache + ".cursor.json"
        deadline = time.time() + 60
        cursor = 0
        while time.time() < deadline:
            if os.path.exists(sidecar):
                try:
                    cursor = json.loads(open(sidecar).read())["cursor"]
                except (ValueError, KeyError, OSError):
                    cursor = 0
                if cursor >= 2:
                    break
            time.sleep(0.02)
        assert cursor >= 2, "staging never advanced"
        child.send_signal(signal.SIGTERM)
        child.wait(timeout=30)
    finally:
        if child.poll() is None:
            child.kill()
    side = json.loads(open(sidecar).read())
    p = _params()
    probe = ChunkStager(x_path, binning.fit_bins(x, max_bin=p.max_bin),
                        OocoreOptions(max_resident_bytes=x.nbytes // 8))
    n_chunks = len(probe.source)
    assert 0 < side["cursor"] < n_chunks, side   # died strictly mid-dataset
    # resume in THIS process (no fault env): bit-identical to in-core
    oo = OocoreOptions(max_resident_bytes=x.nbytes // 8, cache_path=cache)
    resumed = ChunkStager(x_path, probe.mapper, oo)
    assert resumed.resumed_from == side["cursor"]
    _same_booster(fit_booster(x, y, p), fit_booster(x_path, y, p, oocore=oo))


# ------------------------------------------------- straggler-actuated plan
def test_straggler_flag_drives_reassign_ordered(tmp_path):
    """The detector's `train.straggler` flag (from real heartbeat files
    with a slow host) drives `ChunkPlanner.reassign`, the move is
    journaled as `train.chunk.reassign`, and causal tracer order puts the
    flag strictly before the actuation."""
    from mmlspark_tpu.parallel.cluster import Heartbeat
    from mmlspark_tpu.telemetry.goodput import StragglerDetector

    hbs = [Heartbeat(str(tmp_path), process_id=i) for i in range(3)]
    for i, hb in enumerate(hbs):
        p50 = 9.0 if i == 2 else 2.0       # host 2 is 4.5x the fleet median
        hb.beat(1, stats={"step_p50_ms": p50, "steps": 8, "goodput": 1.0})
    tracer = Tracer(sample=1.0)
    reg = MetricsRegistry()
    det = StragglerDetector(hbs[0], threshold=1.5, registry=reg,
                            tracer=tracer, profile_on_flag=False)
    flagged = det.check()
    assert [f["process_id"] for f in flagged] == [2]

    planner = ChunkPlanner(12, hosts=[0, 1, 2], faults=None, tracer=tracer)
    for idx in planner.assigned(2)[:2]:
        planner.mark_done(idx)             # staged chunks never move
    moved = planner.reassign(flagged)
    assert moved and all(frm == 2 for frm, _ in moved.values())
    assert planner.pending(2) == []        # fully drained
    assert all(to in (0, 1) for _, to in moved.values())
    assert set(moved) == set(planner.assigned(0) + planner.assigned(1)) \
        & {i for i in range(12) if i % 3 == 2}

    straggle = tracer.finished(tnames.TRAIN_STRAGGLER_EVENT)
    reassign = tracer.finished(tnames.TRAIN_CHUNK_REASSIGN_EVENT)
    assert straggle and reassign
    assert straggle[0]["seq"] < reassign[0]["seq"]   # flag BEFORE actuation
    assert reassign[0]["attrs"]["from_host"] == 2
    assert reassign[0]["attrs"]["chunks"] == len(moved)


def test_reassign_fault_skips_round_not_plan(tmp_path):
    """The seeded `data.planner.reassign` chaos site: an injected error
    skips that reassignment round (the plan is untouched); the next round
    moves the chunks — actuation degrades to 'straggler keeps its share',
    never to a corrupted plan."""
    inj = FaultInjector(seed=11, rules=[
        {"site": "data.planner.reassign", "kind": "error", "at": [0]}])
    planner = ChunkPlanner(9, hosts=[0, 1, 2], faults=inj,
                           tracer=Tracer(sample=1.0))
    before = {i: planner.owner(i) for i in range(9)}
    assert planner.reassign([2]) == {}                 # round skipped
    assert {i: planner.owner(i) for i in range(9)} == before
    moved = planner.reassign([2])                      # next round lands
    assert moved and planner.pending(2) == []


def test_supervisor_beat_actuates_chunk_planner(tmp_path):
    """reliability.supervisor wiring: a step beat that flags a straggler
    hands the detector rows to the planner — and a planner that throws
    must not kill the training beat (actuation is best-effort)."""
    from mmlspark_tpu.parallel.cluster import Heartbeat
    from mmlspark_tpu.telemetry.goodput import StragglerDetector

    hbs = [Heartbeat(str(tmp_path), process_id=i) for i in range(2)]
    hbs[0].beat(1, stats={"step_p50_ms": 2.0, "steps": 8, "goodput": 1.0})
    hbs[1].beat(1, stats={"step_p50_ms": 9.0, "steps": 8, "goodput": 1.0})
    det = StragglerDetector(hbs[0], threshold=1.5,
                            registry=MetricsRegistry(),
                            tracer=Tracer(sample=1.0),
                            profile_on_flag=False)

    calls = []

    class Planner:
        def reassign(self, flagged):
            calls.append([f["process_id"] for f in flagged])
            raise RuntimeError("actuator broke")

    class Clock:
        def beat_stats(self):
            return {"step_p50_ms": 2.0, "steps": 8, "goodput": 1.0}

    from mmlspark_tpu.reliability import supervisor as sup
    s = sup.TrainingSupervisor.__new__(sup.TrainingSupervisor)
    s.heartbeat = hbs[0]
    s.clock = Clock()
    s.metrics = MetricsRegistry()
    s.straggler = det
    s.chunk_planner = Planner()
    s._beat(2)                             # must not raise
    assert calls == [[1]]


# ------------------------------------------------ multi-host shared cache
def test_multihost_drain_assembles_bit_identical_fit(tmp_path):
    """Three hosts stage disjoint `only` chunk sets into one shared cache;
    a mid-drain reassignment moves host 2's pending chunks; the assembled
    cache equals a direct host binning and the fit over it is
    bit-identical to in-core — re-assignment never touches model math."""
    x, y = _dataset()
    p = _params()
    mapper = binning.fit_bins(x, max_bin=p.max_bin)
    x_path = str(tmp_path / "x.npy")
    np.save(x_path, x)
    cache = str(tmp_path / "bins.npy")
    opts = OocoreOptions(max_resident_bytes=x.nbytes // 8, cache_path=cache)
    probe = ChunkStager(x_path, mapper, opts, only=set())
    n_chunks = len(probe.source)
    assert n_chunks >= 6
    planner = ChunkPlanner(n_chunks, hosts=[0, 1, 2],
                           tracer=Tracer(sample=1.0))

    def stage_host(h):
        todo = set(planner.pending(h))
        if todo:
            ChunkStager(x_path, mapper, opts, only=todo).stage()
            for i in todo:
                planner.mark_done(i)

    stage_host(0)                          # host 0 drains first
    moved = planner.reassign([2])          # then host 2 gets flagged
    assert moved and planner.pending(2) == []
    stage_host(1)
    stage_host(0)                          # the chunks it inherited
    assert all(not planner.pending(h) for h in (0, 1, 2))

    assembled = np.asarray(np.lib.format.open_memmap(cache, mode="r"))
    assert np.array_equal(assembled, binning.apply_bins(mapper, x))
    _same_booster(fit_booster(x, y, p),
                  fit_booster(x, y, p, prebinned=(mapper, assembled)))


# ------------------------------------------------------- voting-parallel
def test_vote_election_deterministic():
    """Two voting_parallel distributed fits produce bit-identical
    boosters — the int32 vote tally and top-k election carry no
    nondeterminism onto the wire."""
    import jax
    if jax.device_count() < 2:
        pytest.skip("needs a multi-device mesh")
    from mmlspark_tpu.models.gbdt.distributed import fit_booster_distributed
    x, y = _dataset(n=1024, f=16, seed=4)
    p = _params(num_iterations=4)
    a = fit_booster_distributed(x, y, p, parallelism="voting_parallel",
                                top_k=3)
    b = fit_booster_distributed(x, y, p, parallelism="voting_parallel",
                                top_k=3)
    _same_booster(a, b)
    assert a[0].n_trees == 4


def test_voting_reduces_allreduce_bytes_4x():
    """The perf headline, pinned on the 8-device CPU mesh so it is
    non-vacuous without TPUs: at F=64 the voting tree grower's all-reduce
    bytes (small int32 vote + elected-only histograms) are >= 4x below
    the full data_parallel histogram psum, read from the SAME compile
    records every distributed fit leaves (telemetry.perf AotCache)."""
    import jax
    import jax.numpy as jnp
    if jax.device_count() < 8:
        pytest.skip("needs the 8-device mesh")
    from mmlspark_tpu.models.gbdt.distributed import make_sharded_tree_fn
    from mmlspark_tpu.models.gbdt.trainer import TreeConfig
    from mmlspark_tpu.parallel import data_mesh
    from mmlspark_tpu.telemetry import perf as tperf

    mesh = data_mesh()
    n, f = 16 * jax.device_count(), 64
    rng = np.random.default_rng(0)
    bins = jnp.asarray(rng.integers(0, 16, size=(n, f)).astype(np.uint8))
    grad = jnp.asarray(rng.normal(size=n).astype(np.float32))
    hess = jnp.ones(n, jnp.float32)
    fmask = jnp.ones(f, bool)
    cfg = TreeConfig(n_features=f, n_bins=16, max_depth=2, num_leaves=7,
                     min_data_in_leaf=1)

    def traffic(mode, top_k):
        _, delta = make_sharded_tree_fn(mesh, mode, top_k=top_k)(
            bins, grad, hess, fmask, cfg)
        jax.block_until_ready(delta)
        recs = [r for r in tperf.get_compile_log().records()
                if r.get("label") == f"gbdt.tree.{mode}"]
        assert recs, f"no compile record for {mode}"
        colls = (recs[-1]["analysis"] or {}).get("collectives") or {}
        return colls.get("all-reduce", {})

    full = traffic("data_parallel", 20)
    vote = traffic("voting_parallel", 2)
    assert full.get("bytes", 0) > 0        # non-vacuity: psum really there
    assert vote.get("bytes", 0) > 0
    reduction = full["bytes"] / vote["bytes"]
    assert reduction >= 4.0, (
        f"voting {vote} vs full {full}: only {reduction:.2f}x")


# ------------------------------------------------------ larger-than-budget
@pytest.mark.slow
def test_oocore_larger_than_budget_smoke(tmp_path):
    """The mmap smoke at real scale (excluded from tier-1 by the `slow`
    mark): a 25 MB .npy staged under a 2 MB residency budget, fit
    bit-identical to in-core."""
    rng = np.random.default_rng(0)
    n, f = 200_000, 32
    x = rng.normal(size=(n, f)).astype(np.float32)
    w = rng.normal(size=f)
    y = (x @ w > 0).astype(np.float32)
    path = str(tmp_path / "big.npy")
    np.save(path, x)
    oo = OocoreOptions(max_resident_bytes=2 << 20,
                       cache_path=str(tmp_path / "bins.npy"),
                       num_workers=2)
    p = _params(num_iterations=3)
    ref = fit_booster(x, y, p)
    oos = fit_booster(path, y, p, oocore=oo)
    _same_booster(ref, oos)


def test_estimator_out_of_core_bit_identical_with_cursor(tmp_path):
    """Estimator surface: `out_of_core=True` + `max_resident_bytes` fit a
    bit-identical model, the spill cache lands under checkpoint_dir, and
    the durable staging cursor rides the checkpoint payload."""
    from mmlspark_tpu.core import Table
    from mmlspark_tpu.models.gbdt import GBDTClassifier
    from mmlspark_tpu.utils.checkpoint import CheckpointManager

    x, y = _dataset(n=1024, f=8)
    t = Table({"features": x, "label": y})
    kw = dict(num_iterations=4, max_bin=31, min_data_in_leaf=5, seed=0)
    ref = GBDTClassifier(**kw).fit(t)
    ck = str(tmp_path / "ck")
    oo = GBDTClassifier(out_of_core=True, max_resident_bytes=x.nbytes // 6,
                        checkpoint_dir=ck, checkpoint_interval=2, **kw).fit(t)
    for field in ref.booster._fields:
        assert np.array_equal(np.asarray(getattr(ref.booster, field)),
                              np.asarray(getattr(oo.booster, field))), field
    assert os.path.exists(os.path.join(ck, "oocore_bins.npy"))
    payload = CheckpointManager(ck).restore()
    assert payload["oocore_cursor"] >= 1   # fully-staged cursor rode along
