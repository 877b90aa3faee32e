"""Fault-tolerant training (ISSUE 4 tentpole): TrainingSupervisor async
verified checkpoints, preemption handling, and deterministic crash-resume.

The acceptance scenario lives here: a seeded chaos schedule kills an LM
training run mid-flight (injected step crash) and a GBDT fit mid-boosting
(SIGTERM'd subprocess); both resume from the latest digest-valid checkpoint
and finish BIT-IDENTICAL to an uninterrupted run, with zero blocking
checkpoint writes on the step thread (checkpoint.write.pending bounded,
submit latency orders of magnitude under the injected write latency)."""
import os
import signal
import subprocess
import sys
import tempfile
import textwrap

import numpy as np
import pytest

from mmlspark_tpu.reliability import (FaultInjector, Preempted, RetryPolicy,
                                      TrainingSupervisor, reliability_metrics)
from mmlspark_tpu.reliability.supervisor import AsyncCheckpointWriter
from mmlspark_tpu.utils.checkpoint import CheckpointManager

pytestmark = pytest.mark.chaos

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _toy_supervisor(directory, faults=None, **kw):
    """Trivial deterministic 'training': x += step+1 each step."""
    state = {"x": np.zeros(3, np.float64)}

    def snap():
        return {"x": state["x"].copy()}

    def rest(payload):
        state["x"] = np.asarray(payload["x"]).copy()

    kw.setdefault("checkpoint_every", 2)
    sup = TrainingSupervisor(directory, snap, rest, faults=faults, **kw)

    def step(k):
        state["x"] = state["x"] + (k + 1)
        return float(state["x"][0])

    return sup, step, state


def test_step_crash_restarts_from_snapshot(tmp_path):
    """An injected step crash restores the last snapshot and replays; the
    final state and per-step results are bit-identical to a fault-free
    run, and the injected schedule is seed-reproducible."""
    reliability_metrics.reset(prefix="train.")
    sup, step, state = _toy_supervisor(str(tmp_path / "ref"))
    ref = sup.run(step, 8)
    sup.close()
    x_ref = state["x"].copy()

    inj = FaultInjector(seed=7, rules=[
        {"site": "train.step5", "kind": "crash", "at": [0]}])
    sup, step, state = _toy_supervisor(str(tmp_path / "faulted"), faults=inj)
    out = sup.run(step, 8)
    sup.close()
    assert out == ref
    assert np.array_equal(state["x"], x_ref)
    assert reliability_metrics.get("train.step_restarts") == 1
    assert ("train.step5", 0, "crash") in inj.schedule()


def test_restart_keeps_non_json_results_history(tmp_path):
    """Non-JSON step results never ride the checkpoint payload, but an
    IN-PROCESS restart must rewind from the in-memory history, not drop
    it — only a cross-process resume legitimately loses it."""
    state = {"x": 0.0}
    inj = FaultInjector(seed=7, rules=[
        {"site": "train.step5", "kind": "crash", "at": [0]}])
    sup = TrainingSupervisor(str(tmp_path / "ck"),
                             lambda: {"x": np.float64(state["x"])},
                             lambda p: state.update(x=float(p["x"])),
                             checkpoint_every=2, faults=inj)

    def step(k):
        state["x"] += 1
        return np.float32(state["x"])   # json.dumps rejects np.float32

    out = sup.run(step, 8)
    sup.close()
    assert len(out) == 8 and [float(v) for v in out] == list(
        map(float, range(1, 9)))


def test_retry_exhausted_then_fresh_process_resumes(tmp_path):
    """Retry budget exhausted -> the run dies (as a real crash would); a
    FRESH supervisor resumes from the newest on-disk checkpoint and the
    completed run is bit-identical to the uninterrupted one."""
    d = str(tmp_path / "ck")
    sup, step, state = _toy_supervisor(str(tmp_path / "ref"))
    ref = sup.run(step, 8)
    sup.close()
    x_ref = state["x"].copy()

    inj = FaultInjector(seed=7, rules=[
        {"site": "train.step5", "kind": "crash", "at": [0]}])
    sup, step, state = _toy_supervisor(
        d, faults=inj, retry_policy=RetryPolicy(max_attempts=1))
    with pytest.raises(Exception, match="injected crash"):
        sup.run(step, 8)
    sup.close()   # flush the async writer, as atexit/GC would

    sup, step, state = _toy_supervisor(d)
    out = sup.run(step, 8)
    sup.close()
    assert sup.resumed_step == 4   # last checkpoint before the crash at 5
    assert out == ref
    assert np.array_equal(state["x"], x_ref)


def test_sigterm_triggers_final_sync_checkpoint(tmp_path):
    """SIGTERM mid-run: the in-flight step finishes, a final SYNCHRONOUS
    checkpoint lands, Preempted is raised — and a resumed run continues
    from exactly there."""
    reliability_metrics.reset(prefix="train.")
    d = str(tmp_path / "ck")
    sup, step, state = _toy_supervisor(str(tmp_path / "ref"))
    ref = sup.run(step, 8)
    sup.close()
    x_ref = state["x"].copy()

    sup, base_step, state = _toy_supervisor(d)

    def step_with_preempt(k):
        out = base_step(k)
        if k == 3:
            os.kill(os.getpid(), signal.SIGTERM)
        return out

    with pytest.raises(Preempted) as exc:
        sup.run(step_with_preempt, 8)
    sup.close()
    assert exc.value.step == 4 and exc.value.signum == signal.SIGTERM
    payload = CheckpointManager(d).restore()
    assert payload["sup_step"] == 4 and payload["sup_preempted"] is True
    assert reliability_metrics.get("train.preempted") == 1

    sup, step, state = _toy_supervisor(d)
    out = sup.run(step, 8)
    sup.close()
    assert out == ref
    assert np.array_equal(state["x"], x_ref)


def test_step_deadline_watchdog_restarts(tmp_path):
    """A step exceeding its wall-clock budget raises StepTimeout and the
    supervisor restarts it from the last snapshot."""
    import time
    reliability_metrics.reset(prefix="train.")
    hung = {"done": False}
    sup, base_step, state = _toy_supervisor(str(tmp_path / "ck"),
                                            step_timeout=0.2)

    def step(k):
        if k == 3 and not hung["done"]:
            hung["done"] = True
            time.sleep(2.0)   # hangs past the budget; retried fresh
            # the abandoned thread must NOT touch shared state on waking
            # (the timeout contract: a hung step may keep running — steps
            # that mutate state after the deadline race the replay)
            raise RuntimeError("abandoned")
        return base_step(k)

    out = sup.run(step, 6)
    sup.close()
    assert len(out) == 6
    assert reliability_metrics.get("train.step_timeouts") == 1
    assert reliability_metrics.get("train.step_restarts") == 1
    # replay from the step-2 snapshot: state identical to a clean run
    sup2, step2, state2 = _toy_supervisor(str(tmp_path / "ref"))
    ref = sup2.run(step2, 6)
    sup2.close()
    assert out == ref and np.array_equal(state["x"], state2["x"])


def test_async_writer_never_blocks_step_thread(tmp_path):
    """The zero-blocking-writes acceptance leg: with 50ms injected into
    every checkpoint WRITE, the step thread's submit stays orders of
    magnitude cheaper, the bounded queue coalesces instead of blocking,
    and the final sync checkpoint still restores the newest state."""
    reliability_metrics.reset()
    inj = FaultInjector(seed=3, rules=[
        {"site": "train.ckpt.write", "kind": "delay", "param": 0.05,
         "prob": 1.0}])
    sup, step, state = _toy_supervisor(str(tmp_path / "ck"), faults=inj,
                                       checkpoint_every=1, queue_depth=1)
    out = sup.run(step, 10)
    sup.close()
    snap = reliability_metrics.snapshot()
    assert len(out) == 10
    # every write paid the injected 50ms; the step thread's submit did not
    # (ORDERING assert, not a wall-clock threshold — tier-1 rule: submit
    # must be far under the injected write latency, whatever the host)
    assert snap["checkpoint.write.p50"] >= 50.0, snap["checkpoint.write.p50"]
    assert (snap["checkpoint.submit.p99"]
            < snap["checkpoint.write.p50"] / 2), snap
    assert snap["checkpoint.write.pending"] <= 1
    # depth-1 queue under slow writes MUST have coalesced (latest wins)
    assert snap.get("checkpoint.write.coalesced", 0) >= 1
    # the final synchronous checkpoint is the newest state
    payload = CheckpointManager(str(tmp_path / "ck")).restore()
    assert payload["sup_step"] == 10
    np.testing.assert_array_equal(payload["x"], state["x"])


def test_async_write_error_costs_one_interval_not_the_run(tmp_path):
    """An injected ERROR in an async write is absorbed (counted), training
    completes, and restore falls back to an older valid step."""
    reliability_metrics.reset(prefix="checkpoint.")
    inj = FaultInjector(seed=3, rules=[
        {"site": "train.ckpt.write", "kind": "error", "at": [1]}])
    sup, step, state = _toy_supervisor(str(tmp_path / "ck"), faults=inj,
                                       checkpoint_every=2)
    out = sup.run(step, 8)
    sup.close()
    assert len(out) == 8
    assert reliability_metrics.get("checkpoint.write.errors") == 1
    assert CheckpointManager(str(tmp_path / "ck")).restore()["sup_step"] == 8


def test_digest_mismatch_skipped_on_restore(tmp_path):
    """ISSUE satellite: a SILENTLY-corrupted newest step (valid npz, wrong
    bytes — sha256 is the only tell) is skipped to the next-newest valid
    step; the explicit-step request still raises."""
    reliability_metrics.reset(prefix="checkpoint.")
    mgr = CheckpointManager(str(tmp_path / "ck"), max_to_keep=3)
    for s in (1, 2, 3):
        mgr.save(s, {"w": np.arange(s * 4, dtype=np.float32),
                     "iteration": s})
    # silent corruption: REPLACE the payload with a valid npz of other data
    np.savez(os.path.join(mgr._step_dir(3), "payload.npz"),
             w=np.zeros(12, np.float32))
    out = mgr.restore()
    assert out["iteration"] == 2
    np.testing.assert_array_equal(out["w"], np.arange(8, dtype=np.float32))
    assert reliability_metrics.get("checkpoint.digest_mismatch") >= 1
    assert reliability_metrics.get("checkpoint.corrupt_skipped") >= 1
    with pytest.raises(ValueError, match="sha256 mismatch"):
        mgr.restore(3)


def test_meta_content_corruption_detected(tmp_path):
    """Corruption that stays VALID JSON (e.g. flipped digits inside a
    GBDT model string in meta.json) must still fail the digest gate and
    fall back — meta content is digested, not just the npz file."""
    import json
    reliability_metrics.reset(prefix="checkpoint.")
    mgr = CheckpointManager(str(tmp_path / "ck"))
    mgr.save(1, {"booster": "tree 1.25 4.5", "iteration": 1})
    mgr.save(2, {"booster": "tree 9.99 4.5", "iteration": 2})
    meta_path = os.path.join(mgr._step_dir(2), "meta.json")
    with open(meta_path) as f:
        meta = json.load(f)
    meta["booster"] = "tree 0.00 4.5"   # silent in-place corruption
    with open(meta_path, "w") as f:
        json.dump(meta, f)
    out = mgr.restore()
    assert out["iteration"] == 1
    assert reliability_metrics.get("checkpoint.digest_mismatch") >= 1


def test_save_records_digests_and_metrics(tmp_path):
    reliability_metrics.reset(prefix="checkpoint.save")
    mgr = CheckpointManager(str(tmp_path / "ck"))
    mgr.save(1, {"w": np.arange(8, dtype=np.float32), "note": "hi"})
    import json
    with open(os.path.join(mgr._step_dir(1), "meta.json")) as f:
        meta = json.load(f)
    assert "payload.npz" in meta["_digests"]
    assert len(meta["_digests"]["payload.npz"]) == 64
    # reserved keys never leak into the restored payload
    assert "_digests" not in mgr.restore()
    assert reliability_metrics.get("checkpoint.save.count") == 1
    assert reliability_metrics.get("checkpoint.save.bytes") > 0
    with pytest.raises(ValueError, match="reserved"):
        mgr.save(2, {"_digests": {}})


# ---------------------------------------------------------------- LM resume
def _lm_batches(n=8):
    rng = np.random.default_rng(0)
    return [rng.integers(0, 64, size=(4, 16)).astype(np.int32)
            for _ in range(n)]


def _lm_trainer():
    from mmlspark_tpu.models.dnn.lm_training import ShardedLMTrainer
    from mmlspark_tpu.parallel import grid_mesh
    return ShardedLMTrainer(vocab_size=64, mesh=grid_mesh((2, 4)),
                            d_model=32, n_heads=4, n_layers=1, d_ff=64,
                            max_len=16, seed=0)


def test_lm_kill_resume_bit_identity(tmp_path):
    """The LM acceptance leg: run_stream is killed by an injected step
    crash (retry exhausted, as a real worker death); a fresh trainer
    resumes from the latest checkpoint and the final params are
    np.array_equal to the uninterrupted run's — losses included."""
    import jax
    batches = _lm_batches()
    a = _lm_trainer()
    ref = a.run_stream(batches)
    leaves_ref = [np.asarray(x) for x in jax.tree_util.tree_leaves(a.params)]

    d = str(tmp_path / "ck")
    inj = FaultInjector(seed=7, rules=[
        {"site": "train.step5", "kind": "crash", "at": [0]}])
    b = _lm_trainer()
    with pytest.raises(Exception, match="injected crash"):
        b.run_stream(batches, checkpoint_dir=d, checkpoint_every=2,
                     faults=inj, retry_policy=RetryPolicy(max_attempts=1))

    c = _lm_trainer()
    out = c.run_stream(batches, checkpoint_dir=d, checkpoint_every=2)
    assert out == ref   # full history, pre-kill steps restored from payload
    leaves_c = [np.asarray(x) for x in jax.tree_util.tree_leaves(c.params)]
    assert all(np.array_equal(x, y) for x, y in zip(leaves_ref, leaves_c))


def test_lm_in_run_crash_restart_bit_identity(tmp_path):
    """Same crash absorbed IN-RUN by the retry policy: the step replays
    from the in-memory snapshot and the run finishes bit-identical, with
    zero blocking writes on the step thread."""
    import jax
    reliability_metrics.reset()
    batches = _lm_batches()
    a = _lm_trainer()
    ref = a.run_stream(batches)
    leaves_ref = [np.asarray(x) for x in jax.tree_util.tree_leaves(a.params)]

    inj = FaultInjector(seed=7, rules=[
        {"site": "train.step5", "kind": "crash", "at": [0]}])
    b = _lm_trainer()
    out = b.run_stream(batches, checkpoint_dir=str(tmp_path / "ck"),
                       checkpoint_every=2, faults=inj)
    leaves_b = [np.asarray(x) for x in jax.tree_util.tree_leaves(b.params)]
    assert out == ref
    assert all(np.array_equal(x, y) for x, y in zip(leaves_ref, leaves_b))
    snap = reliability_metrics.snapshot()
    assert reliability_metrics.get("train.step_restarts") == 1
    # async-writes-only on the step thread (the acceptance metric)
    assert snap["checkpoint.write.pending"] <= 2
    assert snap["checkpoint.write.count"] >= 1


def test_lm_restore_checkpoint_skips_corrupt_newest(tmp_path):
    """The NON-supervisor LM resume path (restore_checkpoint) must also
    ride the corrupt-step fallback: a torn newest step costs one interval,
    not the run."""
    batches = _lm_batches(3)
    a = _lm_trainer()
    a.step(batches[0])
    a.save_checkpoint(str(tmp_path), step=1)
    a.step(batches[1])
    a.save_checkpoint(str(tmp_path), step=2)
    mgr = CheckpointManager(str(tmp_path))
    FaultInjector(seed=3).corrupt_file(
        os.path.join(mgr._step_dir(2), "payload.npz"))
    b = _lm_trainer()
    assert b.restore_checkpoint(str(tmp_path)) == 1


# -------------------------------------------------------------- GBDT resume
@pytest.fixture
def gbdt_table():
    from mmlspark_tpu import Table
    rng = np.random.default_rng(0)
    x = rng.normal(size=(400, 5)).astype(np.float32)
    y = (x @ [1, -2, 0.5, 0, 3]
         + 0.05 * rng.normal(size=400)).astype(np.float32)
    return Table({"features": x, "label": y})


def test_gbdt_resume_scores_bit_identical(gbdt_table, tmp_path):
    """fit_booster interrupted at a checkpoint boundary and resumed must
    score BIT-identically to an uninterrupted run at the same checkpoint
    cadence (the saved live margin + PRNG key make the replay exact —
    raw_score reconstruction would re-associate float sums)."""
    from mmlspark_tpu.models.gbdt import GBDTRegressor
    kw = dict(num_iterations=12, seed=3, bagging_fraction=0.7,
              bagging_freq=1, checkpoint_interval=3)
    full = GBDTRegressor(checkpoint_dir=str(tmp_path / "full"), **kw).fit(
        gbdt_table)
    ck = str(tmp_path / "ck")
    GBDTRegressor(checkpoint_dir=ck,
                  **{**kw, "num_iterations": 6}).fit(gbdt_table)
    resumed = GBDTRegressor(checkpoint_dir=ck, **kw).fit(gbdt_table)
    assert resumed.booster.n_trees == 12
    pf = np.asarray(full.transform(gbdt_table)["prediction"])
    pr = np.asarray(resumed.transform(gbdt_table)["prediction"])
    assert np.array_equal(pf, pr)
    for field in ("split_feature", "threshold", "leaf_value"):
        assert np.array_equal(getattr(full.booster, field),
                              getattr(resumed.booster, field)), field


def test_fit_booster_legacy_checkpoint_fn_signature(gbdt_table):
    """External checkpoint_fn callbacks predating the margin/rng_key
    kwargs must keep working (they just lose exact-resume margins)."""
    from mmlspark_tpu.models.gbdt import BoostParams, fit_booster
    x = np.asarray(gbdt_table["features"], np.float32)
    y = np.asarray(gbdt_table["label"], np.float32)
    seen = []

    def legacy_ck(it, booster, base, final=False):
        seen.append((it, bool(final)))

    fit_booster(x, y, BoostParams(num_iterations=4, seed=0),
                checkpoint_fn=legacy_ck, checkpoint_interval=2)
    assert seen and all(isinstance(i, int) for i, _ in seen)


_GBDT_SUBPROC = """
import os, signal, sys
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
sys.path.insert(0, {repo!r})
from mmlspark_tpu.utils.hostcache import enable_compile_cache
enable_compile_cache()
from mmlspark_tpu import Table
from mmlspark_tpu.models.gbdt import GBDTRegressor
from mmlspark_tpu.utils.checkpoint import CheckpointManager

phase, ckdir, outfile = sys.argv[1], sys.argv[2], sys.argv[3]
rng = np.random.default_rng(0)
x = rng.normal(size=(300, 5)).astype(np.float32)
y = (x @ [1, -2, 0.5, 0, 3] + 0.05 * rng.normal(size=300)).astype(np.float32)
t = Table({{"features": x, "label": y}})

if phase == "kill":
    # SIGTERM ourselves right after the 2nd periodic checkpoint lands —
    # deterministic mid-boosting preemption (no parent timing races)
    orig = CheckpointManager.save
    def save(self, step, payload, prune_newer=False):
        orig(self, step, payload, prune_newer=prune_newer)
        if step >= 6 and not payload.get("final"):
            os.kill(os.getpid(), signal.SIGTERM)
    CheckpointManager.save = save

kw = dict(num_iterations=12, seed=3, checkpoint_interval=3,
          checkpoint_async=False, checkpoint_dir=ckdir)
model = GBDTRegressor(**kw).fit(t)
np.savez(outfile, scores=np.asarray(model.transform(t)["prediction"]),
         n_trees=model.booster.n_trees)
print("DONE", model.booster.n_trees)
"""


def test_gbdt_sigterm_subprocess_kill_resume(tmp_path):
    """The GBDT acceptance leg: a subprocess fit is SIGTERM-killed
    mid-boosting (right after the iteration-6 checkpoint), a second
    subprocess resumes from the digest-valid checkpoint, and its scores
    are bit-identical to an uninterrupted subprocess run."""
    script = tmp_path / "gbdt_fit.py"
    script.write_text(textwrap.dedent(_GBDT_SUBPROC.format(repo=_REPO)))
    env = dict(os.environ)
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("JAX_PLATFORMS", None)
    env.pop("XLA_FLAGS", None)   # subprocesses run single-device CPU

    def run(phase, ckdir, out):
        return subprocess.run(
            [sys.executable, str(script), phase, ckdir, out],
            capture_output=True, text=True, env=env, timeout=420)

    full = run("full", str(tmp_path / "ck_full"), str(tmp_path / "full.npz"))
    assert full.returncode == 0, full.stdout + full.stderr

    killed = run("kill", str(tmp_path / "ck"), str(tmp_path / "k.npz"))
    assert killed.returncode == -signal.SIGTERM, (killed.returncode,
                                                  killed.stdout[-500:],
                                                  killed.stderr[-500:])
    steps = CheckpointManager(str(tmp_path / "ck")).all_steps()
    assert steps and max(steps) == 6, steps   # died mid-boosting, ckpt at 6

    resumed = run("resume", str(tmp_path / "ck"), str(tmp_path / "r.npz"))
    assert resumed.returncode == 0, resumed.stdout + resumed.stderr

    f = np.load(str(tmp_path / "full.npz"))
    r = np.load(str(tmp_path / "r.npz"))
    assert int(r["n_trees"]) == 12
    assert np.array_equal(f["scores"], r["scores"])


def test_ckpt_read_fault_surfaces_then_clean_resume(tmp_path):
    """An injected fault on the checkpoint READ path (`train.ckpt.read`)
    surfaces out of run() — a torn restore must never silently train from
    scratch — and retrying resume on the SAME schedule reads clean and
    finishes bit-identical to an uninterrupted run."""
    from mmlspark_tpu.reliability import InjectedFault

    sup, step, state = _toy_supervisor(str(tmp_path / "ref"))
    ref = sup.run(step, 8)
    sup.close()
    x_ref = state["x"].copy()

    # seed on-disk checkpoints by dying at step 5
    d = str(tmp_path / "ck")
    inj0 = FaultInjector(seed=7, rules=[
        {"site": "train.step5", "kind": "crash", "at": [0]}])
    sup, step, state = _toy_supervisor(
        d, faults=inj0, retry_policy=RetryPolicy(max_attempts=1))
    with pytest.raises(Exception, match="injected crash"):
        sup.run(step, 8)
    sup.close()

    inj = FaultInjector(seed=7, rules=[
        {"site": "train.ckpt.read", "kind": "error", "at": [0]}])
    sup, step, state = _toy_supervisor(d, faults=inj)
    with pytest.raises(InjectedFault):
        sup.run(step, 8)
    # same supervisor, same seeded schedule: the site counter advanced, so
    # the retry restores cleanly and completes exactly like the reference
    out = sup.run(step, 8)
    sup.close()
    assert sup.resumed_step == 4
    assert out == ref
    assert np.array_equal(state["x"], x_ref)
    assert ("train.ckpt.read", 0, "error") in inj.schedule()
