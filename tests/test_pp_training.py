"""GPipe pipeline parallelism (SURVEY §2.10 — the last named strategy:
TP = lm_training, CP = ring_attention, PP = this). Loss parity against the
unpipelined trainer is the correctness bar: the schedule must be a pure
re-ordering of the same math."""
import numpy as np
import pytest

from mmlspark_tpu.parallel import DATA_AXIS, PIPE_AXIS, grid_mesh
from mmlspark_tpu.models.dnn.pp_training import PipelinedLMTrainer
from mmlspark_tpu.models.dnn.lm_training import ShardedLMTrainer

_KW = dict(vocab_size=64, d_model=32, n_heads=4, n_layers=4, d_ff=64,
           max_len=32, lr=1e-3, seed=0)


def _toks(b=16, s=16, seed=0):
    return np.random.default_rng(seed).integers(
        0, 64, size=(b, s)).astype(np.int32)


def test_dp_pp_loss_parity_with_unpipelined():
    """2 x 4 (dp x pp) pipelined steps vs an 8 x 1 dp-only reference from
    identical init: first-step loss must match to f32 reduction noise, and
    both must keep matching after an optimizer update (gradients through
    the ppermute'd schedule are the same gradients)."""
    pp = PipelinedLMTrainer(
        mesh=grid_mesh((2, 4), (DATA_AXIS, PIPE_AXIS)),
        n_microbatches=4, **_KW)
    ref = ShardedLMTrainer(mesh=grid_mesh((8, 1)), **_KW)
    toks = _toks()
    assert pp.step(toks) == pytest.approx(ref.step(toks), abs=1e-4)
    l_pp, l_ref = pp.step(toks), ref.step(toks)
    assert l_pp == pytest.approx(l_ref, abs=1e-3)
    # and training actually trains
    for _ in range(3):
        last = pp.step(toks)
    assert last < l_pp


def test_sgd_gradient_parity_across_pp_degrees():
    """DIRECT gradient parity (not just Adam loss trajectories, which are
    invariant to uniform gradient scaling): SGD steps at pp=1 / pp=2 /
    pp=4 from identical init must land on IDENTICAL parameters. A bare
    psum over the pipe axis in the loss reduction would transpose to a
    second psum and scale every gradient by the PIPE DEGREE — Adam masks
    exactly this; SGD params diverge by lr x grad x (pp-1) on step one.

    Runs in a FRESH SUBPROCESS (the test_multiprocess pattern): on this
    repo's 1-core CI host, XLA:CPU's in-process collectives deadlock
    (0-CPU hang at the loss fetch, rendezvous threads never all arrive)
    when this particular multi-trainer program set compiles late in a
    300-test process — reproducibly fine in a fresh process, where it
    runs in ~30 s. Production is TPU; the subprocess keeps the
    gradient-parity coverage without tripping the host quirk."""
    import subprocess
    import sys
    body = """
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)
import numpy as np
from mmlspark_tpu.parallel import DATA_AXIS, PIPE_AXIS, grid_mesh
from mmlspark_tpu.models.dnn.pp_training import PipelinedLMTrainer

KW = dict(vocab_size=64, d_model=32, n_heads=4, n_layers=4, d_ff=64,
          max_len=32, lr=1e-2, seed=0, optimizer="sgd")
toks = np.random.default_rng(0).integers(0, 64, size=(32, 16)).astype(np.int32)

def params_after_steps(pp_deg, n=2):
    t = PipelinedLMTrainer(
        mesh=grid_mesh((8 // pp_deg, pp_deg), (DATA_AXIS, PIPE_AXIS)),
        n_microbatches=4, **KW)
    for _ in range(n):
        t.step(toks)
    return jax.device_get(t.params)

ref = params_after_steps(1)
for pp_deg in (2, 4):
    got = params_after_steps(pp_deg)
    for name in ("embed", "pos"):
        np.testing.assert_allclose(got[name], ref[name], atol=2e-6,
                                   err_msg=f"pp={pp_deg} {name}")
    np.testing.assert_allclose(got["layers"]["wq"], ref["layers"]["wq"],
                               atol=2e-6, err_msg=f"pp={pp_deg} wq")
print("SGD_PARITY_OK")
"""
    import os
    env = dict(os.environ)
    env["PYTHONPATH"] = (os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))) + os.pathsep + env.get("PYTHONPATH", ""))
    env.pop("JAX_PLATFORMS", None)
    res = subprocess.run([sys.executable, "-c", body], env=env,
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0 and "SGD_PARITY_OK" in res.stdout, (
        res.stdout, res.stderr[-2000:])


def test_pure_pp_and_microbatch_counts():
    """1 x 8 pure pipeline (every device one layer) with M > P and M == P;
    both must agree with the dp-only oracle."""
    kw = dict(_KW, n_layers=8)
    ref = ShardedLMTrainer(mesh=grid_mesh((8, 1)), **kw)
    toks = _toks(b=16)
    want = ref.step(toks)
    for m in (8, 16):
        pp = PipelinedLMTrainer(
            mesh=grid_mesh((1, 8), (DATA_AXIS, PIPE_AXIS)),
            n_microbatches=m, **kw)
        assert pp.step(toks) == pytest.approx(want, abs=1e-4), m


def test_run_multi_step_matches_step_loop():
    """run(tokens, n) chains n updates in ONE device-side fori_loop (one
    host sync) and must land on the same trajectory as n step() calls
    from identical init."""
    toks = _toks()
    a = PipelinedLMTrainer(
        mesh=grid_mesh((2, 4), (DATA_AXIS, PIPE_AXIS)),
        n_microbatches=4, **_KW)
    b = PipelinedLMTrainer(
        mesh=grid_mesh((2, 4), (DATA_AXIS, PIPE_AXIS)),
        n_microbatches=4, **_KW)
    for _ in range(3):
        last_step = a.step(toks)
    last_run = b.run(toks, 3)
    assert last_run == pytest.approx(last_step, abs=1e-5)
    import jax
    np.testing.assert_allclose(jax.device_get(b.params["embed"]),
                               jax.device_get(a.params["embed"]),
                               atol=1e-6)
    with pytest.raises(ValueError, match="n_steps"):
        b.run(toks, 0)
    with pytest.raises(TypeError):
        b.run(toks, 2.5)   # silent truncation would run 2 steps


def test_layers_are_stage_sharded():
    """The point of PP: each device materializes only its stage's layers.
    (M = 2 < P = 4 builds, but says it is mostly bubble.)"""
    with pytest.warns(UserWarning, match=r"n_microbatches \(2\) < pipe"):
        pp = PipelinedLMTrainer(
            mesh=grid_mesh((2, 4), (DATA_AXIS, PIPE_AXIS)),
            n_microbatches=2, **_KW)
    wq = pp.params["layers"]["wq"]          # (4, d, d) global
    assert wq.shape[0] == 4
    assert {s.data.shape[0] for s in wq.addressable_shards} == {1}


def test_validation_errors():
    with pytest.raises(ValueError, match="must divide by the pipe axis"):
        PipelinedLMTrainer(
            mesh=grid_mesh((2, 4), (DATA_AXIS, PIPE_AXIS)),
            **dict(_KW, n_layers=6))
    pp = PipelinedLMTrainer(
        mesh=grid_mesh((2, 4), (DATA_AXIS, PIPE_AXIS)),
        n_microbatches=4, **_KW)
    with pytest.raises(ValueError, match="divide by dp"):
        pp.step(_toks(b=12))


def test_flash_attention_pipeline_parity():
    """attention='flash' inside the GPipe stages (legal: shard_map hands
    each stage per-device code where pallas is a local op) must reproduce
    the dense pipeline's loss trajectory — including through the flash
    BACKWARD, since step() takes gradients through the kernel."""
    kw = dict(vocab_size=64, d_model=32, n_heads=2, n_layers=4, d_ff=64,
              max_len=64, lr=1e-3, seed=0)
    toks = np.random.default_rng(0).integers(
        0, 64, size=(8, 48)).astype(np.int32)
    dense = PipelinedLMTrainer(
        mesh=grid_mesh((2, 4), (DATA_AXIS, PIPE_AXIS)),
        n_microbatches=2, attention="dense", **kw)
    flash = PipelinedLMTrainer(
        mesh=grid_mesh((2, 4), (DATA_AXIS, PIPE_AXIS)),
        n_microbatches=2, attention="flash", **kw)
    for _ in range(2):
        l_d, l_f = dense.step(toks), flash.step(toks)
        assert l_f == pytest.approx(l_d, abs=2e-3)
    assert l_f < 4.2  # actually trained
    with pytest.raises(ValueError, match="dense|flash"):
        PipelinedLMTrainer(mesh=grid_mesh((2, 4), (DATA_AXIS, PIPE_AXIS)),
                           attention="ring", **kw)


def test_3d_dp_pp_tp_parity():
    """The full 3D composition — GPipe stages x Megatron tensor slices x
    data parallel in ONE shard_map — must reproduce the dp-only oracle's
    Adam trajectory. This pins the f/g operator pair: under unchecked
    shard_map a bare psum transposes to another psum, overcounting
    row-parallel grads tp x (non-uniformly, so even Adam diverges)."""
    from mmlspark_tpu.parallel import MODEL_AXIS
    toks = _toks(b=8, s=32)
    ref = ShardedLMTrainer(mesh=grid_mesh((8, 1)), **_KW)
    want = [ref.step(toks) for _ in range(3)]
    t3 = PipelinedLMTrainer(
        mesh=grid_mesh((2, 2, 2), (DATA_AXIS, PIPE_AXIS, MODEL_AXIS)),
        n_microbatches=2, **_KW)
    got = [t3.step(toks) for _ in range(3)]
    assert got == pytest.approx(want, abs=2e-3)
    # true 3D sharding: each device holds (L/pp, d, d/tp) of wq
    wq = t3.params["layers"]["wq"]
    assert {s.data.shape for s in wq.addressable_shards} == {(2, 32, 16)}
    # head/d_ff divisibility enforced
    with pytest.raises(ValueError, match="model axis"):
        PipelinedLMTrainer(
            mesh=grid_mesh((2, 2, 2), (DATA_AXIS, PIPE_AXIS, MODEL_AXIS)),
            **dict(_KW, n_heads=3))


def test_3d_with_flash_attention():
    """flash attention inside the 3D grid: local heads per model shard run
    the Pallas kernel (fwd + flash backward), still matching the oracle."""
    from mmlspark_tpu.parallel import MODEL_AXIS
    toks = _toks(b=8, s=32)
    ref = ShardedLMTrainer(mesh=grid_mesh((8, 1)), **_KW)
    want = [ref.step(toks) for _ in range(2)]
    t3 = PipelinedLMTrainer(
        mesh=grid_mesh((2, 2, 2), (DATA_AXIS, PIPE_AXIS, MODEL_AXIS)),
        n_microbatches=2, attention="flash", **_KW)
    got = [t3.step(toks) for _ in range(2)]
    assert got == pytest.approx(want, abs=2e-3)


def test_checkpoint_resume_exact(tmp_path):
    """save/restore on the 3D trainer: a differently-seeded fresh trainer
    restored from the checkpoint must continue the EXACT loss trajectory
    (params + Adam state, re-placed with live stage/tensor shardings)."""
    from mmlspark_tpu.parallel import MODEL_AXIS
    toks = _toks(b=8, s=32)
    mesh = lambda: grid_mesh((2, 2, 2), (DATA_AXIS, PIPE_AXIS, MODEL_AXIS))
    t = PipelinedLMTrainer(mesh=mesh(), n_microbatches=2, **_KW)
    for _ in range(2):
        t.step(toks)
    t.save_checkpoint(str(tmp_path), step=2)
    want = [t.step(toks) for _ in range(2)]
    t2 = PipelinedLMTrainer(mesh=mesh(), n_microbatches=2,
                            **dict(_KW, seed=99))
    assert t2.restore_checkpoint(str(tmp_path)) == 2
    got = [t2.step(toks) for _ in range(2)]
    assert got == pytest.approx(want, abs=1e-6)
    # config drift must refuse, not silently train a different model
    t3 = PipelinedLMTrainer(mesh=mesh(), n_microbatches=2,
                            **dict(_KW, d_model=64))
    with pytest.raises(ValueError, match="different model"):
        t3.restore_checkpoint(str(tmp_path))


def test_restore_refuses_foreign_layout(tmp_path):
    """A ShardedLMTrainer checkpoint (per-layer leaves) must be refused by
    the pipelined trainer (stacked leaves) with a CLEAR error, not a silent
    zip-truncation into wrong arrays."""
    t_g = ShardedLMTrainer(mesh=grid_mesh((8, 1)), **_KW)
    t_g.save_checkpoint(str(tmp_path), step=1)
    t_p = PipelinedLMTrainer(
        mesh=grid_mesh((2, 4), (DATA_AXIS, PIPE_AXIS)),
        n_microbatches=2, **_KW)
    with pytest.raises(ValueError, match="parameter leaves"):
        t_p.restore_checkpoint(str(tmp_path))


def test_bf16_mixed_precision_trains_close_to_f32():
    """compute_dtype='bfloat16': master weights and Adam state stay f32,
    matmuls/activations run bf16, loss/softmax/LN accumulate f32. The
    bf16 loss trajectory must track the f32 one closely (bf16 rounding
    band, not a different optimization), and the master params must stay
    f32."""
    toks = _toks(b=16)
    f32 = PipelinedLMTrainer(
        mesh=grid_mesh((2, 4), (DATA_AXIS, PIPE_AXIS)),
        n_microbatches=4, **_KW)
    bf16 = PipelinedLMTrainer(
        mesh=grid_mesh((2, 4), (DATA_AXIS, PIPE_AXIS)),
        n_microbatches=4, compute_dtype="bfloat16", **_KW)
    import jax.numpy as jnp
    assert bf16.params["embed"].dtype == jnp.float32
    l_f = [f32.step(toks) for _ in range(4)]
    l_b = [bf16.step(toks) for _ in range(4)]
    assert l_b == pytest.approx(l_f, abs=3e-2)
    assert l_b[-1] < l_b[0]
    with pytest.raises(ValueError, match="compute_dtype"):
        PipelinedLMTrainer(mesh=grid_mesh((2, 4), (DATA_AXIS, PIPE_AXIS)),
                           compute_dtype="float16", **_KW)


def test_remat_is_loss_invariant():
    """remat=True recomputes block activations in the backward — the SAME
    ops in the same order, so the Adam trajectory must match the
    non-remat trainer to reduction noise."""
    toks = _toks(b=16)
    base = PipelinedLMTrainer(
        mesh=grid_mesh((2, 4), (DATA_AXIS, PIPE_AXIS)),
        n_microbatches=4, **_KW)
    rm = PipelinedLMTrainer(
        mesh=grid_mesh((2, 4), (DATA_AXIS, PIPE_AXIS)),
        n_microbatches=4, remat=True, **_KW)
    want = [base.step(toks) for _ in range(3)]
    got = [rm.step(toks) for _ in range(3)]
    assert got == pytest.approx(want, abs=1e-4)
    # selective remat (FF-only checkpoint, attention residuals stored)
    # is the same math again — must track the same trajectory
    sa = PipelinedLMTrainer(
        mesh=grid_mesh((2, 4), (DATA_AXIS, PIPE_AXIS)),
        n_microbatches=4, remat="save_attn", **_KW)
    got_sa = [sa.step(toks) for _ in range(3)]
    assert got_sa == pytest.approx(want, abs=1e-4)
    with pytest.raises(ValueError, match="remat"):
        PipelinedLMTrainer(mesh=grid_mesh((2, 4), (DATA_AXIS, PIPE_AXIS)),
                           remat="everything", **_KW)


def test_bf16_remat_flash_composition():
    """The bench configuration's feature stack — bf16 + remat + flash —
    composed with a real pipe degree, against the plain f32 dense
    trainer."""
    kw = dict(vocab_size=64, d_model=32, n_heads=2, n_layers=4, d_ff=64,
              max_len=64, lr=1e-3, seed=0)
    toks = np.random.default_rng(0).integers(
        0, 64, size=(8, 48)).astype(np.int32)
    ref = PipelinedLMTrainer(
        mesh=grid_mesh((2, 4), (DATA_AXIS, PIPE_AXIS)),
        n_microbatches=2, **kw)
    full = PipelinedLMTrainer(
        mesh=grid_mesh((2, 4), (DATA_AXIS, PIPE_AXIS)),
        n_microbatches=2, attention="flash", compute_dtype="bfloat16",
        remat=True, **kw)
    want = [ref.step(toks) for _ in range(3)]
    got = [full.step(toks) for _ in range(3)]
    assert got == pytest.approx(want, abs=5e-2)
    assert got[-1] < got[0]


def test_4d_dp_pp_tp_cp_parity():
    """The FULL composition — data x pipeline x tensor x context (ring
    attention over sequence shards) in ONE shard_map — must reproduce the
    dp-only oracle. Covers the cross-shard pieces individually easy to get
    wrong: ring causal offsets, next-token targets crossing sequence
    shards (ppermute'd first token), global position embeddings, and the
    per-axis gradient collectives."""
    from mmlspark_tpu.parallel import MODEL_AXIS, SEQ_AXIS
    toks = _toks(b=8, s=32)
    ref = ShardedLMTrainer(mesh=grid_mesh((8, 1)), **_KW)
    want = [ref.step(toks) for _ in range(3)]
    axes = (DATA_AXIS, PIPE_AXIS, MODEL_AXIS, SEQ_AXIS)
    for shape in [(1, 1, 1, 8), (1, 2, 1, 4), (2, 2, 1, 2), (1, 2, 2, 2)]:
        t = PipelinedLMTrainer(mesh=grid_mesh(shape, axes),
                               n_microbatches=2, **_KW)
        got = [t.step(toks) for _ in range(3)]
        assert got == pytest.approx(want, abs=2e-3), shape


def test_4d_flash_blocks_inside_ring():
    """attention='flash' with a seq axis streams each ROTATING ring block
    through the Pallas kernel — flash within the device, ppermute across
    the ring, GPipe across stages, Megatron across tensor shards, all in
    one program; still oracle-exact."""
    from mmlspark_tpu.parallel import MODEL_AXIS, SEQ_AXIS
    toks = _toks(b=8, s=32)
    ref = ShardedLMTrainer(mesh=grid_mesh((8, 1)), **_KW)
    want = [ref.step(toks) for _ in range(2)]
    t = PipelinedLMTrainer(
        mesh=grid_mesh((1, 2, 2, 2),
                       (DATA_AXIS, PIPE_AXIS, MODEL_AXIS, SEQ_AXIS)),
        n_microbatches=2, attention="flash", **_KW)
    got = [t.step(toks) for _ in range(2)]
    assert got == pytest.approx(want, abs=2e-3)
    # ragged sequence vs the seq axis is refused clearly
    with pytest.raises(ValueError, match="seq axis"):
        t.step(_toks(b=8, s=31))
