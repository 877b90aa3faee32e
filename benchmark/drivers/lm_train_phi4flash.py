"""lm_train_phi4flash driver: training steps of a state-space decoder with
shared arrays (Mamba selective-scan layers, differential attention under a
sliding window and full, a memory layer and a KV layer that a Gated Memory
Unit and a cross-attention layer read: the `phi4flash` family) through
`PipelinedLMTrainer` on the one-chip (data 1, pipe 1) mesh, the model built
from the configuration file as a description (`lm_spec.phi4flash_spec`).

It follows the `lm_train_hybrid` driver, whose helpers it imports (the Zipf
stream, the comparisons): set-up builds the trainer from the seed and
decides correctness on the TIMED step program's own first step, at the
published widths, on the cell's own first batch (2 x 8192 tokens) and the
seeded initial weights, against the plain float32 reference:
  (a) the loss;
  (b) the gradient of one leaf of each new kind, read back from Adam's
      first moment, by relative error: the memory Mamba's `A_log`, `x_proj`
      and `in_proj` (two paths reach them: the layer's own output and the
      GMU's reading of the memory), the other Mamba's `A_log`, the GMU's
      `w1`, the KV layer's `qkv_proj` (k and v also get the cross layer's
      cotangent), the cross layer's `q_proj`, `lq1` and `subln`, the window
      layer's `qkv_proj`, the tied `embed`;
  (c) the parameters' change against the change the reference's gradient
      gives by Adam's rule.
Then the mix's other warm-up steps. The window is back-to-back
`trainer.step(tokens)`, each on a fresh batch drawn on the host from a
seeded Zipf unigram stream over the vocabulary slice, each ended by the
loss on the host. The traced run then traces five more steps. There is no
router: every seed does the same work.
"""
import functools
import time

import numpy as np

TRACED_STEPS = 5
# What the limits rest on (PERF.md section 4 has the readings): the system's
# first timed step on the v5e and the reference computed in bfloat16
# throughout on the same chip, eight seeds each on the same batch and
# weights (`tests/calibrate_phi4flash.py`, PR 35), each against the float32
# reference on the cell's 2 x 8192 tokens. There is no router to flip
# tokens, so the seed moves a matrix's reading by about 1% and the bfloat16
# reference reads 5% to 9% above the system on the same seed on the Mamba,
# GMU and cross layers' matrices.
# (a) The loss within the accepted LM cells' band: the system read 5.4e-5
#     to 3.3e-4 apart, the bfloat16 reference 1.0e-5 to 2.9e-4, so the loss
#     cannot hold the precision.
# (b) The gradient leaf by leaf. ONE leaf holds the precision, `gmu.w1`
#     (13M elements; its gradient passes through the memory, the gate, W_2
#     and every later layer): the system read 0.02503 to 0.02533, the
#     bfloat16 reference 0.02660 to 0.02692; the limit 0.0260 leaves the
#     system 2.6% (seven times its spread) and fails the reference by
#     2.3%, on every seed read. Four more leaves show the same gap
#     (`mamba.A_log` 0.02518 / 0.02640, `memory.A_log` 0.03037 / 0.03149,
#     `memory.x_proj` 0.03016 / 0.03162, `memory.in_proj` 0.02619 /
#     0.02669) and keep structural limits: one precision limit is one
#     chance of a false alarm. The other limits are for what is structural
#     (a missing gate, a wrong pairing of heads, a window off by one, a
#     lam0 of the wrong layer, a memory taken after its gate, a reader's
#     cotangent dropped move their leaves by tens of percent to 100%): 3 to
#     4 times the largest reading (`cross.lq1`, 64 numbers whose gradient
#     is a difference of two exponentials' terms, read 1e-4 to 0.055;
#     `kv.qkv_proj` 0.014 to 0.043; `cross.subln` 0.009 to 0.018; the
#     matrices 0.021 to 0.030).
# (c) The parameters' change over the first step, all compared leaves as
#     one vector, between the first reading (0.122 to 0.129; the bfloat16
#     reference 0.127 to 0.130) and 1, which is what a state left as it was
#     reads. A tenth is no rounding, and `lm_train_hybrid.py` (c) says why:
#     Adam's first step is lr in the SIGN of the gradient, so an element
#     whose gradient is smaller than its error moves by 2 lr the wrong way;
#     leaves 2.5% apart in gradient have about 0.4% of such elements, and
#     2 sqrt(0.004) = 0.13.
LOSS_BAND = 3e-3
CHANGE_LIMIT = 0.65
GRAD_LIMIT = {
    "memory.A_log": 0.1, "memory.x_proj": 0.1, "memory.in_proj": 0.1,
    "mamba.A_log": 0.1, "gmu.w1": 0.0260, "kv.qkv_proj": 0.15,
    "cross.q_proj": 0.1, "cross.lq1": 0.2, "cross.subln": 0.06,
    "window.qkv_proj": 0.1, "embed": 0.1,
}
PRECISION_LEAVES = ("gmu.w1",)


@functools.lru_cache(maxsize=None)
def hybrid():
    """The `lm_train_hybrid` driver, for its helpers."""
    from harness import load_module
    return load_module("drivers", "lm_train_hybrid")


def compared_leaves(tree):
    """The leaves of a tree shaped like the weights that the check
    compares, one of each new kind, by name. The layers are found by what
    their mixers hold: a Mamba by `A_log` (the memory layer is the last of
    them), a GMU by `w2` beside no `w3`, a cross layer by `q_proj`, the
    window layer before the KV layer among those with `qkv_proj`."""
    mixers = [lp["mixer"] for run in tree["layers"] for lp in run]
    mambas = [m for m in mixers if "A_log" in m]
    selfs = [m for m in mixers if "qkv_proj" in m]
    cross = next(m for m in mixers if "q_proj" in m)
    gmu = next(m for m in mixers if "w2" in m)
    memory, kv = mambas[-1], selfs[-1]
    out = {"memory.A_log": memory["A_log"], "memory.x_proj": memory["x_proj"],
           "memory.in_proj": memory["in_proj"], "gmu.w1": gmu["w1"],
           "kv.qkv_proj": kv["qkv_proj"], "cross.q_proj": cross["q_proj"],
           "cross.lq1": cross["lq1"], "cross.subln": cross["subln"],
           "embed": tree["embed"]}
    if len(mambas) > 1:
        out["mamba.A_log"] = mambas[0]["A_log"]
    if len(selfs) > 1:
        out["window.qkv_proj"] = selfs[0]["qkv_proj"]
    return out


def build_trainer(cfg, seed):
    from harness import BenchError
    try:
        from mmlspark_tpu.models.dnn.lm_spec import phi4flash_spec
    except ImportError as e:
        raise BenchError(f"this program cannot describe a phi4flash "
                         f"model (Phi-4-mini-flash-reasoning): {e}") from e
    from mmlspark_tpu.models.dnn.pp_training import PipelinedLMTrainer
    from mmlspark_tpu.parallel import DATA_AXIS, PIPE_AXIS, grid_mesh
    opts = cfg["trainer"]
    return PipelinedLMTrainer(
        model=phi4flash_spec(cfg),
        mesh=grid_mesh((1, 1), (DATA_AXIS, PIPE_AXIS)),
        n_microbatches=opts["n_microbatches"], lr=cfg["assumed"]["lr"],
        attention=opts["attention"], seed=seed, optimizer=opts["optimizer"],
        compute_dtype=opts["compute_dtype"], remat=opts["remat"])


def reference_readings(reference, trainer, cfg, tokens, **kwargs):
    """(loss, host gradient of the compared leaves) of the reference on
    `tokens` at the trainer's weights, which stay where they are."""
    loss, grads = reference.loss_and_grads(
        trainer.params, tokens, cfg, pick=compared_leaves, **kwargs)
    return loss, hybrid().on_host(grads)


def first_step_readings(trainer, tokens):
    """(loss, gradient, parameters' change) of the compared leaves in the
    trainer's first `step(tokens)`, all three as the step program left
    them: the gradient from Adam's first moment."""
    on_host = hybrid().on_host
    before = on_host(compared_leaves(trainer.params))
    loss = trainer.step(tokens)
    grads = {k: m / (1.0 - hybrid().ADAM_B1) for k, m in on_host(
        compared_leaves(trainer.opt_state[0].mu)).items()}
    after = on_host(compared_leaves(trainer.params))
    return loss, grads, {k: after[k] - before[k] for k in after}


def scan_routes():
    from mmlspark_tpu.reliability.metrics import reliability_metrics
    from mmlspark_tpu.telemetry import names as tnames
    return {"pallas": reliability_metrics.get(tnames.SSM_SCAN_ROUTE_PALLAS),
            "xla": reliability_metrics.get(tnames.SSM_SCAN_ROUTE_XLA),
            "shared_readers": reliability_metrics.get(
                tnames.LM_SHARED_READERS)}


def run(bench):
    import jax
    import work_phi4_flash as work
    from harness import load_module
    trainer = build_trainer(bench.cfg, bench.seed)
    helpers = hybrid()

    cfg, mix = bench.cfg, bench.mix
    batch, seq, vocab = mix["batch"], mix["seq"], cfg["vocab_size"]
    problems, notes = [], {}
    make_batch = helpers.zipf_stream(bench.seed, vocab, mix["zipf_exponent"],
                                    batch, seq)
    tokens = make_batch()
    reference = load_module("reference", cfg["reference"], bench.bench_dir)
    ref_loss, ref_grads = reference_readings(reference, trainer, cfg, tokens)

    routes_before = scan_routes()
    first, grads, change = first_step_readings(trainer, tokens)
    routes = {k: v - routes_before[k] for k, v in scan_routes().items()}
    errors = helpers.relative_errors(grads, ref_grads)
    lr = cfg["assumed"]["lr"]
    change_apart = helpers.change_error(
        change, helpers.adam_first_change(ref_grads, lr))
    del grads, ref_grads, change
    notes.update(loss_reference=ref_loss, loss_system=first,
                 loss_band=LOSS_BAND,
                 grad_rel_error={k: [errors[k], GRAD_LIMIT[k]]
                                 for k in sorted(errors)},
                 param_change_error=[change_apart, CHANGE_LIMIT],
                 precision_leaves=list(PRECISION_LEAVES),
                 ssm_scan_routes=routes)
    if not abs(first - ref_loss) <= LOSS_BAND:
        problems.append(f"loss of the initial weights: system {first:.5f}, "
                        f"reference {ref_loss:.5f}, apart by more than "
                        f"{LOSS_BAND}")
    for k, err in sorted(errors.items()):
        if not err <= GRAD_LIMIT[k]:
            problems.append(f"first step's gradient of {k}: relative error "
                            f"{err:.4f} against the reference, limit "
                            f"{GRAD_LIMIT[k]}")
    if not change_apart <= CHANGE_LIMIT:
        problems.append(f"first step's change of the parameters: "
                        f"{change_apart:.4f} of the reference's apart, "
                        f"limit {CHANGE_LIMIT}")
    if jax.devices()[0].platform == "tpu" and (
            routes["xla"] or not routes["pallas"]):
        problems.append(f"the selective scan's routes on a TPU: {routes}; "
                        f"the kernels were to take every call")
    for _ in range(mix["warmup_steps"] - 1):
        trainer.step(make_batch())

    t0 = bench.setup_done()
    attempted = failed = 0
    walls, losses, t_last = [], [], t0
    while bench.open():
        attempted += 1
        t_step = time.perf_counter()
        with bench.span("make_batch"):
            tokens = make_batch()
        with bench.span("lm_step"):
            loss = trainer.step(tokens)
        t_last = time.perf_counter()
        walls.append(t_last - t_step)
        losses.append(loss)
        failed += not np.isfinite(loss)
    bench.end_window()
    if failed:
        problems.append(f"{failed} steps returned a loss that is not finite")
    if len(losses) < 20:
        problems.append(f"{len(losses)} steps completed; the checks and the "
                        f"95th percentile want 20")
    elif not np.mean(losses[-10:]) < np.mean(losses[:10]):
        problems.append(f"loss did not fall: first ten "
                        f"{np.mean(losses[:10]):.4f}, last ten "
                        f"{np.mean(losses[-10:]):.4f}")
    facts = {"lm_flops_per_token": work.lm_flops_per_token(cfg, seq)}
    if bench.trace_on:
        with bench.traced():
            for _ in range(TRACED_STEPS):
                with bench.span("make_batch"):
                    tokens = make_batch()
                with bench.span("lm_step"):
                    trainer.step(tokens)
        facts.update(
            traced_steps=TRACED_STEPS,
            ssm_scan_bytes_per_step=work.ssm_scan_bytes_per_step(
                cfg, batch, seq),
            flash_window_flops_per_step=work.flash_window_flops_per_step(
                cfg, batch, seq),
            flash_diff_flops_per_step=work.flash_diff_flops_per_step(
                cfg, batch, seq))

    bench.note_program_memory(trainer._step.lower(
        trainer.params, trainer.opt_state,
        trainer._to_device(tokens)).compile().memory_analysis())
    done = attempted - failed
    n_params = sum(int(np.prod(a.shape)) for a in
                   jax.tree_util.tree_leaves(trainer.params))
    if n_params != work.parameter_count(cfg):
        problems.append(f"the program holds {n_params} parameters, the "
                        f"work file counts {work.parameter_count(cfg)}")
    notes.update(steps=done, parameters=n_params,
                 loss_first10=float(np.mean(losses[:10])),
                 loss_last10=float(np.mean(losses[-10:])),
                 step_median_ms=float(np.median(walls) * 1e3),
                 step_max_ms=float(np.max(walls) * 1e3),
                 # which steps the host or the chip stalled in, if any
                 slow_steps={str(i): round(w * 1e3, 1)
                             for i, w in enumerate(walls)
                             if w > 1.03 * np.median(walls)})
    return {"metrics": {
                "lm_tokens_per_s": done * batch * seq / (t_last - t0),
                "lm_step_p95_ms": float(np.percentile(walls, 95) * 1e3)},
            "attempted": attempted, "failed": int(failed),
            "problems": problems, "facts": facts, "program": {},
            "notes": notes}
