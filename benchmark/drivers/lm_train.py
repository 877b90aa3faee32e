"""lm_train driver: training steps of a decoder LM through
`PipelinedLMTrainer` on the one-chip (data 1, pipe 1) mesh, built as
`chip_smoke.lm_phase` builds it (flash attention, bfloat16 compute,
`remat="save_attn"`, Adam).

Set-up builds the trainer from the seed, compares the loss of the seeded
initial weights with the plain reference on the first batch (before any
step: the step donates its weights), and runs the mix's warm-up steps. The
window is back-to-back `trainer.step(tokens)`, each on a fresh batch drawn on
the host from a seeded Zipf unigram stream, each ended by the loss on the
host. The traced run then traces five more steps.
"""
import time

import numpy as np

TRACED_STEPS = 5
# Loss of the seeded initial weights, system (bfloat16 matmul operands, f32
# accumulation, f32 layer norm and log-softmax) against the float32
# reference, on the same 8 x 1024 tokens. A bfloat16 operand is off by up
# to 2^-9 relative, but the loss is a mean over 8,184 positions of
# log-probabilities whose errors have both signs, so the two agree far
# closer than one logit does: on the v5e they were between -3.5e-4 and
# +5.1e-4 apart at a loss of 11.10 (seven seeds, PR 25). The band is 3e-3,
# six times the widest reading, and under the 1e-2 and more that 8-bit
# operands, a missing layer or a dropped residual move the loss by.
LOSS_BAND = 3e-3


def reference_weights(params):
    """The trainer's parameter tree under the reference's names."""
    return {"wte": params["embed"], "wpe": params["pos"],
            "ln_f": params["final_ln"], "blocks": params["layers"]}


def run(bench):
    from mmlspark_tpu.models.dnn.pp_training import PipelinedLMTrainer
    from mmlspark_tpu.parallel import DATA_AXIS, PIPE_AXIS, grid_mesh
    from harness import load_module

    cfg, mix = bench.cfg, bench.mix
    batch, seq, vocab = mix["batch"], mix["seq"], cfg["vocab_size"]
    problems, notes = [], {}
    opts = cfg["trainer"]
    trainer = PipelinedLMTrainer(
        vocab_size=vocab, mesh=grid_mesh((1, 1), (DATA_AXIS, PIPE_AXIS)),
        n_microbatches=opts["n_microbatches"], d_model=cfg["n_embd"],
        n_heads=cfg["n_head"], n_layers=cfg["n_layer"], d_ff=cfg["n_inner"],
        max_len=cfg["n_positions"], lr=cfg["assumed"]["lr"],
        attention=opts["attention"], seed=bench.seed,
        optimizer=opts["optimizer"], compute_dtype=opts["compute_dtype"],
        remat=opts["remat"])

    # the Zipf unigram stream: rank r has weight r^-s, ranks dealt to token
    # ids by a seeded permutation
    rng = np.random.default_rng(bench.seed)
    weights = np.arange(1, vocab + 1, dtype=np.float64) ** \
        -mix["zipf_exponent"]
    cdf = np.cumsum(weights / weights.sum())
    ids = rng.permutation(vocab).astype(np.int32)

    def make_batch():
        ranks = np.searchsorted(cdf, rng.random((batch, seq)))
        return ids[np.minimum(ranks, vocab - 1)]

    tokens = make_batch()
    reference = load_module("reference", cfg["reference"], bench.bench_dir)
    ref_loss = reference.loss(reference_weights(trainer.params), tokens,
                              cfg["n_head"], ln_eps=1e-6)
    first = trainer.step(tokens)
    notes.update(loss_reference=ref_loss, loss_system=first)
    if not abs(first - ref_loss) <= LOSS_BAND:
        problems.append(f"loss of the initial weights: system {first:.5f}, "
                        f"reference {ref_loss:.5f}, apart by more than "
                        f"{LOSS_BAND}")
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
    facts = {}
    if bench.trace_on:
        with bench.traced():
            for _ in range(TRACED_STEPS):
                with bench.span("make_batch"):
                    tokens = make_batch()
                with bench.span("lm_step"):
                    trainer.step(tokens)
        facts["traced_steps"] = TRACED_STEPS

    # what the step program needs, by the compiler's own account: the
    # runtime's peak counter leaves a program's temporaries out
    bench.note_program_memory(trainer._step.lower(
        trainer.params, trainer.opt_state,
        trainer._to_device(tokens)).compile().memory_analysis())
    done = attempted - failed
    notes.update(steps=done, loss_first10=float(np.mean(losses[:10])),
                 loss_last10=float(np.mean(losses[-10:])),
                 step_median_ms=float(np.median(walls) * 1e3))
    return {"metrics": {
                "lm_tokens_per_s": done * batch * seq / (t_last - t0),
                "lm_step_p95_ms": float(np.percentile(walls, 95) * 1e3)},
            "attempted": attempted, "failed": int(failed),
            "problems": problems, "facts": facts, "notes": notes}
