"""lm_train_hybrid driver: training steps of a hybrid decoder (Gated
DeltaNet, gated grouped-KV attention, sparse experts: the `qwen3_next`
family) through `PipelinedLMTrainer` on the one-chip (data 1, pipe 1) mesh,
the model built from the configuration file as a description
(`lm_spec.qwen3_next_spec`) with the chip's share of the experts.

Set-up builds the trainer from the seed, places each layer's experts on the
group's chips so that this chip carries its share of the load whatever the
seed (`place_experts`), and decides correctness on the TIMED step program's
own first step, at the published widths, on the cell's
own first batch (2 x 8192 tokens) and the seeded initial weights. The plain
float32 reference gives its loss and, for one leaf of each new kind, its
gradient on that batch (on the chip, a sequence at a time, before the step
takes the memory). Then the first `trainer.step(tokens)` runs, and what it
left is read back:
  (a) its loss against the reference's;
  (b) its gradient, leaf by leaf, against the reference's by relative
      error. The step returns no gradient, but Adam's first moment after
      one step from a zero state is (1 - b1) times it, so the optimizer
      state the step wrote holds the gradient the step computed;
  (c) the change of those leaves' parameters over the step against the
      change the reference's gradient gives by Adam's rule.
Then the mix's other warm-up steps. The window is back-to-back
`trainer.step(tokens)`, each on a fresh batch drawn on the host from a
seeded Zipf unigram stream over the vocabulary slice, each ended by the loss
on the host. The traced run then traces five more steps.
"""
import time

import numpy as np

TRACED_STEPS = 5
# `optax.adam`'s defaults, which the trainer takes.
ADAM_B1, ADAM_EPS = 0.9, 1e-8
# What the limits rest on (PERF.md section 4 has the readings by seed): the
# system's first timed step on the v5e over eleven seeds, and the reference
# computed in bfloat16 throughout on the same chip, two seeds
# (`tests/calibrate_hybrid.py`), each against the float32 reference on the
# cell's 2 x 8192 tokens. At this size precision moves little that the step
# leaves behind: the two share their bfloat16 operands, and over 16,384
# tokens what float32 accumulation, norms, softmax and state add averages
# out, so the bfloat16 reference reads 10% to 20% above the system on every
# leaf, which for most leaves is inside what the seed moves.
# (a) The loss within the accepted LM cell's band: the system read 2e-6 to
#     2.4e-4 apart over eleven seeds, the bfloat16 reference 7e-6 and 3.4e-5,
#     so the loss cannot hold the precision.
# (b) The gradient leaf by leaf. ONE leaf holds the precision,
#     `gdn.in_proj_qkvz` (25M elements, fed by the recurrence's backward
#     pass, the convolution, both norms and every later layer): the system
#     read 0.0279 to 0.0296 (eleven seeds), the bfloat16 reference 0.0337 on
#     both of its seeds; the limit 0.032 leaves the system 8% and fails the
#     reference by 5%. The other leaves' limits are for what is structural
#     (a missing gate, a skipped decay, a wrong chunk carry, a dropped
#     pair's expert, a transposed or unscaled matrix move their leaves by
#     tens of percent to 100%) and do not tell bfloat16 from float32: 3 to
#     5 times the largest reading of the first two seeds (`head` 0.0058;
#     `attn.q_proj` 0.0107; `gdn.A_log`, `dt_bias`, `conv` 0.0245 to 0.0267;
#     `moe.shared_expert_gate` 0.0376; the router and the routed experts,
#     which routing moves most, 0.097 to 0.111), written before any other
#     seed was read. Their two readings lie too close for a limit between
#     them (`head` 0.0060 against 0.0064, `gdn.conv` 0.0260 against 0.0292,
#     `gdn.A_log` 0.0396 against 0.0287): routing is discrete, and where a
#     token's tenth and eleventh experts nearly tie, bfloat16 activations
#     swap them, which moves every leaf the expert layers' backward pass
#     feeds by seed.
# (c) The parameters' change over the first step, all compared leaves as
#     one vector, against the change the reference's gradient gives by
#     Adam's rule, between the first reading and 1, which is what a state
#     left as it was reads. The system read 0.236 to 0.253 (the bfloat16
#     reference 0.257 and 0.268). That is no rounding, and here is why:
#     Adam's first step is lr in the SIGN of the gradient, so an element
#     whose gradient is smaller than its error moves by 2 lr the wrong way;
#     a leaf 2.8% apart in gradient has about 1.5% of such elements, and
#     2 sqrt(0.015) = 0.24. An update not applied reads 1, one of the wrong
#     sign 2, a rate twice too large 1 and more.
LOSS_BAND = 3e-3
CHANGE_LIMIT = 0.65
GRAD_LIMIT = {
    "head": 0.03, "gdn.A_log": 0.1, "gdn.dt_bias": 0.1, "gdn.conv": 0.1,
    "gdn.in_proj_qkvz": 0.032, "attn.q_proj": 0.1,
    "moe.shared_expert_gate": 0.1, "moe.router": 0.35, "moe.w_gate": 0.35,
    "moe.w_up": 0.35, "moe.w_down": 0.35,
}


def zipf_stream(seed, vocab, exponent, batch, seq):
    """The LM cell's unigram stream over `vocab` ids: rank r has weight
    r^-s, ranks dealt to token ids by a seeded permutation."""
    rng = np.random.default_rng(seed)
    weights = np.arange(1, vocab + 1, dtype=np.float64) ** -exponent
    cdf = np.cumsum(weights / weights.sum())
    ids = rng.permutation(vocab).astype(np.int32)

    def make_batch():
        ranks = np.searchsorted(cdf, rng.random((batch, seq)))
        return ids[np.minimum(ranks, vocab - 1)]

    return make_batch


def compared_leaves(tree):
    """The leaves of a tree shaped like the weights that the check compares,
    one of each new kind, by name: the first Gated-DeltaNet layer's, its
    expert layer's (the held experts' three stacked matrices), the
    full-attention layer's `q_proj`, the head."""
    layers = tree["layers"]
    gdn, attn = layers[0], layers[-1]
    out = {"gdn." + k: gdn["mixer"][k]
           for k in ("A_log", "dt_bias", "conv", "in_proj_qkvz")}
    for k in ("router", "shared_expert_gate", "w_gate", "w_up", "w_down"):
        out["moe." + k] = gdn["moe"][k]
    out["attn.q_proj"] = attn["mixer"]["q_proj"]
    out["head"] = tree["head"]
    return out


def on_host(leaves):
    return {k: np.asarray(v, np.float32) for k, v in leaves.items()}


def relative_errors(mine, theirs):
    return {k: float(np.linalg.norm(mine[k] - theirs[k])
                     / max(np.linalg.norm(theirs[k]), 1e-30))
            for k in theirs}


def adam_first_change(grads, lr):
    """The parameters' change in Adam's first step from a zero state: both
    moments' bias corrections cancel, leaving -lr g / (|g| + eps)."""
    return {k: -lr * g / (np.abs(g) + ADAM_EPS) for k, g in grads.items()}


def change_error(mine, theirs):
    """|change - reference's change| / |reference's change| over all the
    compared leaves as one vector: 1 for a state left as it was."""
    apart = sum(float(np.sum((mine[k] - theirs[k]) ** 2)) for k in theirs)
    whole = sum(float(np.sum(theirs[k] ** 2)) for k in theirs)
    return (apart / max(whole, 1e-30)) ** 0.5


def build_trainer(cfg, seed):
    from mmlspark_tpu.models.dnn.lm_spec import qwen3_next_spec
    from mmlspark_tpu.models.dnn.pp_training import PipelinedLMTrainer
    from mmlspark_tpu.parallel import DATA_AXIS, PIPE_AXIS, grid_mesh
    opts = cfg["trainer"]
    spec = qwen3_next_spec(cfg, cfg["experts_held"],
                           n_experts=cfg["published"]["num_experts"])
    return PipelinedLMTrainer(
        model=spec, mesh=grid_mesh((1, 1), (DATA_AXIS, PIPE_AXIS)),
        n_microbatches=opts["n_microbatches"], lr=cfg["assumed"]["lr"],
        attention=opts["attention"], seed=seed, optimizer=opts["optimizer"],
        compute_dtype=opts["compute_dtype"], remat=opts["remat"])


def deal_experts(loads, per_chip):
    """The experts dealt to `len(loads) // per_chip` chips, `per_chip`
    each: in order of falling load, each to the least loaded chip that
    still has room (longest processing time first). A list of expert ids
    per chip."""
    chips = len(loads) // per_chip
    dealt, carried = [[] for _ in range(chips)], np.zeros(chips)
    for e in np.argsort(-np.asarray(loads, np.float64), kind="stable"):
        room = [c for c in range(chips) if len(dealt[c]) < per_chip]
        c = min(room, key=lambda c: carried[c])
        dealt[c].append(int(e))
        carried[c] += loads[e]
    return dealt


def place_experts(reference, trainer, cfg, tokens):
    """Place each layer's experts on the group's chips so that the chips
    carry equal loads, as a deployment's expert-parallel group does, and
    return this chip's share of each layer's pairs on `tokens`.

    Why: at seeded weights a layer's experts are not equally wanted (the
    fullest gets several times the mean), so the share of the pairs that
    falls to ids lo .. hi-1 differs by seed (5.6% to 6.7% for 6.25%), and
    with it the work of a step. Layer by layer, the plain reference's
    forward pass counts how many of `tokens`' pairs each expert gets,
    `deal_experts` deals the experts to the chips, and the router's columns
    are permuted so that this chip's deal gets the ids it holds (the held
    experts' own weights are drawn independently of their ids and stay).
    The layer's output, with that placement, feeds the next layer. It
    holds for the batches that follow as far as they are like the first and
    the router stays where it was: over the window the share reads 6.1% to
    6.4% (PERF.md section 6)."""
    import jax
    lo, hi = cfg["experts_held"]
    eps, kinds = cfg["rms_norm_eps"], reference.layer_kinds(cfg)

    def through(kind):
        mixer = (reference.attention_mixer if kind == "attention"
                 else reference.gdn_mixer)
        return jax.jit(lambda x, lp: x + mixer(
            reference.rms_norm(x, lp["norm_in"], eps), lp["mixer"], cfg))

    mixed = {kind: through(kind) for kind in set(kinds)}
    picks = jax.jit(lambda x, lp: reference.route(
        reference.rms_norm(x, lp["norm_post"], eps), lp["moe"], cfg)[0])
    fed = jax.jit(lambda x, lp: x + reference.moe(
        reference.rms_norm(x, lp["norm_post"], eps), lp["moe"], cfg,
        (lo, hi)))
    weights = trainer.params
    n_periods = jax.tree_util.tree_leaves(weights["layers"][0])[0].shape[0]
    xs = [weights["embed"][seq] for seq in np.asarray(tokens)]
    shares = []
    for period in range(n_periods):
        for pos, kind in enumerate(kinds):
            lp = jax.tree_util.tree_map(lambda a: a[period],
                                        weights["layers"][pos])
            xs = [mixed[kind](x, lp) for x in xs]
            router = lp["moe"]["router"]
            loads = np.bincount(
                np.concatenate([np.asarray(picks(x, lp)).ravel()
                                for x in xs]), minlength=router.shape[1])
            mine = deal_experts(loads, hi - lo)[lo // (hi - lo)]
            rest = np.setdiff1d(np.arange(len(loads)), mine)
            order = np.concatenate([rest[:lo], mine, rest[lo:]])
            lp["moe"]["router"] = router[:, order]
            stacked = weights["layers"][pos]["moe"]["router"]
            weights["layers"][pos]["moe"]["router"] = jax.device_put(
                stacked.at[period].set(lp["moe"]["router"]),
                stacked.sharding)
            xs = [fed(x, lp) for x in xs]
            shares.append(float(loads[mine].sum() / loads.sum()))
    return shares


def reference_readings(reference, trainer, cfg, tokens, **kwargs):
    """(loss, host gradient of the compared leaves) of the reference on
    `tokens` at the trainer's weights, which stay where they are."""
    loss, grads = reference.loss_and_grads(
        trainer.params, tokens, cfg, tuple(cfg["experts_held"]),
        pick=compared_leaves, **kwargs)
    return loss, on_host(grads)


def first_step_readings(trainer, step, tokens):
    """(loss, gradient, parameters' change) of the compared leaves in the
    trainer's first `step(tokens)`, all three as the step program left
    them: the gradient from Adam's first moment."""
    before = on_host(compared_leaves(trainer.params))
    loss = step(tokens)[0]
    grads = {k: m / (1.0 - ADAM_B1) for k, m in on_host(
        compared_leaves(trainer.opt_state[0].mu)).items()}
    after = on_host(compared_leaves(trainer.params))
    return loss, grads, {k: after[k] - before[k] for k in after}


def run(bench):
    import work_qwen3_next as work
    from harness import load_module
    from mmlspark_tpu.reliability.metrics import reliability_metrics
    from mmlspark_tpu.telemetry import names as tnames

    cfg, mix = bench.cfg, bench.mix
    batch, seq, vocab = mix["batch"], mix["seq"], cfg["vocab_size"]
    held = tuple(cfg["experts_held"])
    problems, notes = [], {}
    trainer = build_trainer(cfg, bench.seed)
    make_batch = zipf_stream(bench.seed, vocab, mix["zipf_exponent"], batch,
                             seq)
    tokens = make_batch()
    reference = load_module("reference", cfg["reference"], bench.bench_dir)
    notes["placed_share_by_layer"] = place_experts(reference, trainer, cfg,
                                                   tokens)

    ref_loss, ref_grads = reference_readings(reference, trainer, cfg, tokens)
    counters = (tnames.MOE_PAIRS_ROUTED, tnames.MOE_PAIRS_HELD)

    def step(tokens):
        """(loss, pairs routed, pairs held) of one `trainer.step`."""
        before = [reliability_metrics.get(name) for name in counters]
        loss = trainer.step(tokens)
        routed, held = (reliability_metrics.get(name) - was
                        for name, was in zip(counters, before))
        return loss, routed, held

    first, grads, change = first_step_readings(trainer, step, tokens)
    errors = relative_errors(grads, ref_grads)
    lr = cfg["assumed"]["lr"]
    change_apart = change_error(change, adam_first_change(ref_grads, lr))
    del grads, ref_grads, change
    notes.update(loss_reference=ref_loss, loss_system=first,
                 loss_band=LOSS_BAND,
                 grad_rel_error={k: [errors[k], GRAD_LIMIT[k]]
                                 for k in sorted(errors)},
                 param_change_error=[change_apart, CHANGE_LIMIT])
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
    for _ in range(mix["warmup_steps"] - 1):
        step(make_batch())

    t0 = bench.setup_done()
    attempted = failed = 0
    walls, losses, held_by_step, routed_pairs, t_last = [], [], [], 0, t0
    while bench.open():
        attempted += 1
        t_step = time.perf_counter()
        with bench.span("make_batch"):
            tokens = make_batch()
        with bench.span("lm_step"):
            loss, routed, held_now = step(tokens)
        t_last = time.perf_counter()
        walls.append(t_last - t_step)
        losses.append(loss)
        held_by_step.append(held_now)
        routed_pairs += routed
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
    held_pairs = sum(held_by_step)
    tokens_done = max(attempted, 1) * batch * seq
    facts = {"lm_flops_per_token": work.lm_flops_per_token(
        cfg, seq, held_pairs / tokens_done)}
    program = {}
    if bench.trace_on:
        traced_held, loads = 0, []
        with bench.traced():
            for _ in range(TRACED_STEPS):
                with bench.span("make_batch"):
                    tokens = make_batch()
                with bench.span("lm_step"):
                    traced_held += step(tokens)[2]
                loads.append(reliability_metrics.gauge(
                    tnames.MOE_LOAD_MAX_OVER_MEAN))
        facts.update(
            traced_steps=TRACED_STEPS,
            gdn_scan_flops_per_step=work.gdn_scan_flops_per_step(
                cfg, batch, seq),
            moe_experts_flops_per_step=work.moe_experts_flops_per_step(
                cfg, traced_held / TRACED_STEPS),
            flash_d256_flops_per_step=work.flash_flops_per_step(
                cfg, batch, seq))
        program[tnames.MOE_LOAD_MAX_OVER_MEAN] = float(np.mean(loads))

    bench.note_program_memory(trainer._step.lower(
        trainer.params, trainer.opt_state,
        trainer._to_device(tokens)).compile().memory_analysis())
    done = attempted - failed
    notes.update(steps=done, loss_first10=float(np.mean(losses[:10])),
                 loss_last10=float(np.mean(losses[-10:])),
                 step_median_ms=float(np.median(walls) * 1e3),
                 moe_pairs_routed_per_step=routed_pairs / max(attempted, 1),
                 moe_pairs_held_per_step=held_pairs / max(attempted, 1),
                 moe_pairs_held_share=held_pairs / max(routed_pairs, 1),
                 moe_pairs_held_first10=float(np.mean(held_by_step[:10])),
                 moe_pairs_held_last10=float(np.mean(held_by_step[-10:])),
                 moe_pairs_held_share_expected=(held[1] - held[0])
                 / cfg["published"]["num_experts"])
    return {"metrics": {
                "lm_tokens_per_s": done * batch * seq / (t_last - t0),
                "lm_step_p95_ms": float(np.percentile(walls, 95) * 1e3)},
            "attempted": attempted, "failed": int(failed),
            "problems": problems, "facts": facts, "program": program,
            "notes": notes}
