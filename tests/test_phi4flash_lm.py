"""The state-space decoder with shared arrays (Mamba layers, differential
attention under a window and full, a memory layer and a KV layer that
Gated Memory Units and cross-attention layers read) through the unedited
`PipelinedLMTrainer`, against the benchmark's plain float32 reference
(`benchmark/reference/phi4_flash.py`) at toy widths on the CPU."""
import importlib.util
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mmlspark_tpu.models.dnn import ssm_layers
from mmlspark_tpu.models.dnn.lm_spec import LMSpec, Run, phi4flash_spec
from mmlspark_tpu.models.dnn.pp_training import PipelinedLMTrainer
from mmlspark_tpu.parallel import DATA_AXIS, PIPE_AXIS, grid_mesh
from mmlspark_tpu.reliability.metrics import reliability_metrics
from mmlspark_tpu.telemetry import names as tnames

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# the published rule at toy widths: 8 layers = (Mamba, window) x 2, the
# memory Mamba, the KV layer, (GMU, cross) x 1
CFG = dict(hidden_size=32, intermediate_size=48, layer_norm_eps=1e-5,
           mb_per_layer=2, num_attention_heads=8, num_hidden_layers=8,
           num_key_value_heads=4, sliding_window=5, vocab_size=61,
           mamba_d_state=8, mamba_dt_rank=4)
WHOLE = {**CFG, "num_hidden_layers": 32}
# one period of each run of the whole model, as the benchmark's cell holds
CUT = {**WHOLE, "held_layers": [14, 19]}


def load_benchmark(*path):
    """A file of `benchmark/`, imported by its path."""
    spec = importlib.util.spec_from_file_location(
        "benchmark_" + path[-1][:-3], os.path.join(REPO, "benchmark", *path))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def ref():
    return load_benchmark("reference", "phi4_flash.py")


def trainer_of(cfg=CFG, pipe=1, **kw):
    kw = {"n_microbatches": 1, "lr": 1e-3, "seed": 3, "attention": "dense",
          "remat": True, **kw}
    return PipelinedLMTrainer(
        model=phi4flash_spec(cfg),
        mesh=grid_mesh((1, pipe), (DATA_AXIS, PIPE_AXIS)), **kw)


def shaken(trainer, seed=7):
    """The trainer with every leaf moved off its initial value (biases
    start at 0 and norms at 1, where a dropped term would not show)."""
    rng = np.random.default_rng(seed)
    trainer.params = jax.tree_util.tree_map(
        lambda a: a + jnp.asarray(
            rng.standard_normal(a.shape) * 0.05, a.dtype), trainer.params)
    return trainer


def tokens(batch=2, seq=40, seed=0, vocab=CFG["vocab_size"]):
    return np.random.default_rng(seed).integers(
        0, vocab, (batch, seq)).astype(np.int32)


def rel(got, want):
    return float(jnp.linalg.norm(got - want)
                 / (jnp.linalg.norm(want) + 1e-30))


def by_path(tree):
    return {jax.tree_util.keystr(p): a for p, a in
            jax.tree_util.tree_leaves_with_path(tree)}


def test_the_published_rule_as_a_description():
    whole = phi4flash_spec(WHOLE)
    assert whole.runs == (
        Run(("mamba", "window_attention"), 8, 0),
        Run(("memory_mamba", "kv_attention"), 1, 16),
        Run(("gmu", "cross_attention"), 7, 18))
    assert len(whole.period) == 32 and whole.n_periods == 1
    assert whole.family is ssm_layers
    cut = phi4flash_spec(CUT)
    assert cut.runs == (Run(("mamba", "window_attention"), 1, 14),
                        Run(("memory_mamba", "kv_attention"), 1, 16),
                        Run(("gmu", "cross_attention"), 1, 18))
    assert cut.mamba.d_inner == 64 and cut.diff_attention.window == 5
    # the family's defaults where the file gives no Mamba size
    plain = phi4flash_spec({k: v for k, v in WHOLE.items()
                            if not k.startswith("mamba_")})
    assert (plain.mamba.d_state, plain.mamba.dt_rank,
            plain.mamba.conv_width) == (16, 2, 4)


@pytest.mark.parametrize("runs,match", [
    ((Run(("gmu", "cross_attention"), 1, 18),), "reads what a memory_mamba"),
    ((Run(("memory_mamba", "kv_attention"), 2, 16),), "repeated once"),
    ((), "is its runs"),
])
def test_a_description_the_family_cannot_run_is_refused(runs, match):
    whole = phi4flash_spec(CFG)
    with pytest.raises(ValueError, match=match):
        LMSpec(vocab_size=8, d_model=32, d_ff=48, runs=runs, n_periods=1,
               period=tuple(k for r in runs for k in r.period * r.n)
               or ("mamba",), mamba=whole.mamba,
               diff_attention=whole.diff_attention)


def test_a_pipe_axis_of_two_stages_is_refused():
    """The shared arrays cannot cross a stage boundary: the model is one
    period, which the trainer cannot divide, and `check` says why."""
    with pytest.raises(ValueError, match="must divide by the pipe axis"):
        trainer_of(pipe=2)
    spec = phi4flash_spec(CFG)
    with pytest.raises(ValueError, match="cross a pipe-stage boundary"):
        LMSpec(**{**{f.name: getattr(spec, f.name) for f in
                     spec.__dataclass_fields__.values()}, "n_periods": 2})


@pytest.fixture(scope="module", params=["dense", "flash"])
def whole_model(ref, request):
    """System and reference loss and gradients of the 8-layer model on one
    batch; `flash` sends both attention kinds down the kernels (window and
    full, values twice as wide as keys) through the interpreter."""
    trainer = shaken(trainer_of(attention=request.param))
    tok = tokens()
    weights = jax.tree_util.tree_map(np.asarray, trainer.params)
    ref_loss, ref_grads = ref.loss_and_grads(weights, tok, CFG)
    with jax.default_matmul_precision("highest"):
        sys_loss, sys_grads = trainer.loss_and_grads(tok)
    return sys_loss, ref_loss, by_path(sys_grads), by_path(ref_grads)


def test_loss_matches_the_reference(whole_model):
    sys_loss, ref_loss, _, _ = whole_model
    assert abs(sys_loss - ref_loss) < 1e-4


# one case a kind of leaf, so each counts: the whole tree is compared
LEAF_KINDS = ["['embed']", "final_norm", "ln_1", "ln_2", "in_proj", "conv_w",
              "conv_b", "x_proj", "dt_proj", "dt_bias", "A_log", "['D']",
              "out_proj", "qkv_proj", "qkv_bias", "q_proj", "q_bias",
              "o_proj", "o_bias", "lq1", "lk2", "subln", "['mixer']['w1']",
              "['mixer']['w2']", "['mlp']"]


@pytest.mark.parametrize("kind", LEAF_KINDS)
def test_gradients_match_the_reference(whole_model, kind):
    _, _, got, want = whole_model
    mine = [p for p in want if kind in p]
    assert mine, kind
    for path in mine:
        assert rel(got[path], want[path]) < 1e-4, path


def test_bfloat16_stays_near_the_float32_reference(ref):
    """Mixed precision: loss within 2e-2, every leaf's gradient within 0.15
    of the reference's norm (bfloat16 has 8 bits: a matrix's gradient
    reads 1e-2 to 5e-2 here, the lambda vectors' the most)."""
    trainer = shaken(trainer_of(compute_dtype="bfloat16"))
    tok = tokens()
    weights = jax.tree_util.tree_map(np.asarray, trainer.params)
    ref_loss, ref_grads = ref.loss_and_grads(weights, tok, CFG)
    sys_loss, sys_grads = trainer.loss_and_grads(tok)
    assert abs(sys_loss - ref_loss) < 2e-2
    want = by_path(ref_grads)
    worst = {p: rel(g, want[p]) for p, g in by_path(sys_grads).items()}
    assert max(worst.values()) < 0.15, max(worst.items(), key=lambda x: x[1])


def test_the_whole_arrangement_trains_through_the_unedited_trainer():
    """32 layers as three runs (8, 1 and 7 repetitions: two scans and a
    plain call), two microbatches: the loss falls, and one trace of the
    step counts the 14 sublayers that read a shared array."""
    trainer = trainer_of(WHOLE, n_microbatches=2)
    tok = tokens()
    before = reliability_metrics.get(tnames.LM_SHARED_READERS)
    loss0, _ = trainer.loss_and_grads(tok)
    assert reliability_metrics.get(tnames.LM_SHARED_READERS) - before == 14
    losses = [trainer.step(tok) for _ in range(5)]
    assert np.isfinite(losses).all() and losses[-1] < loss0 - 0.05
    layers = trainer.params["layers"]
    assert [jax.tree_util.tree_leaves(run)[0].shape[0]
            for run in layers] == [8, 1, 7]


def test_the_cut_keeps_published_indices(ref):
    """Layers 2 to 7 of 8: the system agrees with the reference, which
    takes each layer's index from `held_layers`, and not with the same six
    kinds two layers further down a deeper model (layers 4 to 9 of 12),
    whose lam0 differ. At the cell's own cut the KV layer's lam0 is layer
    17's (0.7963), not the fourth held layer's (0.5561)."""
    cut = {**CFG, "held_layers": [2, 7]}
    deeper = {**CFG, "num_hidden_layers": 12, "held_layers": [4, 9]}
    assert phi4flash_spec(cut).period == phi4flash_spec(deeper).period
    trainer = shaken(trainer_of(cut))
    tok = tokens()
    weights = jax.tree_util.tree_map(np.asarray, trainer.params)
    with jax.default_matmul_precision("highest"):
        sys_loss, _ = trainer.loss_and_grads(tok)
    assert abs(sys_loss - ref.loss_and_grads(weights, tok, cut)[0]) < 1e-4
    assert abs(sys_loss - ref.loss_and_grads(weights, tok, deeper)[0]) > 1e-3
    assert phi4flash_spec(CUT).runs[1].first == 16
    assert abs(float(ssm_layers.lam0_of(17))
               - (0.8 - 0.6 * math.exp(-5.1))) < 1e-6


def test_shared_gradients_reach_their_makers():
    """The memory Mamba's gradient has two paths, its own output and the
    GMU; with the GMU's W_2 at zero only the first is left, and the
    difference is exactly the GMU's path: the first-half Mamba's gradient
    (which reaches the loss through the residual stream alone) changes
    too, but the memory layer's `D` (which the residual path and the memory
    path both read) changes by what the memory carried. Likewise the KV
    layer's k and v columns of `qkv_proj` with the cross layer's W_o."""
    tok = tokens()

    def grads(zero):
        trainer = shaken(trainer_of(CUT))
        p = trainer.params
        for run, pos, leaf in zero:
            p["layers"][run][pos]["mixer"][leaf] = jnp.zeros_like(
                p["layers"][run][pos]["mixer"][leaf])
        with jax.default_matmul_precision("highest"):
            return trainer.loss_and_grads(tok)[1]["layers"]

    full = grads([])
    no_gmu = grads([(2, 0, "w2")])
    no_cross = grads([(2, 1, "o_proj")])
    # the GMU's own input gate sees the memory only through W_2
    assert float(jnp.abs(no_gmu[2][0]["mixer"]["w1"]).max()) == 0.0
    assert float(jnp.abs(full[2][0]["mixer"]["w1"]).max()) > 0.0
    a_log = [g[1][0]["mixer"]["A_log"] for g in (full, no_gmu)]
    assert rel(a_log[0], a_log[1]) > 1e-3
    # cross-attention reads k and v: with its output projection at zero
    # its own query projection gets nothing, and the KV layer's k, v
    # columns lose that path
    assert float(jnp.abs(no_cross[2][1]["mixer"]["q_proj"]).max()) == 0.0
    h_d = 8 * 4
    kv_cols = [g[1][1]["mixer"]["qkv_proj"][..., h_d:]
               for g in (full, no_cross)]
    assert rel(kv_cols[0], kv_cols[1]) > 1e-3


@pytest.mark.parametrize("attention", ["dense", "flash"])
def test_remat_changes_no_number(attention):
    """`remat` true and false: the same loss and gradients; under true the
    attention sublayers' checkpoints keep the flash residuals (counted)
    and the shared arrays are kept by name (`lm.shared`)."""
    tok = tokens()
    plain = shaken(trainer_of(CUT, remat=False, attention=attention))
    loss, grads = plain.loss_and_grads(tok)
    before = reliability_metrics.get(tnames.LM_REMAT_KEEP_FLASH)
    again = shaken(trainer_of(CUT, remat=True, attention=attention))
    got_loss, got = again.loss_and_grads(tok)
    kept = reliability_metrics.get(tnames.LM_REMAT_KEEP_FLASH) - before
    assert kept == (3 if attention == "flash" else 0)
    assert abs(got_loss - loss) < 1e-6
    want = by_path(grads)
    for path, g in by_path(got).items():
        assert rel(g, want[path]) < 5e-5, path
    assert {tnames.KEEP_SSM, tnames.KEEP_SHARED} <= set(
        tnames.REMAT_RESIDUALS)


def test_the_parameter_count_is_the_widths_arithmetic():
    """The toy model's tree holds what the benchmark's work file counts
    from the widths, and at the published widths, six layers and 25,008
    rows that count is 697,094,272 (what the driver holds its tree to)."""
    work = load_benchmark("work_phi4_flash.py")
    tree = ssm_layers.init(phi4flash_spec(CUT), 0)
    held = sum(a.size for a in jax.tree_util.tree_leaves(tree))
    assert held == work.parameter_count(CUT)
    published = dict(CUT, hidden_size=2560, intermediate_size=10240,
                     num_attention_heads=40, num_key_value_heads=20,
                     vocab_size=25008, mamba_d_state=16, mamba_dt_rank=160)
    assert work.parameter_count(published) == 697_094_272
