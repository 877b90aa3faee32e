"""The hybrid decoder (Gated DeltaNet, gated grouped-KV attention, sparse
experts held as one chip's share) through `PipelinedLMTrainer`, against the
benchmark's plain float32 reference (`benchmark/reference/qwen3_next.py`)
at tiny widths on the CPU; and the dense block's description against the
arguments it replaces."""
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mmlspark_tpu.models.dnn.lm_spec import (Experts, LMSpec, gpt2_spec,
                                             qwen3_next_spec)
from mmlspark_tpu.models.dnn.pp_training import PipelinedLMTrainer
from mmlspark_tpu.parallel import DATA_AXIS, PIPE_AXIS, grid_mesh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CFG = dict(hidden_size=64, num_hidden_layers=8, full_attention_interval=4,
           head_dim=32, num_attention_heads=4, num_key_value_heads=2,
           partial_rotary_factor=0.25, rope_theta=1e7, rms_norm_eps=1e-6,
           linear_num_key_heads=2, linear_num_value_heads=4,
           linear_key_head_dim=16, linear_value_head_dim=16,
           linear_conv_kernel_dim=4, num_experts=16, num_experts_per_tok=4,
           moe_intermediate_size=32, shared_expert_intermediate_size=32,
           norm_topk_prob=True, vocab_size=97)
HELD = (8, 16)          # the second of two shares of 8 + 8 experts


@pytest.fixture(scope="module")
def ref():
    spec = importlib.util.spec_from_file_location(
        "reference_qwen3_next",
        os.path.join(REPO, "benchmark", "reference", "qwen3_next.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def one_chip():
    return grid_mesh((1, 1), (DATA_AXIS, PIPE_AXIS))


def hybrid_trainer(held=HELD, mesh=None, **kw):
    kw = {"n_microbatches": 1, "lr": 1e-3, "seed": 3, "attention": "dense",
          "remat": True, **kw}
    return PipelinedLMTrainer(model=qwen3_next_spec(CFG, held),
                              mesh=mesh or one_chip(), **kw)


def tokens(batch=2, seq=100, seed=0):
    return np.random.default_rng(seed).integers(
        0, CFG["vocab_size"], (batch, seq)).astype(np.int32)


@pytest.fixture(scope="module")
def whole_model(ref):
    """System and reference loss and gradients of the whole model (2
    periods, 8 layers) on one batch; a length that is no multiple of the
    recurrence's chunk."""
    trainer = hybrid_trainer()
    tok = tokens()
    weights = jax.tree_util.tree_map(np.asarray, trainer.params)
    ref_loss, ref_grads = ref.loss_and_grads(weights, tok, CFG, HELD)
    with jax.default_matmul_precision("highest"):
        sys_loss, sys_grads = trainer.loss_and_grads(tok)
    paths = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_leaves_with_path(ref_grads)]
    return (sys_loss, ref_loss, dict(zip(paths, zip(
        jax.tree_util.tree_leaves(sys_grads),
        jax.tree_util.tree_leaves(ref_grads)))))


def test_loss_matches_the_reference(whole_model):
    sys_loss, ref_loss, _ = whole_model
    assert abs(sys_loss - ref_loss) < 1e-5


# one case a kind of leaf, so each counts: the whole tree is compared
LEAF_KINDS = ["embed", "head", "final_norm", "norm_in", "norm_post",
              "in_proj_qkvz", "in_proj_ba", "conv", "A_log", "dt_bias",
              "['norm']", "out_proj", "q_proj", "k_proj", "v_proj", "q_norm",
              "k_norm", "o_proj", "router", "w_gate", "w_up", "w_down",
              "shared_gate", "shared_up", "shared_down",
              "shared_expert_gate"]


@pytest.mark.parametrize("kind", LEAF_KINDS)
def test_gradients_match_the_reference(whole_model, kind):
    _, _, leaves = whole_model
    mine = {p: v for p, v in leaves.items() if kind in p}
    assert mine, kind
    for path, (got, want) in mine.items():
        err = float(jnp.linalg.norm(got - want)
                    / (jnp.linalg.norm(want) + 1e-30))
        assert err < 2e-5, (path, err)


def test_every_leaf_is_covered(whole_model):
    _, _, leaves = whole_model
    assert all(any(k in p for k in LEAF_KINDS) for p in leaves)


@pytest.mark.parametrize("seq,chunk", [(150, 64), (64, 64), (37, 16),
                                       (130, 128)])
def test_chunked_recurrence_matches_the_positional_one(ref, seq, chunk):
    from mmlspark_tpu.ops.gated_delta import chunk_gated_delta_rule
    heads, dk, dv = 3, 16, 24
    ks = jax.random.split(jax.random.PRNGKey(seq), 5)

    def l2(t):
        return t / jnp.sqrt((t * t).sum(-1, keepdims=True) + 1e-6)

    args = (l2(jax.random.normal(ks[0], (seq, heads, dk))) * dk ** -0.5,
            l2(jax.random.normal(ks[1], (seq, heads, dk))),
            jax.random.normal(ks[2], (seq, heads, dv)),
            -jax.nn.softplus(jax.random.normal(ks[3], (seq, heads))) * 0.3,
            jax.nn.sigmoid(jax.random.normal(ks[4], (seq, heads))))
    with jax.default_matmul_precision("highest"):
        want = ref.delta_rule(*args)
        got = chunk_gated_delta_rule(*args, chunk=chunk)
        assert float(jnp.abs(got - want).max()) < 2e-6
        g_got = jax.grad(lambda *a: (chunk_gated_delta_rule(
            *a, chunk=chunk) ** 2).sum(), argnums=(0, 1, 2, 3, 4))(*args)
        g_want = jax.grad(lambda *a: (ref.delta_rule(*a) ** 2).sum(),
                          argnums=(0, 1, 2, 3, 4))(*args)
    for a, b in zip(g_got, g_want):
        assert float(jnp.abs(a - b).max() / jnp.abs(b).max()) < 2e-5


def _moe_params(rng, d=64, n_all=16, held=(0, 16), width=32):
    n = held[1] - held[0]

    def w(*shape):
        return (rng.standard_normal(shape) * 0.1).astype(np.float32)

    full = {"router": w(d, n_all), "w_gate": w(n_all, d, width),
            "w_up": w(n_all, d, width), "w_down": w(n_all, width, d),
            "shared_gate": w(d, width), "shared_up": w(d, width),
            "shared_down": w(width, d), "shared_expert_gate": w(d, 1)}
    part = dict(full, **{k: full[k][held[0]:held[1]]
                         for k in ("w_gate", "w_up", "w_down")})
    assert part["w_gate"].shape[0] == n
    return full, part


def test_shares_add_up_to_the_uncut_layer(ref):
    """The guide's share test: the routed parts of both shares (8 + 8
    experts) plus the shared expert ONCE equal the uncut reference layer.
    1,500 tokens: about 375 pairs an expert, so a run spans two tiles."""
    from mmlspark_tpu.models.dnn.moe import moe_layer
    rng = np.random.default_rng(1)
    n = 1500
    x = rng.standard_normal((n, 64)).astype(np.float32)
    cfg = dict(CFG)
    with jax.default_matmul_precision("highest"):
        full, _ = _moe_params(rng)
        whole = ref.moe(x, full, cfg, (0, 16))
        shared = (jax.nn.silu(x @ full["shared_gate"])
                  * (x @ full["shared_up"])) @ full["shared_down"]
        shared = shared * jax.nn.sigmoid(x @ full["shared_expert_gate"])
        total, held_pairs = shared, 0
        for held in ((0, 8), (8, 16)):
            part = dict(full, **{k: full[k][held[0]:held[1]]
                                 for k in ("w_gate", "w_up", "w_down")})
            y, stats = moe_layer(x, part, 4, held)
            assert float(jnp.abs(y - ref.moe(x, part, cfg, held)).max()) \
                < 1e-5
            total = total + (y - shared)
            held_pairs += float(stats[1])
            assert float(stats[0]) == n * 4
    assert held_pairs == n * 4           # every pair is held by one share
    assert float(jnp.abs(total - whole).max()) < 1e-5


@pytest.mark.parametrize("n", [50, 256, 300, 600])
def test_skewed_routing_drops_nothing(ref, n):
    """Every token to one held expert (and its other picks to absent ones):
    all N pairs are computed, however the run falls into tiles of 256 (a
    part of one, exactly one, one and a part, two and a part)."""
    from mmlspark_tpu.models.dnn.moe import moe_layer
    rng = np.random.default_rng(2)
    full, part = _moe_params(rng, held=(4, 8))
    x = np.abs(rng.standard_normal((n, 64))).astype(np.float32)
    router = np.zeros((64, 16), np.float32)
    router[:, 5] = 1.0                       # held
    router[:, [0, 1, 2]] = 0.5               # absent
    router[:, 6] = -1.0                      # held, never picked
    part = dict(part, router=router)
    with jax.default_matmul_precision("highest"):
        y, stats = moe_layer(x, part, 4, (4, 8))
        want = ref.moe(x, part, dict(CFG), (4, 8))
        grads = jax.grad(lambda p: (moe_layer(x, p, 4, (4, 8))[0]
                                    ** 2).sum())(part)
        g_want = jax.grad(lambda p: (ref.moe(x, p, dict(CFG), (4, 8))
                                     ** 2).sum())(part)
    assert float(stats[1]) == n and float(stats[0]) == 4 * n
    assert float(stats[2]) == 4.0            # n pairs on one of 4 experts
    assert float(jnp.abs(y - want).max()) < 1e-5
    for k in ("w_gate", "w_up", "w_down", "router"):
        scale = float(jnp.abs(g_want[k]).max())
        assert float(jnp.abs(grads[k] - g_want[k]).max()) < 1e-4 * scale, k
    assert float(jnp.abs(grads["w_gate"][0]).max()) == 0.0   # expert 4 idle


@pytest.mark.parametrize("dtype,limit", [(jnp.float32, 2e-5),
                                         (jnp.bfloat16, 2e-2)])
def test_flash_head_dim_256_with_repeated_kv(dtype, limit):
    """The full-attention mixer's kernel call: head size 256, 2 KV heads
    repeated for 8 query heads, against `reference_attention`."""
    from mmlspark_tpu.ops.flash_attention import flash_attention
    from mmlspark_tpu.parallel.ring_attention import reference_attention
    seq, heads, kv, d = 300, 8, 2, 256
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (seq, heads, d)).astype(dtype)
    k = jnp.repeat(jax.random.normal(ks[1], (seq, kv, d)), heads // kv,
                   axis=1).astype(dtype)
    v = jnp.repeat(jax.random.normal(ks[2], (seq, kv, d)), heads // kv,
                   axis=1).astype(dtype)
    with jax.default_matmul_precision("highest"):
        want = reference_attention(q.astype(jnp.float32),
                                   k.astype(jnp.float32),
                                   v.astype(jnp.float32), causal=True)
        got = flash_attention(q, k, v, causal=True)
    assert float(jnp.abs(got.astype(jnp.float32) - want).max()) < limit


def test_wide_heads_cap_the_backward_blocks():
    from mmlspark_tpu.ops.flash_attention import _auto_blocks
    assert _auto_blocks(8192, 8192, jnp.bfloat16, 256) == (1024, 1024, 512,
                                                           512)
    assert _auto_blocks(8192, 8192, jnp.bfloat16, 128)[2:] == (1024, 1024)
    assert _auto_blocks(8192, 8192, jnp.bfloat16, 64)[2:] == (1024, 1024)


def test_flash_and_dense_mixers_agree():
    tok = tokens(seq=64)
    dense = hybrid_trainer(attention="dense").loss_and_grads(tok)[0]
    flash = hybrid_trainer(attention="flash").loss_and_grads(tok)[0]
    assert abs(dense - flash) < 1e-4


@pytest.mark.parametrize("kw", [
    dict(attention="dense", compute_dtype="float32", remat=False),
    dict(attention="flash", compute_dtype="bfloat16", remat="save_attn"),
])
def test_gpt2_description_gives_the_old_arguments_loss(kw):
    """`gpt2-medium`'s shape of call: the six integers and the description
    built from them train the same program, bit for bit."""
    sizes = dict(vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
                 max_len=64)
    common = dict(mesh=one_chip(), n_microbatches=1, seed=5, **kw)
    old = PipelinedLMTrainer(**sizes, **common)
    new = PipelinedLMTrainer(model=gpt2_spec(**sizes), **common)
    tok = np.random.default_rng(3).integers(0, 64, (2, 32)).astype(np.int32)
    assert [old.step(tok) for _ in range(3)] == \
        [new.step(tok) for _ in range(3)]
    assert old.meta == new.meta == {"n_heads": 4, "d_model": 32}
    assert old._step.lower(old.params, old.opt_state,
                           old._to_device(tok)).as_text() == \
        new._step.lower(new.params, new.opt_state,
                        new._to_device(tok)).as_text()


def test_step_returns_the_counts_with_the_loss():
    from mmlspark_tpu.reliability.metrics import reliability_metrics as rm
    from mmlspark_tpu.telemetry import names as tnames
    trainer = hybrid_trainer()
    tok = tokens()
    routed, held = (rm.get(tnames.MOE_PAIRS_ROUTED),
                    rm.get(tnames.MOE_PAIRS_HELD))
    first = trainer.step(tok)
    second = trainer.step(tok)
    assert np.isfinite(first) and second < first
    # 2 steps x 8 expert layers x 200 tokens x 4 experts a token
    assert rm.get(tnames.MOE_PAIRS_ROUTED) - routed == 2 * 8 * 200 * 4
    share = (rm.get(tnames.MOE_PAIRS_HELD) - held) / (2 * 8 * 200 * 4)
    assert 0.3 < share < 0.7                 # 8 of 16 experts held
    assert rm.gauge(tnames.MOE_LOAD_MAX_OVER_MEAN) >= 1.0
    assert np.isfinite(trainer.run(tok, 2))


def test_two_pipe_stages_match_one():
    """2 periods over 2 pipe stages and 2 microbatches: the loss of the
    one-stage program, and the same counts."""
    tok = tokens(batch=2, seq=48)
    one = hybrid_trainer().loss_and_grads(tok)[0]
    two = hybrid_trainer(mesh=grid_mesh((1, 2), (DATA_AXIS, PIPE_AXIS)),
                         n_microbatches=2).loss_and_grads(tok)[0]
    assert abs(one - two) < 1e-5


def test_checkpoint_roundtrip_of_the_hybrid_tree(tmp_path):
    tok = tokens(seq=48)
    a = hybrid_trainer()
    a.step(tok)
    a.save_checkpoint(str(tmp_path), 1)
    want = a.step(tok)
    b = hybrid_trainer(seed=9)
    assert b.restore_checkpoint(str(tmp_path)) == 1
    assert b.step(tok) == want
    other = PipelinedLMTrainer(model=qwen3_next_spec(CFG, (0, 8)),
                               mesh=one_chip(), n_microbatches=1)
    with pytest.raises(ValueError, match="model config"):
        other.restore_checkpoint(str(tmp_path))


@pytest.mark.parametrize("bad,match", [
    (dict(period=("dense", "gdn")), "does not mix"),
    (dict(period=("window",)), "layer kinds"),
    (dict(period=("gdn",)), "needs its"),
    (dict(period=("dense",), n_periods=0), "n_periods"),
])
def test_description_is_validated(bad, match):
    base = dict(vocab_size=8, d_model=8, period=("dense",), n_periods=1)
    with pytest.raises(ValueError, match=match):
        LMSpec(**{**base, **bad})


def test_experts_held_must_be_a_range_of_the_router():
    good = qwen3_next_spec(CFG, (0, 8))
    with pytest.raises(ValueError, match="no range"):
        LMSpec(**{**good.__dict__, "experts": Experts(16, 4, 32, 32,
                                                      (8, 17))})


def test_hybrid_periods_divide_by_the_pipe_axis():
    # the model and seq axes' refusal: tests/test_lm_families.py
    with pytest.raises(ValueError, match="n_periods"):
        hybrid_trainer(mesh=grid_mesh((1, 4), (DATA_AXIS, PIPE_AXIS)))


def test_step_program_names_every_region():
    """The compiled step carries every region a reader of the new cell's
    capture names (a named region that matches no event fails a run)."""
    from mmlspark_tpu.telemetry import names as tnames
    from mmlspark_tpu.telemetry import perf
    trainer = hybrid_trainer(attention="flash", compute_dtype="bfloat16")
    tok = tokens(seq=64)
    text = trainer._step.lower(
        trainer.params, trainer.opt_state,
        trainer._to_device(tok)).compile().as_text()
    scopes = perf.scope_map(text)
    counts = perf.region_instruction_counts(scopes)
    for region in (tnames.LM_EMBED, tnames.LM_CAST, tnames.LM_HEAD,
                   tnames.LM_OPT, tnames.LM_ATTN, tnames.LM_ATTN_FLASH,
                   tnames.LM_GDN, tnames.LM_GDN_SCAN, tnames.LM_MOE_ROUTER,
                   tnames.LM_MOE_DISPATCH, tnames.LM_MOE_EXPERTS,
                   tnames.LM_MOE_SHARED):
        assert counts.get(region, 0) > 0, region
    assert tnames.LM_MLP not in counts
    assert {way for _, way in scopes.values()} == {"fwd", "bwd", "remat"}
