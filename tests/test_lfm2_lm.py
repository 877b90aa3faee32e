"""The short-convolution decoder (gated short convolutions, grouped-KV
attention, a leading dense layer, sigmoid-routed experts held as one chip's
share, a chunked head tied to the embedding) through `PipelinedLMTrainer`,
against the benchmark's plain float32 reference
(`benchmark/reference/lfm2_moe.py`) at tiny widths on the CPU."""
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mmlspark_tpu.models.dnn import moe, shortconv_layers
from mmlspark_tpu.models.dnn.lm_spec import (Experts, GatedAttention, LMSpec,
                                             ShortConv, lfm2_moe_spec)
from mmlspark_tpu.models.dnn.pp_training import PipelinedLMTrainer
from mmlspark_tpu.parallel import DATA_AXIS, PIPE_AXIS, grid_mesh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# the published order at toy widths: two leading layers published, the
# second held; then two whole periods
CFG = dict(hidden_size=64, intermediate_size=96, conv_L_cache=3,
           norm_eps=1e-5, num_attention_heads=4, num_key_value_heads=2,
           layer_types=["conv", "conv"]
           + ["full_attention", "conv", "conv", "conv"] * 2,
           num_dense_layers=1, published={"num_dense_layers": 2},
           num_layers=9, rope_parameters={"rope_theta": 1e6},
           num_experts=16, num_experts_per_tok=4, moe_intermediate_size=32,
           norm_topk_prob=True, use_expert_bias=True,
           routed_scaling_factor=1, vocab_size=97)
HELD = (8, 16)          # the second of two shares of 8 + 8 experts


@pytest.fixture(scope="module")
def ref():
    spec = importlib.util.spec_from_file_location(
        "reference_lfm2_moe",
        os.path.join(REPO, "benchmark", "reference", "lfm2_moe.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def mesh_of(pipe):
    return grid_mesh((1, pipe), (DATA_AXIS, PIPE_AXIS))


def lfm2_trainer(held=HELD, pipe=1, **kw):
    kw = {"n_microbatches": pipe, "lr": 1e-3, "seed": 3,
          "attention": "dense", "remat": True, **kw}
    return PipelinedLMTrainer(model=lfm2_moe_spec(CFG, held),
                              mesh=mesh_of(pipe), **kw)


def tokens(batch=2, seq=100, seed=0):
    return np.random.default_rng(seed).integers(
        0, CFG["vocab_size"], (batch, seq)).astype(np.int32)


def rel(got, want):
    return float(jnp.linalg.norm(got - want)
                 / (jnp.linalg.norm(want) + 1e-30))


def test_the_published_order_as_a_description():
    spec = lfm2_moe_spec(CFG, HELD)
    assert spec.leading == ("conv",) and spec.leading_ffn == ("dense",)
    assert spec.period == ("full_attention", "conv", "conv", "conv")
    assert spec.period_ffn == ("experts",) * 4 and spec.n_periods == 2
    assert spec.attention.head_dim == spec.attention.rotary_dim == 16
    assert spec.experts.scoring == "sigmoid_bias" \
        and spec.experts.shared_width == 0
    assert spec.family.__name__.endswith("shortconv_layers")
    # the published 40 layers: both leading layers, then 38 layers whose
    # last period is cut short, so they repeat only as one period of 38
    whole = lfm2_moe_spec({**CFG, "layer_types": ["conv", "conv"]
                           + ["full_attention", "conv", "conv", "conv"] * 9
                           + ["full_attention", "conv"],
                           "num_dense_layers": 2, "num_layers": 40}, HELD)
    assert whole.leading == ("conv", "conv")
    assert len(whole.period) == 38 and whole.n_periods == 1


@pytest.fixture(scope="module", params=[1, 2], ids=["pipe1", "pipe2"])
def whole_model(ref, request):
    """System and reference loss and gradients of the whole model (a
    leading layer and 2 periods, 9 layers) on one batch, on one pipe stage
    and on two (a period a stage, the leading layer on the first)."""
    trainer = lfm2_trainer(pipe=request.param)
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
LEAF_KINDS = ["['embed']", "final_norm", "operator_norm", "ffn_norm",
              "in_proj", "taps", "out_proj", "q_proj", "k_proj", "v_proj",
              "q_layernorm", "k_layernorm", "o_proj", "['w1']", "['w3']",
              "['w2']", "router", "w_gate", "w_up", "w_down"]


@pytest.mark.parametrize("kind", LEAF_KINDS)
def test_gradients_match_the_reference(whole_model, kind):
    _, _, leaves = whole_model
    mine = {p: v for p, v in leaves.items() if kind in p}
    assert mine, kind
    for path, (got, want) in mine.items():
        assert rel(got, want) < 2e-5, path


def test_every_leaf_is_covered_and_the_bias_gets_no_gradient(whole_model):
    _, _, leaves = whole_model
    biases = {p: v for p, v in leaves.items() if "expert_bias" in p}
    assert len(biases) == 4
    for got, want in biases.values():
        assert not np.asarray(got).any() and not np.asarray(want).any()
    assert all(any(k in p for k in LEAF_KINDS + ["expert_bias"])
               for p in leaves)
    assert sum("['leading']" in p for p in leaves) == 8


def _conv_params(rng, d=64):
    return {"in_proj": rng.standard_normal((d, 3 * d)).astype(np.float32)
            * 0.1, "taps": rng.standard_normal((3, d)).astype(np.float32),
            "out_proj": rng.standard_normal((d, d)).astype(np.float32) * 0.1}


def test_conv_mixer_matches_the_reference(ref):
    rng = np.random.default_rng(4)
    p = _conv_params(rng)
    x = rng.standard_normal((2, 37, 64)).astype(np.float32)
    with jax.default_matmul_precision("highest"):
        got = shortconv_layers.conv_mixer(x, p)
        want = jnp.stack([ref.conv_mixer(seq, p, CFG) for seq in x])
    assert float(jnp.abs(got - want).max()) < 1e-5


@pytest.mark.parametrize("t", [0, 1, 17, 36])
def test_conv_taps_are_causal(t):
    """An input after position t does not move output t, and the inputs at
    t, t - 1 and t - 2 do (three taps)."""
    rng = np.random.default_rng(5)
    p = _conv_params(rng)
    x = rng.standard_normal((1, 37, 64)).astype(np.float32)
    moved = x.copy()
    moved[:, t + 1:] += 1.0
    base = shortconv_layers.conv_mixer(x, p)
    assert np.array_equal(np.asarray(base[:, :t + 1]), np.asarray(
        shortconv_layers.conv_mixer(moved, p)[:, :t + 1]))
    reach = jax.jacobian(lambda x: shortconv_layers.conv_mixer(
        x, p)[0, t].sum())(x)[0]
    seen = np.flatnonzero(np.abs(np.asarray(reach)).sum(-1))
    assert list(seen) == list(range(max(t - 2, 0), t + 1))


@pytest.mark.parametrize("attention", ["dense", "flash"])
def test_attention_mixer_matches_the_reference(ref, attention):
    rng = np.random.default_rng(6)
    d, h, kv, hd = 64, 4, 2, 16

    def w(*shape):
        return rng.standard_normal(shape).astype(np.float32) * 0.2

    p = {"q_proj": w(d, h * hd), "k_proj": w(d, kv * hd),
         "v_proj": w(d, kv * hd), "o_proj": w(h * hd, d),
         "q_layernorm": 1 + w(hd), "k_layernorm": 1 + w(hd)}
    x = rng.standard_normal((2, 70, d)).astype(np.float32)
    a = GatedAttention(h, kv, hd, 1e6, hd)
    with jax.default_matmul_precision("highest"):
        got = shortconv_layers.attention_mixer(x, p, a, 1e-5, attention)
        want = jnp.stack([ref.attention_mixer(seq, p, CFG) for seq in x])
    assert float(jnp.abs(got - want).max()) < 2e-5


@pytest.mark.parametrize("dtype,limit", [(jnp.float32, 2e-5),
                                         (jnp.bfloat16, 2e-2)])
def test_flash_head_dim_64_with_kv_repeated_four_times(dtype, limit):
    """The attention mixer's kernel call at the published head geometry:
    head size 64, 2 KV heads repeated for 8 query heads."""
    from mmlspark_tpu.ops.flash_attention import flash_attention
    from mmlspark_tpu.parallel.ring_attention import reference_attention
    seq, heads, kv, d = 300, 8, 2, 64
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


def _moe_params(rng, d=64, n_all=16, width=32, bias=0.05):
    def w(*shape):
        return (rng.standard_normal(shape) * 0.1).astype(np.float32)
    return {"router": w(d, n_all), "w_gate": w(n_all, d, width),
            "w_up": w(n_all, d, width), "w_down": w(n_all, width, d),
            "expert_bias": (rng.standard_normal(n_all) * bias
                            ).astype(np.float32)}


def _share(full, held):
    return dict(full, **{k: full[k][held[0]:held[1]]
                         for k in ("w_gate", "w_up", "w_down")})


def test_shares_add_up_to_the_uncut_layer(ref):
    """The guide's share test: the routed parts of all eight shares (8 of
    64 experts each; nothing is computed by every chip alike, the layer has
    no shared expert) equal the uncut reference layer. 4,500 tokens: about
    280 pairs an expert, so a run spans two tiles."""
    rng = np.random.default_rng(1)
    n, n_all = 4500, 64
    x = rng.standard_normal((n, 64)).astype(np.float32)
    cfg = dict(CFG, num_experts=n_all)
    full = _moe_params(rng, n_all=n_all)
    with jax.default_matmul_precision("highest"):
        whole = ref.moe(x, full, cfg, (0, n_all))
        total, held_pairs = 0.0, 0
        for lo in range(0, n_all, 8):
            held = (lo, lo + 8)
            y, stats = moe.moe_layer(x, _share(full, held), 4, held,
                                     scoring="sigmoid_bias")
            assert float(jnp.abs(
                y - ref.moe(x, _share(full, held), cfg, held)).max()) < 1e-5
            total = total + y
            held_pairs += float(stats[1])
            assert float(stats[0]) == n * 4
    assert held_pairs == n * 4           # every pair is held by one share
    assert float(jnp.abs(total - whole).max()) < 1e-5


def test_expert_layer_gradients_match_the_reference(ref):
    rng = np.random.default_rng(2)
    part = _share(_moe_params(rng), (4, 12))
    x = rng.standard_normal((700, 64)).astype(np.float32)
    cfg = dict(CFG)
    with jax.default_matmul_precision("highest"):
        grads = jax.grad(lambda p, x: (moe.moe_layer(
            x, p, 4, (4, 12), scoring="sigmoid_bias")[0] ** 2).sum(),
            argnums=(0, 1))(part, x)
        wants = jax.grad(lambda p, x: (ref.moe(x, p, cfg, (4, 12))
                                       ** 2).sum(), argnums=(0, 1))(part, x)
    for k in ("w_gate", "w_up", "w_down", "router"):
        assert rel(grads[0][k], wants[0][k]) < 1e-4, k
    assert rel(grads[1], wants[1]) < 1e-4
    assert not np.asarray(grads[0]["expert_bias"]).any()


def test_selection_bias_changes_the_chosen_and_not_the_weights_formula():
    """A bias that lifts one expert above all others puts it among every
    token's four; its weight is still its UNBIASED score over the chosen
    scores' sum (+ 1e-6), and the unbiased router never picked it for
    most tokens."""
    rng = np.random.default_rng(3)
    x = rng.standard_normal((200, 64)).astype(np.float32)
    p = _moe_params(rng, bias=0.0)
    bias = np.zeros(16, np.float32)
    bias[5] = 10.0
    plain_idx, plain_w = moe.route(x, p["router"], 4, scoring="sigmoid")
    idx, w = moe.route(x, p["router"], 4, scoring="sigmoid", bias=bias)
    assert (np.asarray(idx) == 5).any(-1).all()
    assert (np.asarray(plain_idx) == 5).any(-1).mean() < 0.5
    scores = np.asarray(jax.nn.sigmoid(x @ p["router"]))
    chosen = np.take_along_axis(scores, np.asarray(idx), axis=-1)
    want = chosen / (chosen.sum(-1, keepdims=True) + 1e-6)
    assert np.abs(np.asarray(w) - want).max() < 1e-6
    assert np.asarray(w).max() < 1.0 and np.abs(
        np.asarray(plain_w).sum(-1) - 1).max() < 1e-5
    # `scale` multiplies the weights and moves no choice
    idx2, w2 = moe.route(x, p["router"], 4, scoring="sigmoid", bias=bias,
                         scale=2.5)
    assert np.array_equal(idx2, idx) and np.allclose(w2, 2.5 * w)


def _parent_route(x, w_router, top_k, renormalize=True):
    """`moe.route` as it was before it knew a second scoring rule."""
    logits = jnp.einsum("nd,de->ne", x, w_router,
                        preferred_element_type=jnp.float32)
    top, idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
    if renormalize:
        top = top / top.sum(-1, keepdims=True)
    return idx.astype(jnp.int32), top


@pytest.mark.parametrize("renormalize", [True, False])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_softmax_route_is_the_parents_bit_for_bit(renormalize, dtype):
    rng = np.random.default_rng(8)
    x = jnp.asarray(rng.standard_normal((300, 64)), dtype)
    w = jnp.asarray(rng.standard_normal((64, 16)) * 0.3, dtype)
    got = jax.jit(lambda x, w: moe.route(x, w, 4, renormalize))(x, w)
    want = jax.jit(lambda x, w: _parent_route(x, w, 4, renormalize))(x, w)
    assert np.array_equal(got[0], want[0])
    assert np.array_equal(got[1], want[1])


def _plain_route(x, w_router, top_k, scoring, bias):
    """`moe.route`'s weights by `lax.top_k` and `take_along_axis`, for
    reverse-mode as JAX writes it."""
    logits = jnp.einsum("nd,de->ne", x, w_router,
                        preferred_element_type=jnp.float32)
    if scoring == "softmax":
        top, _ = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
        return top / top.sum(-1, keepdims=True)
    scores = jax.nn.sigmoid(logits)
    _, idx = jax.lax.top_k(scores + bias, top_k)
    top = jnp.take_along_axis(scores, idx, axis=-1)
    return top / (top.sum(-1, keepdims=True) + 1e-6)


@pytest.mark.parametrize("scoring", ["softmax", "sigmoid_bias"])
def test_the_routers_own_backward_is_reverse_modes(scoring):
    """`moe._choose` writes its backward by hand so that it reads named
    residuals; the gradients are those of the plain expression."""
    rng = np.random.default_rng(9)
    x = jnp.asarray(rng.standard_normal((300, 64)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((64, 16)) * 0.3, jnp.float32)
    bias = jnp.asarray(rng.standard_normal(16) * 0.05, jnp.float32)
    g = jnp.asarray(rng.standard_normal((300, 4)), jnp.float32)
    got = jax.grad(lambda x, w, b: (moe.route(
        x, w, 4, True, scoring, b if scoring != "softmax" else None)[1]
        * g).sum(), argnums=(0, 1, 2))(x, w, bias)
    want = jax.grad(lambda x, w: (_plain_route(x, w, 4, scoring, bias)
                                  * g).sum(), argnums=(0, 1))(x, w)
    assert rel(got[0], want[0]) < 1e-5 and rel(got[1], want[1]) < 1e-5
    assert not np.asarray(got[2]).any()      # the selection bias: none


def test_the_tied_heads_gradient_is_the_sum_of_both_uses(ref):
    """`embed` is looked up and is the head: its gradient through the
    trainer is the reference's gradient of the lookup alone plus that of
    the head alone."""
    trainer = lfm2_trainer()
    tok = tokens(seq=48)
    weights = jax.tree_util.tree_map(np.asarray, trainer.params)
    with jax.default_matmul_precision("highest"):
        _, grads = trainer.loss_and_grads(tok)

    # split the two uses by hand: the reference with a SEPARATE head table
    def split_loss(lookup, head, seq):
        leading, kinds = ref.layers_held(CFG)
        x = lookup[seq]
        for kind, lp in zip(leading, weights["leading"]):
            x = ref.feed(ref.mix(x, lp, kind, CFG), lp, CFG, HELD)
        for period in range(2):
            for kind, lps in zip(kinds, weights["layers"]):
                lp = jax.tree_util.tree_map(lambda a: a[period], lps)
                x = ref.feed(ref.mix(x, lp, kind, CFG), lp, CFG, HELD)
        x = ref.rms_norm(x, weights["final_norm"], CFG["norm_eps"])
        logp = jax.nn.log_softmax(x @ head.T, axis=-1)
        return -jnp.take_along_axis(logp[:-1], seq[1:, None], axis=-1).sum()

    with jax.default_matmul_precision("highest"):
        table = jnp.asarray(weights["embed"])
        g_lookup = g_head = 0.0
        for seq in jnp.asarray(tok):
            a, b = jax.grad(split_loss, argnums=(0, 1))(table, table, seq)
            g_lookup, g_head = g_lookup + a, g_head + b
    count = tok.shape[0] * (tok.shape[1] - 1)
    assert float(jnp.linalg.norm(g_lookup)) > 0 \
        and float(jnp.linalg.norm(g_head)) > 0
    assert rel(grads["embed"], (g_lookup + g_head) / count) < 2e-5
    assert rel(grads["embed"], g_head / count) > 1e-3


def test_flash_and_dense_mixers_agree():
    tok = tokens(seq=64)
    dense = lfm2_trainer(attention="dense").loss_and_grads(tok)[0]
    flash = lfm2_trainer(attention="flash").loss_and_grads(tok)[0]
    assert abs(dense - flash) < 1e-4


def test_steps_count_the_pairs_and_leave_the_bias_where_it_was():
    from mmlspark_tpu.reliability.metrics import reliability_metrics as rm
    from mmlspark_tpu.telemetry import names as tnames
    trainer = lfm2_trainer()
    tok = tokens()

    def biases():
        return [np.asarray(lp["moe"]["expert_bias"])
                for lp in trainer.params["layers"]]

    before = biases()
    assert all(b.any() for b in before)
    routed, held = (rm.get(tnames.MOE_PAIRS_ROUTED),
                    rm.get(tnames.MOE_PAIRS_HELD))
    first = trainer.step(tok)
    second = trainer.step(tok)
    assert np.isfinite(first) and second < first
    # 2 steps x 8 expert layers (the leading layer has none) x 200 tokens
    # x 4 experts a token
    assert rm.get(tnames.MOE_PAIRS_ROUTED) - routed == 2 * 8 * 200 * 4
    share = (rm.get(tnames.MOE_PAIRS_HELD) - held) / (2 * 8 * 200 * 4)
    assert 0.3 < share < 0.7                 # 8 of 16 experts held
    assert rm.gauge(tnames.MOE_LOAD_MAX_OVER_MEAN) >= 1.0
    assert np.isfinite(trainer.run(tok, 2))
    # Adam moved every trained leaf and not the selection bias, bit for bit
    assert all(np.array_equal(a, b) for a, b in zip(before, biases()))
    assert not any(np.asarray(m).any() for m in jax.tree_util.tree_leaves(
        [lp["moe"]["expert_bias"] for lp in trainer.opt_state[0].nu["layers"]]))


def test_the_leading_layer_lives_on_the_first_stage_unstacked():
    """On two pipe stages the leading layer's leaves are replicated
    entries (no period axis), each stage holds one period, and the loss is
    the one-stage program's."""
    tok = tokens(batch=2, seq=48)
    one, two = lfm2_trainer(), lfm2_trainer(pipe=2)
    w1 = two.params["leading"][0]["mlp"]["w1"]
    assert w1.shape == (64, 96) and w1.sharding.is_fully_replicated
    stacked = two.params["layers"][1]["mixer"]["in_proj"]
    assert stacked.shape == (2, 64, 192)
    assert {s.data.shape[0] for s in stacked.addressable_shards} == {1}
    assert abs(one.loss_and_grads(tok)[0] - two.loss_and_grads(tok)[0]) \
        < 1e-5


def test_checkpoint_roundtrip_of_the_tree_with_leading_layers(tmp_path):
    tok = tokens(seq=48)
    a = lfm2_trainer()
    a.step(tok)
    a.save_checkpoint(str(tmp_path), 1)
    want = a.step(tok)
    b = lfm2_trainer(seed=9)
    assert b.restore_checkpoint(str(tmp_path)) == 1
    assert b.step(tok) == want
    other = lfm2_trainer(held=(0, 8))
    with pytest.raises(ValueError, match="model config"):
        other.restore_checkpoint(str(tmp_path))


BASE = dict(vocab_size=8, d_model=8, period=("conv",), n_periods=1,
            period_ffn=("dense",), d_ff=8, short_conv=ShortConv())


@pytest.mark.parametrize("bad,match", [
    (dict(period_ffn=()), "give each layer"),
    (dict(period_ffn=("gelu",)), "give each layer"),
    (dict(short_conv=None), "needs its .*short_conv"),
    (dict(d_ff=0), "needs its .*d_ff"),
    (dict(period_ffn=("experts",)), "needs its .*experts"),
    (dict(period=("full_attention",)), "needs its .*attention"),
    (dict(leading=("conv",)), "give each layer"),
    (dict(leading=("full_attention",), leading_ffn=("dense",),
          attention=GatedAttention(2, 1, 4, 1e4, 4)), "leading layer is"),
    (dict(leading=("conv",), leading_ffn=("experts",),
          experts=Experts(4, 2, 8, 0, (0, 4), scoring="sigmoid_bias")),
     "leading layer is"),
    (dict(period_ffn=("experts",),
          experts=Experts(4, 2, 8, 8, (0, 4))), "no shared expert"),
    (dict(period_ffn=("experts",),
          experts=Experts(4, 2, 8, 0, (2, 5))), "no range"),
    (dict(period=("conv", "gdn"), period_ffn=("dense",) * 2),
     "does not mix"),
    (dict(leading=("dense",), leading_ffn=("dense",)), "does not mix"),
])
def test_description_is_validated(bad, match):
    with pytest.raises(ValueError, match=match):
        LMSpec(**{**BASE, **bad})


@pytest.mark.parametrize("kind", ["dense", "gdn"])
def test_other_families_refuse_leading_layers(kind):
    from mmlspark_tpu.models.dnn.lm_spec import GatedDeltaNet, gpt2_spec
    good = gpt2_spec(16, 8, 2, 1, 16, 8) if kind == "dense" else LMSpec(
        vocab_size=16, d_model=16, period=("gdn",), n_periods=1,
        delta_net=GatedDeltaNet(1, 2, 8, 8),
        experts=Experts(4, 2, 8, 8, (0, 4)))
    with pytest.raises(ValueError, match="no leading layers"):
        LMSpec(**{**good.__dict__, "leading": (good.period[0],)})


def test_step_program_names_every_region():
    """The compiled step carries every region a reader of the new cell's
    capture names (a named region that matches no event fails a run), the
    leading layer's inside the embedding's."""
    from mmlspark_tpu.telemetry import names as tnames
    from mmlspark_tpu.telemetry import perf
    trainer = lfm2_trainer(attention="flash", compute_dtype="bfloat16")
    tok = tokens(seq=64)
    text = trainer._step.lower(
        trainer.params, trainer.opt_state,
        trainer._to_device(tok)).compile().as_text()
    scopes = perf.scope_map(text)
    counts = perf.region_instruction_counts(scopes)
    for region in (tnames.LM_EMBED, tnames.LM_CAST, tnames.LM_HEAD,
                   tnames.LM_OPT, tnames.LM_ATTN, tnames.LM_ATTN_FLASH,
                   tnames.LM_CONV, tnames.LM_CONV_GATE, tnames.LM_MLP,
                   tnames.LM_MOE_ROUTER, tnames.LM_MOE_DISPATCH,
                   tnames.LM_MOE_EXPERTS):
        assert counts.get(region, 0) > 0, region
    assert tnames.LM_MOE_SHARED not in counts and tnames.LM_GDN not in counts
    assert {way for _, way in scopes.values()} == {"fwd", "bwd", "remat"}
