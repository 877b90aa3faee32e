"""The seam between `PipelinedLMTrainer` and the model families it trains
(`lm_spec.FAMILIES`: layer kind -> module): a family defined HERE trains
through the unedited trainer, a period stays inside one family, and a mesh
axis a family has no form for is refused by the trainer's one check."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mmlspark_tpu.models.dnn import lm_spec
from mmlspark_tpu.models.dnn.lm_spec import (LMSpec, gpt2_spec,
                                             lfm2_moe_spec, qwen3_next_spec)
from mmlspark_tpu.models.dnn.pp_training import PipelinedLMTrainer
from mmlspark_tpu.parallel import (DATA_AXIS, MODEL_AXIS, PIPE_AXIS,
                                   SEQ_AXIS, grid_mesh)
from mmlspark_tpu.reliability.metrics import reliability_metrics
from mmlspark_tpu.telemetry import names as tnames


class fake_layers:
    """A family of one matrix a layer, a head tied to the embedding and one
    stat (the layers a stage ran), with everything `docs/dnn.md` "Model
    families" lists and nothing else."""
    AXES = (DATA_AXIS, PIPE_AXIS)
    STATS = {"layers_run": jax.ShapeDtypeStruct((), jnp.float32)}
    reported = []

    def check(spec):
        if spec.d_model < 2:
            raise ValueError("a fake layer wants d_model >= 2")

    def meta(spec):
        return {"d_model": spec.d_model, "family": "fake"}

    def init(spec, seed):
        rng = np.random.default_rng(seed)
        d = spec.d_model
        return {"table": rng.normal(0, 0.5, (spec.vocab_size, d)
                                    ).astype(np.float32),
                "layers": {"m": rng.normal(0, d ** -0.5, (spec.n_periods, d,
                                                          d)
                                           ).astype(np.float32)}}

    def cast(p, dtype):
        return jax.tree_util.tree_map(lambda a: a.astype(dtype), p)

    def embed(p, tokens, seq_off):
        return p["table"][tokens]

    def stage(x, layers, spec, attention, remat, tp_axis=None, cp_axis=None):
        def one(h, m):
            return h + jnp.tanh(h @ m), jnp.float32(1.0)
        x, ran = jax.lax.scan(one, x, layers["m"])
        return x, {"layers_run": ran.sum()}

    def head_loss(p, y, targets, mask, spec):
        logp = jax.nn.log_softmax(
            jnp.einsum("msd,vd->msv", y, p["table"]).astype(jnp.float32))
        nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
        return (nll * mask).sum()

    def summary(stats):
        return [stats["layers_run"][None]]

    def report(values):
        fake_layers.reported.append(float(values[0]))


def test_a_fake_family_trains_through_the_unedited_trainer(monkeypatch):
    """4 one-matrix layers over 2 pipe stages and 2 microbatches: the loss
    falls, and the family's stat (2 stages x 2 layers x 2 live ticks)
    reaches the family's `report` on the host with each step's loss."""
    monkeypatch.setitem(lm_spec.FAMILIES, "fake", fake_layers)
    monkeypatch.setattr(fake_layers, "reported", [])
    spec = LMSpec(vocab_size=32, d_model=16, period=("fake",), n_periods=4)
    assert spec.family is fake_layers
    trainer = PipelinedLMTrainer(
        model=spec, mesh=grid_mesh((1, 2), (DATA_AXIS, PIPE_AXIS)),
        n_microbatches=2, lr=1e-2, seed=1)
    assert trainer.meta == {"d_model": 16, "family": "fake"}
    m = trainer.params["layers"]["m"]
    assert {s.data.shape[0] for s in m.addressable_shards} == {2}
    tok = np.random.default_rng(0).integers(0, 32, (4, 12)).astype(np.int32)
    losses = [trainer.step(tok) for _ in range(6)]
    assert np.isfinite(losses).all() and losses[-1] < losses[0] - 0.05
    assert fake_layers.reported == [8.0] * 6
    loss, grads = trainer.loss_and_grads(tok)
    assert loss < losses[0] and set(grads) == {"table", "layers"}
    assert trainer.run(tok, 2) < loss
    with pytest.raises(ValueError, match="d_model >= 2"):
        LMSpec(vocab_size=32, d_model=1, period=("fake",), n_periods=4)


@pytest.mark.parametrize("period", [("dense", "gdn"), ("attention", "dense")])
def test_a_period_of_two_families_is_refused_with_both_named(period):
    with pytest.raises(ValueError, match="one family.*does not mix") as e:
        LMSpec(vocab_size=8, d_model=8, period=period, n_periods=1)
    assert "dense_layers" in str(e.value) and "hybrid_layers" in str(e.value)


TINY_HYBRID = dict(
    hidden_size=16, num_hidden_layers=2, full_attention_interval=2,
    head_dim=8, num_attention_heads=2, num_key_value_heads=1,
    partial_rotary_factor=0.5, rope_theta=1e4, rms_norm_eps=1e-6,
    linear_num_key_heads=1, linear_num_value_heads=2, linear_key_head_dim=8,
    linear_value_head_dim=8, linear_conv_kernel_dim=4, num_experts=4,
    num_experts_per_tok=2, moe_intermediate_size=8,
    shared_expert_intermediate_size=8, norm_topk_prob=True, vocab_size=16)
SPECS = {"dense_layers": lambda: gpt2_spec(16, 8, 2, 1, 16, 8),
         "hybrid_layers": lambda: qwen3_next_spec(TINY_HYBRID, (0, 4))}


@pytest.mark.parametrize("family,axis,match", [
    ("hybrid_layers", MODEL_AXIS, "data and pipe axes"),
    ("hybrid_layers", SEQ_AXIS, "data and pipe axes"),
    ("dense_layers", "expert", "data and pipe and model and seq axes"),
])
def test_a_family_refuses_a_mesh_axis_it_has_no_form_for(family, axis,
                                                         match):
    """The trainer's one check, from `family.AXES`: a hybrid model's layers
    have no Megatron slicing and no ring form, and no family knows an
    `expert` axis. The refusal names the family and the axis."""
    mesh = grid_mesh((1, 1, 1), (DATA_AXIS, PIPE_AXIS, axis))
    with pytest.raises(ValueError, match=match) as e:
        PipelinedLMTrainer(model=SPECS[family](), mesh=mesh)
    assert family in str(e.value) and axis in str(e.value)


# ------------------------------------------------------- what `remat` keeps
# (`hybrid_layers.checkpoint_sublayers`, the one rule of both share families)
TINY_LFM2 = dict(
    hidden_size=16, intermediate_size=24, conv_L_cache=3, norm_eps=1e-5,
    num_attention_heads=2, num_key_value_heads=1,
    layer_types=["conv", "full_attention", "conv"], num_dense_layers=1,
    rope_parameters={"rope_theta": 1e4}, num_experts=4,
    num_experts_per_tok=2, moe_intermediate_size=8, norm_topk_prob=True,
    use_expert_bias=True, routed_scaling_factor=1, vocab_size=16)
SPECS["shortconv_layers"] = lambda: lfm2_moe_spec(TINY_LFM2, (0, 4))
SHARE_FAMILIES = ("hybrid_layers", "shortconv_layers")
KEEPS = (tnames.LM_REMAT_KEEP_FLASH, tnames.LM_REMAT_KEEP_ROUTING)
# arrays a sublayer keeps under each name: the flash forward's out and lse;
# the router's scores, ids and chosen scores and the plan's four vectors
KEPT_ARRAYS = {tnames.KEEP_FLASH: 2, tnames.KEEP_ROUTING: 7}


def family_trainer(family, **kw):
    kw = {"n_microbatches": 1, "seed": 5, "attention": "flash", **kw}
    return PipelinedLMTrainer(
        model=SPECS[family](),
        mesh=grid_mesh((1, 1), (DATA_AXIS, PIPE_AXIS)), **kw)


def toy_tokens(seq=24):
    return np.random.default_rng(2).integers(0, 16, (2, seq)).astype(np.int32)


def counted(fn, names=KEEPS):
    """(fn's result, what it added to each counter)."""
    before = [reliability_metrics.get(n) for n in names]
    out = fn()
    return out, tuple(reliability_metrics.get(n) - b
                      for n, b in zip(names, before))


@pytest.fixture(scope="module", params=SHARE_FAMILIES)
def without_remat(request):
    """(family, loss, gradients) of a toy model with nothing checkpointed,
    and no sublayer counted as keeping anything."""
    trainer = family_trainer(request.param, remat=False)
    (loss, grads), kept = counted(lambda: trainer.loss_and_grads(
        toy_tokens()))
    assert kept == (0, 0)
    return request.param, loss, grads


@pytest.mark.parametrize("remat,kept", [
    (True, (1, 2)), ("full", (1, 2)), ("save_attn", (0, 2))])
def test_a_remat_value_changes_what_is_kept_and_no_number(without_remat,
                                                          remat, kept):
    """The loss and every leaf's gradient of `remat=False`, whichever value
    it has; and the trace counts one flash-keeping checkpoint an attention
    layer of the period and one routing-keeping checkpoint an expert layer
    (both toy periods: two layers, one of them attention), none for a mixer
    that "save_attn" leaves unchecked."""
    family, loss, grads = without_remat
    trainer = family_trainer(family, remat=remat)
    (got_loss, got), counts = counted(
        lambda: trainer.loss_and_grads(toy_tokens()))
    assert counts == kept
    assert abs(got_loss - loss) < 1e-6
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree_util.tree_leaves(grads)):
        apart = float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))
        assert apart < 1e-5, (jax.tree_util.keystr(path), apart)


def test_the_dense_family_counts_no_kept_residual():
    trainer = PipelinedLMTrainer(
        model=SPECS["dense_layers"](), attention="flash", remat="save_attn",
        n_microbatches=1, mesh=grid_mesh((1, 1), (DATA_AXIS, PIPE_AXIS)))
    tok = np.random.default_rng(2).integers(0, 16, (2, 8)).astype(np.int32)
    _, kept = counted(lambda: trainer.loss_and_grads(tok))
    assert kept == (0, 0)


def one_layer(family, kind):
    """(a function (h, lp, remat) -> h of one toy layer of `kind` with an
    expert layer behind it, h, lp)."""
    spec = SPECS[family]()
    module = lm_spec.FAMILIES[kind]
    i = spec.period.index(kind)
    lp = jax.tree_util.tree_map(lambda a: jnp.asarray(a[0]),
                                module.init(spec, 0)["layers"][i])
    h = jnp.asarray(np.random.default_rng(0).standard_normal((2, 16, 16)),
                    jnp.float32)
    if family == "hybrid_layers":
        def layer(h, lp, remat):
            return module.hybrid_layer(h, lp, kind, spec, "flash", remat)[0]
    else:
        def layer(h, lp, remat):
            return module.layer(h, lp, kind, "experts", spec, "flash",
                                remat)[0]
    return layer, h, lp


def stored(layer, h, lp, remat):
    """What reverse-mode stores of a layer beyond its arguments and
    constants: [(shape, where it comes from)]."""
    from jax._src.ad_checkpoint import saved_residuals
    return [(aval.shape, why) for aval, why in saved_residuals(
        lambda h, lp: layer(h, lp, remat), h, lp)
        if not why.startswith(("from the argument", "from a constant",
                               "from a literal"))]


def names_of(residuals):
    return {why.split("'")[1] for _, why in residuals
            if why.startswith("named")}


@pytest.mark.parametrize("family,kind,names", [
    ("hybrid_layers", "attention", {tnames.KEEP_FLASH, tnames.KEEP_ROUTING}),
    ("hybrid_layers", "gdn", {tnames.KEEP_ROUTING}),
    ("shortconv_layers", "full_attention", {tnames.KEEP_FLASH,
                                            tnames.KEEP_ROUTING}),
    ("shortconv_layers", "conv", {tnames.KEEP_ROUTING})])
def test_full_remat_stores_the_named_residuals_and_nothing_else(family, kind,
                                                                names):
    """Under True a layer stores its two sublayers' inputs (its own, an
    argument, and the mixer's result) and the residuals of
    `REMAT_RESIDUALS` that its sublayers make: no projection, no state.
    (`saved_residuals` shows a named array that a `custom_vjp` also returns
    as the rounding the name leaves behind.)"""
    layer, h, lp = one_layer(family, kind)
    residuals = stored(layer, h, lp, True)
    unnamed = [why for _, why in residuals
               if not why.startswith(("named", "output of reduce_precision"))]
    assert len(unnamed) == 1 and unnamed[0].startswith("output of add"), \
        unnamed
    assert names_of(residuals) == names
    assert len(residuals) == 1 + sum(KEPT_ARRAYS[n] for n in names)
    assert stored(layer, h, lp, "full") == residuals


@pytest.mark.parametrize("family,kind", [
    ("hybrid_layers", "attention"), ("shortconv_layers", "full_attention")])
def test_save_attn_stores_the_mixer_and_recomputes_the_feed_forward(family,
                                                                    kind):
    """`remat="save_attn"` (ROADMAP D13): the mixer sublayer keeps what
    reverse-mode keeps (projections, norms, the kernels' q, k, v); the
    expert sublayer is as under True."""
    layer, h, lp = one_layer(family, kind)
    residuals = stored(layer, h, lp, "save_attn")
    assert any("(attention_mixer)" in why for _, why in residuals), residuals
    assert len(residuals) > len(stored(layer, h, lp, True)) + 5
    feed = [why for _, why in residuals if "/moe.py" in why]
    assert len(feed) == KEPT_ARRAYS[tnames.KEEP_ROUTING] and all(
        why.startswith(("named", "output of reduce_prec")) for why in feed)
    # nothing checkpointed: the expert sublayer stores its own too
    assert len(stored(layer, h, lp, False)) > len(residuals)


@pytest.mark.parametrize("family", SHARE_FAMILIES)
@pytest.mark.parametrize("remat", [False, True, "save_attn"])
def test_the_compiled_step_sorts_and_chooses_once_an_expert_layer(family,
                                                                  remat):
    """Two expert layers a toy period: two sorts of the pairs and two
    top-ks in the whole compiled step, forward and backward, whatever is
    recomputed (a checkpoint that kept no routing would run four of
    each)."""
    trainer = family_trainer(family, remat=remat)
    text = trainer._step.lower(
        trainer.params, trainer.opt_state,
        trainer._to_device(toy_tokens())).compile().as_text()
    assert len(re.findall(r"\bsort\(", text)) == 2
    assert len(re.findall(r'custom_call_target="TopK"', text)) == 2


# ------------------------------------------------- what a dense layer keeps
# (`dense_layers.stage`: docs/dnn.md "What a dense layer keeps")
def dense_layer(d=1024, heads=16, d_ff=4096, seq=128, n_layers=1):
    """(a function (h, lp, remat) -> h of `n_layers` dense layers through
    the family's stage, h (2, seq, d) float32, lp): gpt2-medium's widths
    by default."""
    from mmlspark_tpu.models.dnn import dense_layers
    spec = gpt2_spec(64, d, heads, n_layers, d_ff, seq)
    lp = jax.tree_util.tree_map(jnp.asarray,
                                dense_layers.init(spec, 0)["layers"])
    h = jnp.asarray(np.random.default_rng(0).standard_normal((2, seq, d)),
                    jnp.float32)

    def layer(h, lp, remat):
        return dense_layers.stage(h, lp, spec, "flash", remat)[0]
    return layer, h, lp, spec


@pytest.mark.parametrize("remat,slabs,kernels", [
    (True, 1, 0), ("full", 1, 0), ("save_attn", 3, 5)])
def test_a_dense_layer_keeps_its_slabs_and_the_kernels_operands(remat, slabs,
                                                                kernels):
    """At gpt2-medium's widths (d 1024, 16 heads of 64; 2 x 128 tokens) a
    layer keeps (mb, S, d) slabs, lane-dense, and under "save_attn" the
    five arrays the flash kernels read back in their own layout (q, k, v,
    out as (mb, h, S, dh), the row sums): eight arrays, where reverse
    mode's own residuals of the layer norm made fifteen (three of them
    float32 copies of x). No (mb, S, d_ff) activation is kept: the
    feed-forward sublayer is recomputed."""
    layer, h, lp, _ = dense_layer()
    residuals = stored(layer, h, lp, remat)
    shapes = [shape[1:] for shape, _ in residuals]     # (layers, ...) stacks
    slab = [s for s in shapes if s == h.shape]
    kernel = [s for s in shapes if s in ((2, 16, 128, 64), (2, 16, 128, 1))]
    assert (len(slab), len(kernel)) == (slabs, kernels), shapes
    assert len(shapes) == slabs + kernels
    assert all(s[-1] % 128 == 0 for s in slab)


@pytest.mark.parametrize("remat", [False, True, "save_attn"])
def test_the_dense_stage_is_a_loop_over_its_sublayers(remat):
    """The stage's loss and gradients (input and every layer leaf) equal a
    plain Python loop of `_block_attn` then `_block_ff` over the layers,
    float32, whatever `remat` keeps."""
    from mmlspark_tpu.models.dnn import dense_layers
    layer, h, lp, spec = dense_layer(d=128, heads=2, d_ff=256, seq=128,
                                     n_layers=2)
    w = jnp.asarray(np.random.default_rng(1).standard_normal(h.shape),
                    jnp.float32)

    def looped(h, lp):
        for i in range(spec.n_periods):
            lp_i = jax.tree_util.tree_map(lambda a: a[i], lp)
            h = dense_layers._block_attn(h, lp_i, 2, 64, attention="flash")
            h = dense_layers._block_ff(h, lp_i)
        return jnp.sum(h * w)

    want, want_g = jax.value_and_grad(looped, argnums=(0, 1))(h, lp)
    got, got_g = jax.value_and_grad(
        lambda h, lp: jnp.sum(layer(h, lp, remat) * w), argnums=(0, 1))(h, lp)
    assert abs(float(got - want)) <= 1e-6 * abs(float(want))
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got_g),
                            jax.tree_util.tree_leaves(want_g)):
        apart = float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))
        assert apart <= 1e-6, (jax.tree_util.keystr(path), apart)
