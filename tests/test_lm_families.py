"""The seam between `PipelinedLMTrainer` and the model families it trains
(`lm_spec.FAMILIES`: layer kind -> module): a family defined HERE trains
through the unedited trainer, a period stays inside one family, and a mesh
axis a family has no form for is refused by the trainer's one check."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mmlspark_tpu.models.dnn import lm_spec
from mmlspark_tpu.models.dnn.lm_spec import LMSpec, gpt2_spec, qwen3_next_spec
from mmlspark_tpu.models.dnn.pp_training import PipelinedLMTrainer
from mmlspark_tpu.parallel import (DATA_AXIS, MODEL_AXIS, PIPE_AXIS,
                                   SEQ_AXIS, grid_mesh)


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
