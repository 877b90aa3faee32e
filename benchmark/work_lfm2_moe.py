"""work_lfm2_moe.py - the operations and bytes the `lfm2-24b-a2b` cells
need, from the configuration's widths and the layer equations of
`reference/lfm2_moe.py`. `work.py` is for the accepted cells and is not
edited; the LFM2 driver hands these to the `derived` reader as facts.

As in `work.py`, every quantity is what the algorithm needs, not what an
implementation does: recomputed operations (remat, the flash backward's
second QK^T) and K and V repeated for their query heads do not count.
2 operations per multiply-add; backward = 2 x forward.
"""


def layers_held(cfg):
    """[(operator, feed-forward)] of the layers held here, in order: the
    `num_dense_layers` leading layers held (dense MLP), then the published
    layers after the published leading ones (experts)."""
    n_dense = cfg["num_dense_layers"]
    skipped = cfg["published"]["num_dense_layers"] - n_dense
    types = cfg["layer_types"][skipped:skipped + cfg["num_layers"]]
    return [(kind, "dense" if i < n_dense else "experts")
            for i, kind in enumerate(types)]


def count(cfg, what):
    return sum(what in layer for layer in layers_held(cfg))


def conv_flops_per_token(cfg):
    """in_proj (d x 3d), out_proj (d x d), and the gate pass: B * u, the
    taps' multiply-adds, C *."""
    d = cfg["hidden_size"]
    return 2.0 * (3 * d * d + d * d) + (2 * cfg["conv_L_cache"] + 1) * d


def conv_gate_bytes_per_token(cfg):
    """What the gate pass must move, per token and conv layer, bfloat16:
    forward reads B, C, u and writes y; backward reads B, C, u and dy and
    writes dB, dC, du."""
    d = cfg["hidden_size"]
    return 2.0 * d * ((3 + 1) + (4 + 3))


def attention_projection_flops_per_token(cfg):
    d, h, kv = (cfg["hidden_size"], cfg["num_attention_heads"],
                cfg["num_key_value_heads"])
    hd = d // h
    return 2.0 * (d * h * hd + 2 * d * kv * hd + h * hd * d)


def causal_attention_flops_per_token(cfg, seq):
    """QK^T and PV over the lower triangle: 2 matmuls, S / 2 keys a query
    on average, every query head (heads x head size = hidden size)."""
    return 2.0 * seq * cfg["hidden_size"]


def dense_mlp_flops_per_token(cfg):
    return 6.0 * cfg["hidden_size"] * cfg["intermediate_size"]


def router_flops_per_token(cfg):
    """Router logits over all published experts."""
    return 2.0 * cfg["hidden_size"] * cfg["published"]["num_experts"]


def expert_flops_per_pair(cfg):
    """One (token, expert) pair through a routed expert's gated MLP."""
    return 6.0 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def head_flops_per_token(cfg):
    return 2.0 * cfg["hidden_size"] * cfg["vocab_size"]


def lm_flops_per_token(cfg, seq, pairs_held_per_token):
    """Forward + backward operations of one token of a sequence of `seq`
    tokens through the layers, experts and vocabulary held here.
    `pairs_held_per_token` is measured (`moe.pairs.held` over the tokens of
    the steps, all expert layers together)."""
    forward = count(cfg, "conv") * conv_flops_per_token(cfg) \
        + count(cfg, "full_attention") * (
            attention_projection_flops_per_token(cfg)
            + causal_attention_flops_per_token(cfg, seq)) \
        + count(cfg, "dense") * dense_mlp_flops_per_token(cfg) \
        + count(cfg, "experts") * router_flops_per_token(cfg) \
        + pairs_held_per_token * expert_flops_per_pair(cfg) \
        + head_flops_per_token(cfg)
    return 3.0 * forward


def conv_gate_bytes_per_step(cfg, batch, seq):
    return conv_gate_bytes_per_token(cfg) * batch * seq * count(cfg, "conv")


def moe_experts_flops_per_step(cfg, pairs_held_per_step):
    """18 x hidden x expert width per held pair: three matmuls forward, six
    backward."""
    return 3.0 * expert_flops_per_pair(cfg) * pairs_held_per_step


def flash_flops_per_step(cfg, batch, seq):
    """As `work.flash_flops_per_step`: per full-attention layer and
    sequence, 6 causal matmuls of S x S x (heads x head size) over the
    lower triangle."""
    return 6.0 * seq * seq * cfg["hidden_size"] \
        * count(cfg, "full_attention") * batch


def parameter_count(cfg):
    """The parameters held here: layers, held experts, router, selection
    bias, norms, the embedding slice (tied: counted once)."""
    d, h, kv = (cfg["hidden_size"], cfg["num_attention_heads"],
                cfg["num_key_value_heads"])
    hd = d // h
    mixer = {"conv": 3 * d * d + cfg["conv_L_cache"] * d + d * d,
             "full_attention": 2 * d * h * hd + 2 * d * kv * hd + 2 * hd}
    held = cfg["experts_held"][1] - cfg["experts_held"][0]
    routed = cfg["published"]["num_experts"]
    feed = {"dense": 3 * d * cfg["intermediate_size"],
            "experts": held * 3 * d * cfg["moe_intermediate_size"]
            + d * routed + (routed if cfg["use_expert_bias"] else 0)}
    return sum(mixer[kind] + feed[ffn] + 2 * d
               for kind, ffn in layers_held(cfg)) \
        + cfg["vocab_size"] * d + d
