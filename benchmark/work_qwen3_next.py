"""work_qwen3_next.py - the operations the `qwen3-next-80b-a3b` cells need,
from the configuration's widths and the layer equations of
`reference/qwen3_next.py`. `work.py` is for the accepted cells and is not
edited; the hybrid driver hands these to the `derived` reader as facts.

As in `work.py`, every quantity is what the algorithm needs, not what an
implementation does: recomputed operations (remat, the flash backward's
second QK^T) and the chunked recurrence's extra products (the C x C solve,
W, U0) do not count. 2 operations per multiply-add; backward = 2 x forward.
"""


def layer_kinds(cfg):
    n = cfg["full_attention_interval"]
    return ["attention" if (i + 1) % n == 0 else "gdn"
            for i in range(cfg["num_layers"])]


def gdn_projection_flops_per_token(cfg):
    """in_proj_qkvz, in_proj_ba, out_proj and the width-4 convolution."""
    d = cfg["hidden_size"]
    qk = cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"]
    v = cfg["linear_num_value_heads"] * cfg["linear_value_head_dim"]
    matmul = d * (2 * qk + 2 * v) + d * 2 * cfg["linear_num_value_heads"] \
        + v * d
    conv = cfg["linear_conv_kernel_dim"] * (2 * qk + v)
    return 2.0 * (matmul + conv)


def gdn_scan_flops_per_token(cfg):
    """The recurrence itself, per token and layer: per value head the decay
    of the state (dk dv), the read k^T S (2 dk dv), the rank-one update
    (2 dk dv) and the query q^T S (2 dk dv)."""
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    return 7.0 * dk * dv * cfg["linear_num_value_heads"]


def gdn_scan_bytes_per_token(cfg):
    """What the recurrence must move, per token and layer, forward and
    backward, with bfloat16 activations and the key heads not repeated:
    forward reads q, k, v, g, beta and writes o; backward reads them and do
    and writes dq, dk, dv, dg, dbeta."""
    qk = cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"]
    v = cfg["linear_num_value_heads"] * cfg["linear_value_head_dim"]
    gates = 2 * cfg["linear_num_value_heads"] * 4
    forward = 2 * (2 * qk + 2 * v) + gates
    backward = 2 * (2 * qk + 2 * v) + gates + 2 * (2 * qk + v) + gates
    return float(forward + backward)


def attention_projection_flops_per_token(cfg):
    d, h, kv, hd = (cfg["hidden_size"], cfg["num_attention_heads"],
                    cfg["num_key_value_heads"], cfg["head_dim"])
    return 2.0 * (d * 2 * h * hd + 2 * d * kv * hd + h * hd * d)


def causal_attention_flops_per_token(cfg, seq):
    """QK^T and PV over the lower triangle: 2 matmuls, S / 2 keys a query
    on average, every query head."""
    return 2.0 * seq * cfg["num_attention_heads"] * cfg["head_dim"]


def moe_fixed_flops_per_token(cfg):
    """Router over all published experts, shared expert, its gate."""
    d = cfg["hidden_size"]
    return 2.0 * (d * cfg["published"]["num_experts"]
                  + 3 * d * cfg["shared_expert_intermediate_size"] + d)


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
    kinds = layer_kinds(cfg)
    n_gdn, n_attn = kinds.count("gdn"), kinds.count("attention")
    forward = n_gdn * (gdn_projection_flops_per_token(cfg)
                       + gdn_scan_flops_per_token(cfg)) \
        + n_attn * (attention_projection_flops_per_token(cfg)
                    + causal_attention_flops_per_token(cfg, seq)) \
        + len(kinds) * moe_fixed_flops_per_token(cfg) \
        + pairs_held_per_token * expert_flops_per_pair(cfg) \
        + head_flops_per_token(cfg)
    return 3.0 * forward


def gdn_scan_flops_per_step(cfg, batch, seq):
    return 3.0 * gdn_scan_flops_per_token(cfg) * batch * seq \
        * layer_kinds(cfg).count("gdn")


def gdn_scan_bytes_per_step(cfg, batch, seq):
    return gdn_scan_bytes_per_token(cfg) * batch * seq \
        * layer_kinds(cfg).count("gdn")


def moe_experts_flops_per_step(cfg, pairs_held_per_step):
    """18 x hidden x expert width per held pair: three matmuls forward, six
    backward."""
    return 3.0 * expert_flops_per_pair(cfg) * pairs_held_per_step


def flash_flops_per_step(cfg, batch, seq):
    """As `work.flash_flops_per_step`: per full-attention layer and
    sequence, 6 causal matmuls of S x S x (heads x head_dim) over the lower
    triangle."""
    width = cfg["num_attention_heads"] * cfg["head_dim"]
    return 6.0 * seq * seq * width * layer_kinds(cfg).count("attention") \
        * batch
