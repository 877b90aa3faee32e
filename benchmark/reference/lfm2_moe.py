"""Plain reference of the `lfm2-24b-a2b` configuration: the forward pass,
next-token loss and gradients of an LFM2-MoE decoder in straightforward
`jax.numpy`, float32 under `default_matmul_precision("highest")`. No
kernels, no tiles, no mixed precision; independent of the package (imports
jax only).

It runs in blocks so that the gradient of an 8,192-token sequence at the
published widths fits on one chip beside a trainer: one sequence at a time,
and `jax.checkpoint` round each sublayer, expert and block of queries, which
changes what the backward pass keeps and nothing that is computed.

It follows the published architecture (Hugging Face `lfm2_moe`,
`Lfm2MoeForCausalLM`): `layer_types` gives each layer's operator, the first
`num_dense_layers` layers carry a dense SwiGLU MLP and the others the
sparse expert layer, norms are plain RMSNorms (weight at 1), the head is
tied to the embedding.

    norm(x, w)   = x * rsqrt(mean(x^2) + eps) * w                 (float32)
    layer        : h = h + operator(operator_norm(h));
                   h = h + feed_forward(ffn_norm(h))
    conv         : (B, C, u) = thirds of x W_in; v = B * u;
                   c[t] = k0 v[t-2] + k1 v[t-1] + k2 v[t] per channel
                   (depthwise, causal, zeros before the sequence, no bias,
                   no activation); (C * c) W_out
    attention    : q 32 heads x 64, k and v 8 heads x 64, no biases; norm
                   over the 64 of each head of q and of k; rotary on all 64
                   (theta 1e6, rotate-half); causal softmax, scale 64^-0.5,
                   KV head j serves query heads 4j .. 4j+3; W_o
    dense MLP    : w2(silu(w1 x) * w3 x)
    experts      : s = sigmoid(x W_r) over all 64 in float32; the 4 largest
                   of s + expert_bias are chosen; weights s[chosen] /
                   (sum of s[chosen] + 1e-6), times routed_scaling_factor;
                   sum_k weight_k * down_e(silu(gate_e x) * up_e x); no
                   shared expert
    logits       : norm(h, final_norm) @ embed^T

The chip's share (the configuration file says of which deployment):
`experts_held = (lo, hi)` is the contiguous range of experts whose weights
are here. The router is as wide as published and picks among all experts;
a pair routed to an absent expert adds nothing. The vocabulary slice is a
smaller vocabulary: `embed` has as many rows as the slice. The layers held
are `layers_held(cfg)`: the `num_dense_layers` leading layers held here,
then `num_layers` less that many of the published layers that follow the
published leading ones.

Departures from the published weights' layout (none changes the function
for seeded weights): `taps` is (width, channels) where the published
depthwise kernel is (channels, 1, width); the experts' matrices are
stacked (E, in, out) under `w_gate`, `w_up`, `w_down` (published `w1`,
`w3`, `w2` per expert, (out, in)).

`dtype` exists for one purpose: the benchmark's calibration reads what this
reference gives when everything is computed in bfloat16, which has to fail
the cell's limits (PERF.md). The reference proper is float32.

Weights: {"embed" (V, d), "final_norm" (d,), "leading": [one dict a
leading layer], "layers": [one dict per position of the period, leaves
stacked over periods (P, ...)]}, each layer {"operator_norm", "ffn_norm",
"mixer": {...}, "mlp": {...} or "moe": {...}} with the names of
`conv_mixer`, `attention_mixer`, `dense_mlp` and `moe` below.
"""
import math

import jax
import jax.numpy as jnp

SIGMOID_NORM_EPS = 1e-6


def rms_norm(x, w, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt((x32 * x32).mean(-1, keepdims=True) + eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


def rotary(x, theta):
    """Rotary embedding on the whole last dimension of x (S, H, D),
    positions 0 .. S-1, half-split convention."""
    seq, _, dim = x.shape
    inv = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    ang = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]
    cos, sin = jnp.cos(ang).astype(x.dtype), jnp.sin(ang).astype(x.dtype)
    half = jnp.concatenate([-x[..., dim // 2:], x[..., :dim // 2]], -1)
    return x * cos + half * sin


def attention_mixer(x, p, cfg, q_block=512):
    seq = x.shape[0]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg["hidden_size"] // h
    theta = float(cfg["rope_parameters"]["rope_theta"])
    q = (x @ p["q_proj"]).reshape(seq, h, d)
    k = (x @ p["k_proj"]).reshape(seq, kv, d)
    v = (x @ p["v_proj"]).reshape(seq, kv, d)
    q = rotary(rms_norm(q, p["q_layernorm"], cfg["norm_eps"]), theta)
    k = rotary(rms_norm(k, p["k_layernorm"], cfg["norm_eps"]), theta)
    k = jnp.repeat(k, h // kv, axis=1)          # head j of kv -> 4j..4j+3
    v = jnp.repeat(v, h // kv, axis=1)
    kt, vt = k.transpose(1, 2, 0), v.transpose(1, 0, 2)
    q_block = min(q_block, seq)
    pad = (-seq) % q_block
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
        -1, q_block, h, d).transpose(0, 2, 1, 3)       # (nb, h, qb, d)
    starts = jnp.arange(qb.shape[0]) * q_block

    @jax.checkpoint
    def one_block(args):
        qs, start = args
        scores = (qs @ kt).astype(jnp.float32) / math.sqrt(d)
        rows = start + jnp.arange(q_block)[:, None]
        seen = jnp.arange(seq)[None, :] <= rows
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), -1)
        return probs.astype(x.dtype) @ vt              # (h, qb, d)

    out = jax.lax.map(one_block, (qb, starts))
    out = out.transpose(0, 2, 1, 3).reshape(-1, h * d)[:seq]
    return out @ p["o_proj"]


def conv_mixer(x, p, cfg):
    """The gated short convolution, the convolution as shifted adds."""
    seq, width = x.shape[0], cfg["conv_L_cache"]
    b, c, u = jnp.split(x @ p["in_proj"], 3, axis=-1)
    v = jnp.pad(b * u, ((width - 1, 0), (0, 0)))
    conv = sum(v[j:j + seq] * p["taps"][j] for j in range(width))
    return (c * conv) @ p["out_proj"]


def dense_mlp(x, p):
    return (jax.nn.silu(x @ p["w1"]) * (x @ p["w3"])) @ p["w2"]


def route(x, p, cfg):
    """(indices (N, k), weights (N, k)) of the router: sigmoid scores over
    all experts in float32, the k largest of score + expert_bias chosen,
    weighted by their own scores renormalised over the chosen."""
    scores = jax.nn.sigmoid((x @ p["router"]).astype(jnp.float32))
    biased = scores + p["expert_bias"].astype(jnp.float32) \
        if cfg["use_expert_bias"] else scores
    _, idx = jax.lax.top_k(biased, cfg["num_experts_per_tok"])
    top = jnp.take_along_axis(scores, idx, axis=-1)
    if cfg["norm_topk_prob"]:
        top = top / (top.sum(-1, keepdims=True) + SIGMOID_NORM_EPS)
    return idx, top * cfg["routed_scaling_factor"]


def moe(x, p, cfg, experts_held):
    """x (N, d). The held experts' part of the routed sum, as a loop over
    them."""
    lo, hi = experts_held
    idx, top = route(x, p, cfg)

    def one_expert(e, w_gate, w_up, w_down):
        weight = (top * (idx == lo + e)).sum(-1).astype(x.dtype)
        y = (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down
        return y * weight[:, None]

    def body(acc, args):
        return acc + jax.checkpoint(one_expert)(*args), None

    routed, _ = jax.lax.scan(
        body, jnp.zeros_like(x),
        (jnp.arange(hi - lo), p["w_gate"], p["w_up"], p["w_down"]))
    return routed


def layers_held(cfg):
    """(the leading layers' kinds, the period's kinds) of the layers held
    here, in their published order."""
    types = list(cfg["layer_types"])
    n_dense = cfg["num_dense_layers"]
    skipped = cfg.get("published", {}).get("num_dense_layers", n_dense) \
        - n_dense
    held = types[skipped:skipped + cfg.get("num_layers", len(types))]
    rest = held[n_dense:]
    n = next(n for n in range(1, len(rest) + 1) if len(rest) % n == 0
             and rest == rest[:n] * (len(rest) // n))
    return held[:n_dense], rest[:n]


def mix(x, lp, kind, cfg):
    """x + operator(operator_norm(x)) of one layer."""
    y = rms_norm(x, lp["operator_norm"], cfg["norm_eps"])
    if kind == "full_attention":
        return x + attention_mixer(y, lp["mixer"], cfg)
    return x + conv_mixer(y, lp["mixer"], cfg)


def feed(x, lp, cfg, experts_held):
    """x + feed_forward(ffn_norm(x)) of one layer: the dense MLP where the
    layer has one, else the experts held."""
    y = rms_norm(x, lp["ffn_norm"], cfg["norm_eps"])
    if "mlp" in lp:
        return x + dense_mlp(y, lp["mlp"])
    return x + moe(y, lp["moe"], cfg, experts_held)


def sequence_logits(weights, tokens, cfg, experts_held):
    """Logits (S, V) of one (S,) sequence over the vocabulary slice."""
    leading, kinds = layers_held(cfg)
    x = weights["embed"][tokens]

    def layer(x, lp, kind):
        x = jax.checkpoint(lambda x, lp: mix(x, lp, kind, cfg))(x, lp)
        return jax.checkpoint(
            lambda x, lp: feed(x, lp, cfg, experts_held))(x, lp)

    for kind, lp in zip(leading, weights["leading"]):
        x = layer(x, lp, kind)

    def period(x, lps):
        for kind, lp in zip(kinds, lps):
            x = layer(x, lp, kind)
        return x, None

    x, _ = jax.lax.scan(period, x, weights["layers"])
    x = rms_norm(x, weights["final_norm"], cfg["norm_eps"])
    return x @ weights["embed"].T


def sequence_loss(weights, tokens, cfg, experts_held):
    """Summed next-token negative log-likelihood of one (S,) sequence over
    its S - 1 predicted positions."""
    logits = sequence_logits(weights, tokens, cfg, experts_held)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.take_along_axis(logp[:-1], tokens[1:, None], axis=-1).sum()


def _cast(weights, dtype):
    return jax.tree_util.tree_map(lambda a: jnp.asarray(a, dtype), weights)


def loss_and_grads(weights, tokens, cfg, experts_held, dtype=jnp.float32,
                   pick=None):
    """(mean loss of (B, S) tokens, its gradient in float32), a sequence at
    a time. `pick` maps a tree shaped like `weights` to the pytree of its
    leaves to differentiate (default: all of them); the gradient comes back
    in that pytree's shape."""
    flat, treedef = jax.tree_util.tree_flatten(weights)
    picked = (pick or (lambda tree: tree))(
        treedef.unflatten(list(range(len(flat)))))
    places = jax.tree_util.tree_leaves(picked)

    def one_sequence(chosen, flat, seq_tokens):
        flat = list(flat)
        for i, leaf in zip(places, jax.tree_util.tree_leaves(chosen)):
            flat[i] = leaf
        return sequence_loss(_cast(treedef.unflatten(flat), dtype),
                             seq_tokens, cfg, experts_held)

    chosen = jax.tree_util.tree_map(lambda i: flat[i], picked)
    tokens = jnp.asarray(tokens, jnp.int32)
    total, grads = 0.0, None
    with jax.default_matmul_precision("highest"):
        fn = jax.jit(jax.value_and_grad(one_sequence))
        for seq_tokens in tokens:
            value, g = fn(chosen, flat, seq_tokens)
            total += float(value)
            grads = g if grads is None else jax.tree_util.tree_map(
                jnp.add, grads, g)
    count = tokens.shape[0] * (tokens.shape[1] - 1)
    return total / count, jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32) / count, grads)
