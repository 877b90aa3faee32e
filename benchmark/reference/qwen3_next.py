"""Plain reference of the `qwen3-next-80b-a3b` configuration: the forward
pass, next-token loss and gradients of a Qwen3-Next decoder in
straightforward `jax.numpy`, float32 under
`default_matmul_precision("highest")`. No kernels, no chunked recurrence, no
mixed precision; independent of the package (imports jax only).

It runs in blocks so that the gradient of an 8,192-token sequence at the
published widths fits on one chip beside a trainer: one sequence at a time,
and `jax.checkpoint` round each sublayer, expert, block of queries and block
of positions, which changes what the backward pass keeps and nothing that
is computed.

It follows the published architecture (Hugging Face `qwen3_next`,
`Qwen3NextForCausalLM`): layer `i` is full attention when
`(i + 1) % full_attention_interval == 0` and a Gated DeltaNet otherwise,
every layer's feed-forward is the sparse expert layer with one shared
expert, norms are zero-centred RMSNorms (`1 + w`), the head is untied.

    norm(x, w)   = x * rsqrt(mean(x^2) + eps) * (1 + w)           (float32)
    layer        : h = h + mixer(norm_in(h)); h = h + moe(norm_post(h))
    attention    : q_proj -> 16 heads x [query 256 | gate 256]; k, v: 2
                   heads x 256; norm over 256 on query and key; rotary on
                   the first 64 dimensions (theta 1e7, rotate-half); causal
                   softmax, scale 256^-0.5, KV head j serves query heads
                   8j .. 8j+7; output * sigmoid(gate); o_proj
    gated delta  : in_proj_qkvz -> q 16x128, k 16x128, v 32x128, z 32x128;
                   in_proj_ba -> b, a (32 each); causal depthwise conv of
                   width 4 and SiLU on concat(q, k, v); beta = sigmoid(b);
                   g = -exp(A_log) * softplus(a + dt_bias); q, k
                   L2-normalised, q * 128^-0.5, each key head repeated for
                   its 2 value heads; per value head and position
                       S = exp(g_t) S;  r = k_t^T S;  d = beta_t (v_t - r)
                       S = S + k_t d^T; o_t = q_t^T S
                   o * rsqrt(mean(o^2) + eps) * w * silu(z); out_proj
    experts      : p = softmax(x W_r) over all 512; the 10 largest,
                   renormalised; sum_k p_k * down_e(silu(gate_e x) * up_e x)
                   + sigmoid(x w_g) * shared(x)

The chip's share (the configuration file says of which deployment):
`experts_held = (lo, hi)` is the contiguous range of experts whose weights
are here. The router is as wide as published and picks among all experts;
a pair routed to an absent expert adds nothing. The vocabulary slice is a
smaller vocabulary: `embed` and `head` have as many rows as the slice.

Departures from the published weights' layout (none changes the function
for seeded weights): `in_proj_qkvz` is stored [q | k | v | z] and
`in_proj_ba` [b | a], where the published weights interleave them per key
head (a fixed permutation of output columns); `conv` is (width, channels).

`dtype` exists for one purpose: the benchmark's calibration reads what this
reference gives when everything is computed in bfloat16, which has to fail
the cell's limits (PERF.md). The reference proper is float32.

Weights: {"embed" (V, d), "head" (V, d), "final_norm" (d,), "layers": [one
dict per position of the period, leaves stacked over periods (P, ...)]},
each layer {"norm_in", "norm_post", "mixer": {...}, "moe": {...}} with the
names of `gdn_mixer`, `attention_mixer` and `moe` below.
"""
import math

import jax
import jax.numpy as jnp


def rms_norm(x, w, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt((x32 * x32).mean(-1, keepdims=True) + eps)
    return (y * (1.0 + w.astype(jnp.float32))).astype(x.dtype)


def rotary(x, theta, rot):
    """Rotary embedding on the first `rot` of the last dimension of
    x (S, H, D), positions 0 .. S-1, half-split convention."""
    seq = x.shape[0]
    inv = theta ** (-jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    ang = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[:, None, :]
    cos, sin = jnp.cos(ang).astype(x.dtype), jnp.sin(ang).astype(x.dtype)
    xr, rest = x[..., :rot], x[..., rot:]
    half = jnp.concatenate([-xr[..., rot // 2:], xr[..., :rot // 2]], -1)
    return jnp.concatenate([xr * cos + half * sin, rest], axis=-1)


def attention_mixer(x, p, cfg, q_block=512):
    seq = x.shape[0]
    h, kv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"])
    qg = (x @ p["q_proj"]).reshape(seq, h, 2 * d)
    q, gate = qg[..., :d], qg[..., d:].reshape(seq, h * d)
    k = (x @ p["k_proj"]).reshape(seq, kv, d)
    v = (x @ p["v_proj"]).reshape(seq, kv, d)
    rot = int(d * cfg["partial_rotary_factor"])
    q = rotary(rms_norm(q, p["q_norm"], cfg["rms_norm_eps"]),
               float(cfg["rope_theta"]), rot)
    k = rotary(rms_norm(k, p["k_norm"], cfg["rms_norm_eps"]),
               float(cfg["rope_theta"]), rot)
    k = jnp.repeat(k, h // kv, axis=1)          # head j of kv -> 8j..8j+7
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
    return (out * jax.nn.sigmoid(gate)) @ p["o_proj"]


def gdn_project(x, p, cfg):
    """Everything of the Gated-DeltaNet mixer before the recurrence:
    (q, k, v, g, beta, z) with q, k (S, Hv, dk), v, z (S, Hv, dv), g and
    beta (S, Hv); g float32."""
    seq = x.shape[0]
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    width = cfg["linear_conv_kernel_dim"]
    qkvz = x @ p["in_proj_qkvz"]
    n_qkv = 2 * hk * dk + hv * dv
    qkv, z = qkvz[:, :n_qkv], qkvz[:, n_qkv:].reshape(seq, hv, dv)
    ba = x @ p["in_proj_ba"]
    b, a = ba[:, :hv], ba[:, hv:]
    padded = jnp.pad(qkv, ((width - 1, 0), (0, 0)))
    qkv = jax.nn.silu(sum(padded[j:j + seq] * p["conv"][j]
                          for j in range(width)))
    q = qkv[:, :hk * dk].reshape(seq, hk, dk)
    k = qkv[:, hk * dk:2 * hk * dk].reshape(seq, hk, dk)
    v = qkv[:, 2 * hk * dk:].reshape(seq, hv, dv)
    beta = jax.nn.sigmoid(b)
    g = -jnp.exp(p["A_log"].astype(jnp.float32)) * jax.nn.softplus(
        a.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))

    def l2(t):
        t32 = t.astype(jnp.float32)
        return (t32 * jax.lax.rsqrt((t32 * t32).sum(-1, keepdims=True)
                                    + 1e-6)).astype(t.dtype)

    q = jnp.repeat(l2(q) * dk ** -0.5, hv // hk, axis=1)
    k = jnp.repeat(l2(k), hv // hk, axis=1)
    return q, k, v, g, beta, z


def delta_rule(q, k, v, g, beta, block=128):
    """The gated delta rule, one position at a time: q, k (S, H, dk),
    v (S, H, dv), g and beta (S, H); returns o (S, H, dv). The scan over
    positions is nested, `block` positions inside, so that the backward pass
    keeps a state a block and a block's states, not one a position (a
    position past the end, all zeros, leaves the state as it is)."""
    seq, heads, dk = q.shape
    dv = v.shape[2]
    block = min(block, seq)
    pad = (-seq) % block

    def blocks(a):
        a = jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
        return a.reshape((-1, block) + a.shape[1:])

    def step(state, xs):
        q_t, k_t, v_t, g_t, beta_t = xs
        state = state * jnp.exp(g_t).astype(state.dtype)[:, None, None]
        read = jnp.einsum("hk,hkv->hv", k_t, state)
        delta = beta_t[:, None] * (v_t - read)
        state = state + k_t[:, :, None] * delta[:, None, :]
        return state, jnp.einsum("hk,hkv->hv", q_t, state)

    _, out = jax.lax.scan(
        jax.checkpoint(lambda state, xs: jax.lax.scan(step, state, xs)),
        jnp.zeros((heads, dk, dv), v.dtype),
        tuple(blocks(a) for a in (q, k, v, g, beta)))
    return out.reshape((-1, heads, dv))[:seq]


def gdn_mixer(x, p, cfg):
    seq = x.shape[0]
    q, k, v, g, beta, z = gdn_project(x, p, cfg)
    o = delta_rule(q, k, v, g, beta)
    o32 = o.astype(jnp.float32)
    o32 = o32 * jax.lax.rsqrt((o32 * o32).mean(-1, keepdims=True)
                              + cfg["rms_norm_eps"])
    o = (o32 * p["norm"].astype(jnp.float32)).astype(x.dtype) \
        * jax.nn.silu(z)
    return o.reshape(seq, -1) @ p["out_proj"]


def route(x, p, cfg):
    """(indices (N, k), weights (N, k)) of the router: softmax over all
    experts in float32, the k largest, renormalised to sum 1."""
    probs = jax.nn.softmax((x @ p["router"]).astype(jnp.float32), axis=-1)
    top, idx = jax.lax.top_k(probs, cfg["num_experts_per_tok"])
    if cfg["norm_topk_prob"]:
        top = top / top.sum(-1, keepdims=True)
    return idx, top


def moe(x, p, cfg, experts_held):
    """x (N, d). The held experts' part of the routed sum, as a loop over
    them, plus the shared expert behind its sigmoid gate."""
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
    shared = (jax.nn.silu(x @ p["shared_gate"]) * (x @ p["shared_up"])) \
        @ p["shared_down"]
    return routed + jax.nn.sigmoid(x @ p["shared_expert_gate"]) * shared


def layer_kinds(cfg):
    """The period's layer kinds, in their published order."""
    n = cfg["full_attention_interval"]
    return ["attention" if (i + 1) % n == 0 else "gdn" for i in range(n)]


def sequence_logits(weights, tokens, cfg, experts_held):
    """Logits (S, V) of one (S,) sequence over the vocabulary slice."""
    kinds = layer_kinds(cfg)
    eps = cfg["rms_norm_eps"]
    x = weights["embed"][tokens]

    def period(x, lps):
        for kind, lp in zip(kinds, lps):
            mixer = attention_mixer if kind == "attention" else gdn_mixer
            x = x + jax.checkpoint(lambda x, lp: mixer(
                rms_norm(x, lp["norm_in"], eps), lp["mixer"], cfg))(x, lp)
            x = x + jax.checkpoint(lambda x, lp: moe(
                rms_norm(x, lp["norm_post"], eps), lp["moe"], cfg,
                experts_held))(x, lp)
        return x, None

    x, _ = jax.lax.scan(period, x, weights["layers"])
    x = rms_norm(x, weights["final_norm"], eps)
    return x @ weights["head"].T


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
