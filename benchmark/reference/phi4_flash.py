"""Plain reference of the `phi-4-mini-flash-reasoning` configuration: the
forward pass, next-token loss and gradients of the decoder-hybrid-decoder
("SambaY", arXiv:2507.06607; Hugging Face `phi4flash`) in straightforward
`jax.numpy`, float32 under `default_matmul_precision("highest")`. No
kernels, no tiles, no mixed precision; independent of the package (imports
jax only).

It runs in blocks so that the gradient of an 8,192-token sequence at the
published widths fits on one chip beside a trainer: one sequence at a
time, `jax.checkpoint` round each sublayer and block of queries, the scan
a `lax.scan` over positions in checkpointed segments. That changes what
the backward pass keeps and nothing that is computed.

    ln(x; w, b)  = (x - mean) * rsqrt(var + eps) * w + b         (float32)
    layer l      : h = h + mixer_l(ln_1(h)); h = h + mlp(ln_2(h))
    mlp          : (u * silu(g)) W_down, g = x w1, u = x w3
    logits       : ln(h; final) @ embed^T      (tied; no positions at all)

The mixer of published layer l of n (`kind_of`): l < n / 2: Mamba (l even)
or differential attention under a sliding window (l odd); l = n / 2: Mamba
whose scan output before its gate is also the MEMORY; l = n / 2 + 1: full
causal differential attention whose k, v are kept; after them a Gated
Memory Unit on the memory (l even) or differential cross-attention over
the kept k, v (l odd).

    mamba        : (u, z) = halves of x W_in;
                   c[t] = b + sum_{j<4} taps[j] u[t - 3 + j] a channel
                   (four shifted adds, zeros before the sequence);
                   v = silu(c); (r, B, C) = split(v W_x);
                   dt = softplus(r W_dt + b_dt); A = -exp(A_log);
                   s_t = exp(dt_t A) s_{t-1} + dt_t v_t B_t, s_{-1} = 0
                   (state (channels, N), a loop over positions);
                   y_t = s_t C_t + D v_t; out = (y * silu(z)) W_out
    gmu          : (memory * silu(x W_1)) W_2
    attention    : q, k, v = x W_qkv + b (cross: q = x W_q + b, the KV
                   layer's k, v); differential head i of H / 2 reads query
                   heads 2i, 2i + 1, key heads 2j, 2j + 1 and V = value
                   heads 2j, 2j + 1 side by side, j = i // (H / KV);
                   O_i = (softmax(q1 k1^T / sqrt(D) + mask)
                          - lam softmax(q2 k2^T / sqrt(D) + mask)) V, the
                   two softmaxes computed apart;
                   lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam0,
                   lam0 = 0.8 - 0.6 exp(-0.3 l);
                   O_i = rms(O_i; subln, eps) (1 - lam0); concat; W_o + b.
                   mask: causal, and on a window layer key s is visible to
                   query t iff t - window < s <= t

The chip's share (the configuration file says of which deployment): the
published layers `held_layers` = [first, last], each keeping its published
index, and a vocabulary slice, which is a smaller vocabulary.

`dtype` exists for one purpose: the benchmark's calibration reads what
this reference gives when everything is computed in bfloat16, which has to
fail the cell's limits (PERF.md). The reference proper is float32.

Weights: {"embed" (V, d), "final_norm": {"scale", "bias"}, "layers": [a
run of layers: [one dict per position of the run's period, leaves stacked
over the run's repetitions (n, ...)]]}, each layer {"ln_1", "ln_2",
"mixer": {...}, "mlp": {"w1", "w3", "w2"}} with the names of `mamba`,
`gmu` and `attention` below. Departures from the published weights' layout
(none changes the function for seeded weights): `conv_w` is (width,
channels); `w1`, `w3` are the two halves of the published `gate_up_proj`.
"""
import math

import jax
import jax.numpy as jnp

LAM0 = (0.8, 0.6, 0.3)
SCAN_SEGMENT = 256


def layer_norm(x, p, eps):
    x32 = x.astype(jnp.float32)
    mean = x32.mean(-1, keepdims=True)
    var = ((x32 - mean) ** 2).mean(-1, keepdims=True)
    y = (x32 - mean) * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32)
            + p["bias"].astype(jnp.float32)).astype(x.dtype)


def kind_of(index, cfg):
    n, per = cfg["num_hidden_layers"], cfg["mb_per_layer"]
    ssm = index % per == 0
    if index < n // 2:
        return "mamba" if ssm else "window_attention"
    if index < n // 2 + per:
        return "memory_mamba" if ssm else "kv_attention"
    return "gmu" if ssm else "cross_attention"


def layers_held(cfg):
    """The published indices of the layers held here."""
    first, last = cfg.get("held_layers", (0, cfg["num_hidden_layers"] - 1))
    return list(range(first, last + 1))


def mamba_sizes(cfg):
    """(channels, states a channel, the step's rank, taps): the file's, at
    its top level or under `assumed`, else the Mamba-1 defaults."""
    def size(key, default):
        return cfg.get(key, cfg.get("assumed", {}).get(key, default))
    d = cfg["hidden_size"]
    return (size("mamba_expand", 2) * d, size("mamba_d_state", 16),
            size("mamba_dt_rank", -(-d // 16)), size("mamba_d_conv", 4))


def positional_scan(v, dt, a, b, c, d_skip):
    """The recurrence as a loop over positions, the (channels, N) state
    its carry, in float32; segments are checkpointed so the backward pass
    keeps one state a segment. v, dt (S, Di); a (Di, N); b, c (S, N)."""
    f32 = jnp.float32
    seq = v.shape[0]
    pad = (-seq) % SCAN_SEGMENT
    xs = tuple(jnp.pad(t.astype(f32), ((0, pad), (0, 0))).reshape(
        -1, SCAN_SEGMENT, t.shape[-1]) for t in (v, dt, b, c))

    def step(state, x):
        v_t, dt_t, b_t, c_t = x
        state = jnp.exp(dt_t[:, None] * a) * state \
            + (dt_t * v_t)[:, None] * b_t[None, :]
        return state, (state * c_t[None, :]).sum(-1)

    @jax.checkpoint
    def segment(state, x):
        return jax.lax.scan(step, state, x)

    _, y = jax.lax.scan(segment, jnp.zeros(a.shape, f32), xs)
    return y.reshape(-1, v.shape[-1])[:seq] + d_skip.astype(f32) * v


def mamba(x, p, cfg):
    """-> (out (S, d), the scan's output before its gate (S, Di))."""
    _, n_state, rank, width = mamba_sizes(cfg)
    seq = x.shape[0]
    u, z = jnp.split(x @ p["in_proj"], 2, axis=-1)
    padded = jnp.pad(u, ((width - 1, 0), (0, 0)))
    conv = sum(padded[j:j + seq] * p["conv_w"][j] for j in range(width))
    v = jax.nn.silu(conv + p["conv_b"])
    r, b, c = jnp.split(v @ p["x_proj"], [rank, rank + n_state], axis=-1)
    dt = jax.nn.softplus((r @ p["dt_proj"]).astype(jnp.float32)
                         + p["dt_bias"].astype(jnp.float32))
    y = positional_scan(v, dt, -jnp.exp(p["A_log"].astype(jnp.float32)),
                        b, c, p["D"]).astype(x.dtype)
    return (y * jax.nn.silu(z)) @ p["out_proj"], y


def gmu(x, p, memory):
    return (memory * jax.nn.silu(x @ p["w1"])) @ p["w2"]


def attend(q, k, v, window, q_block=512):
    """softmax(q k^T / sqrt(D) + mask) v for q, k (H, S, D), v (H, S, Dv),
    a block of queries at a time, the window as a mask."""
    h, seq, d = q.shape
    q_block = min(q_block, seq)
    pad = (-seq) % q_block
    qb = jnp.pad(q, ((0, 0), (0, pad), (0, 0))).reshape(
        h, -1, q_block, d).transpose(1, 0, 2, 3)        # (nb, H, qb, D)
    starts = jnp.arange(qb.shape[0]) * q_block
    kt = k.transpose(0, 2, 1)

    @jax.checkpoint
    def one_block(args):
        qs, start = args
        scores = (qs @ kt).astype(jnp.float32) / math.sqrt(d)
        rows = start + jnp.arange(q_block)[:, None]
        cols = jnp.arange(seq)[None, :]
        seen = cols <= rows
        if window is not None:
            seen = seen & (cols > rows - window)
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), -1)
        return probs.astype(v.dtype) @ v                # (H, qb, Dv)

    out = jax.lax.map(one_block, (qb, starts))          # (nb, H, qb, Dv)
    return out.transpose(1, 0, 2, 3).reshape(h, -1, v.shape[-1])[:, :seq]


def attention(x, p, cfg, index, window, kept=None):
    """Differential attention of published layer `index` -> (out (S, d),
    (k, v) as (S, KV, D)). `kept`: the KV layer's (k, v) for a
    cross-attention layer, whose `p` has a query projection only."""
    f32 = jnp.float32
    seq = x.shape[0]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg["hidden_size"] // h
    if kept is None:
        qkv = x @ p["qkv_proj"] + p["qkv_bias"].astype(x.dtype)
        q, k, v = jnp.split(qkv, [h * d, (h + kv) * d], axis=-1)
        kept = (k.reshape(seq, kv, d), v.reshape(seq, kv, d))
    else:
        q = x @ p["q_proj"] + p["q_bias"].astype(x.dtype)
    k, v = kept
    q = q.reshape(seq, h // 2, 2, d)                    # (S, i, 1 | 2, D)
    pair = jnp.arange(h // 2) // (h // kv)              # j of head i
    k = k.reshape(seq, kv // 2, 2, d)[:, pair]          # (S, i, 1 | 2, D)
    v = v.reshape(seq, kv // 2, 2 * d)[:, pair]         # (S, i, 2D)
    v = v.transpose(1, 0, 2)
    first = attend(q[:, :, 0].transpose(1, 0, 2),
                   k[:, :, 0].transpose(1, 0, 2), v, window)
    second = attend(q[:, :, 1].transpose(1, 0, 2),
                    k[:, :, 1].transpose(1, 0, 2), v, window)
    lam0 = LAM0[0] - LAM0[1] * math.exp(-LAM0[2] * index)
    lam = jnp.exp(jnp.sum(p["lq1"].astype(f32) * p["lk1"].astype(f32))) \
        - jnp.exp(jnp.sum(p["lq2"].astype(f32) * p["lk2"].astype(f32))) \
        + lam0
    o = first.astype(f32) - lam * second.astype(f32)    # (i, S, 2D)
    o = o * jax.lax.rsqrt((o * o).mean(-1, keepdims=True)
                          + cfg["layer_norm_eps"]) \
        * p["subln"].astype(f32) * (1.0 - lam0)
    o = o.astype(x.dtype).transpose(1, 0, 2).reshape(seq, h * d)
    return o @ p["o_proj"] + p["o_bias"].astype(x.dtype), kept


def mlp(x, p):
    return (jax.nn.silu(x @ p["w1"]) * (x @ p["w3"])) @ p["w2"]


def layers_of(weights):
    """The held layers' parameter dicts in order, unstacked."""
    out = []
    for run in weights["layers"]:
        n = jax.tree_util.tree_leaves(run[0])[0].shape[0]
        for i in range(n):
            out.extend(jax.tree_util.tree_map(lambda a: a[i], lp)
                       for lp in run)
    return out


def sequence_logits(weights, tokens, cfg):
    """Logits (S, V) of one (S,) sequence over the vocabulary slice; the
    memory and the kept k, v are plain Python values."""
    eps = cfg["layer_norm_eps"]
    x = weights["embed"][tokens]
    memory = kept = None
    for index, lp in zip(layers_held(cfg), layers_of(weights)):
        kind = kind_of(index, cfg)
        y = layer_norm(x, lp["ln_1"], eps)
        if kind in ("mamba", "memory_mamba"):
            out, scanned = jax.checkpoint(
                lambda y, p: mamba(y, p, cfg))(y, lp["mixer"])
            if kind == "memory_mamba":
                memory = scanned
        elif kind == "gmu":
            out = jax.checkpoint(gmu)(y, lp["mixer"], memory)
        elif kind == "cross_attention":
            out, _ = jax.checkpoint(
                lambda y, p, kept, index=index: attention(
                    y, p, cfg, index, None, kept))(y, lp["mixer"], kept)
        else:
            window = cfg["sliding_window"] \
                if kind == "window_attention" else None
            out, made = jax.checkpoint(
                lambda y, p, index=index, window=window: attention(
                    y, p, cfg, index, window))(y, lp["mixer"])
            if kind == "kv_attention":
                kept = made
        x = x + out
        x = x + jax.checkpoint(mlp)(layer_norm(x, lp["ln_2"], eps),
                                    lp["mlp"])
    x = layer_norm(x, weights["final_norm"], eps)
    return x @ weights["embed"].T


def sequence_loss(weights, tokens, cfg):
    """Summed next-token negative log-likelihood of one (S,) sequence over
    its S - 1 predicted positions."""
    logits = sequence_logits(weights, tokens, cfg)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.take_along_axis(logp[:-1], tokens[1:, None], axis=-1).sum()


def _cast(weights, dtype):
    return jax.tree_util.tree_map(lambda a: jnp.asarray(a, dtype), weights)


def loss_and_grads(weights, tokens, cfg, dtype=jnp.float32, pick=None):
    """(mean loss of (B, S) tokens, its gradient in float32), a sequence at
    a time. `pick` maps a tree shaped like `weights` to the pytree of its
    leaves to differentiate (default: all of them); the gradient comes back
    in that pytree's shape. `dtype` bfloat16: the calibration's second
    entry, every leaf and product in bfloat16."""
    flat, treedef = jax.tree_util.tree_flatten(weights)
    picked = (pick or (lambda tree: tree))(
        treedef.unflatten(list(range(len(flat)))))
    places = jax.tree_util.tree_leaves(picked)

    def one_sequence(chosen, flat, seq_tokens):
        flat = list(flat)
        for i, leaf in zip(places, jax.tree_util.tree_leaves(chosen)):
            flat[i] = leaf
        return sequence_loss(_cast(treedef.unflatten(flat), dtype),
                             seq_tokens, cfg)

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
