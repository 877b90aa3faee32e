"""The state-space decoder family with shared arrays (kinds `mamba`,
`window_attention`, `memory_mamba`, `kv_attention`, `gmu`,
`cross_attention`: LayerNorm with bias, a Mamba selective-scan mixer,
differential attention under a sliding window or full causal, a Gated
Memory Unit, differential cross-attention, a dense SwiGLU, no positions, a
chunked head tied to the embedding), on (B, S, d) activations, as
`PipelinedLMTrainer` runs it. A model is a sequence of RUNS
(`lm_spec.Run`: a period of kinds repeated n times from a published layer
index on); `phi4flash_spec` reads a `phi4flash` config.json;
`benchmark/reference/phi4_flash.py` has the same equations in plain
float32. What a family supplies: docs/dnn.md "Model families".

A layer: h + mixer(ln_1(h)), then h + mlp(ln_2(h)).
  mamba        (u, z) = halves of x W_in; v = silu(conv_4(u) + b);
               (r, B, C) = v W_x; dt = softplus(r W_dt + b_dt);
               y = selective_scan(v, dt, -exp(A_log), B, C, D)
               (`ops/selective_scan.py`); out = (y * silu(z)) W_out
  memory_mamba the same, and y (before the gate) is the model's MEMORY
  gmu          out = (memory * silu(x W_1)) W_2
  attention    q, k, v = x W_qkv + b; query heads 2i, 2i + 1 and key heads
               2j, 2j + 1 (j = i // (heads / kv heads)) pair up, the pair's
               two value heads side by side are its V;
               O_i = F(q_2i, k_2j, V) - lam F(q_2i+1, k_2j+1, V), F one
               softmax attention, lam = exp(lq1.lk1) - exp(lq2.lk2) + lam0,
               lam0 = 0.8 - 0.6 exp(-0.3 l) at PUBLISHED layer index l;
               rms norm over V's width, * (1 - lam0); W_o + b_o.
               window_attention: key s visible to query t iff
               t - window < s <= t; kv_attention: full causal, and its k
               and v (after the bias) are kept for the layers after it
  cross_attention  q = x W_q + b of its own, the KV layer's k and v
  mlp          `moe.gated_mlp`: w2(silu(w1 x) * w3 x)

HOW A SHARED ARRAY TRAVELS. `stage` walks the runs in order; a run of one
repetition is called as it stands, a longer one is a `lax.scan` over its
stacked parameters. The memory and the kept k, v are plain values of
`stage`: the maker's mixer sublayer returns them beside h, a reader's takes
them as arguments (a scanned run closes over them, and the scan's backward
sums every repetition's cotangent). Under `remat` each sublayer is a
`jax.checkpoint` (`hybrid_layers.checkpoint_sublayers`): a reader is
recomputed from its input AND the shared arrays, which are therefore kept
from the forward pass; in the maker they are tagged `lm.shared` (and every
scan's output and boundary states `ssm.forward`), names the policy keeps,
so the maker's recomputation reads the forward pass's arrays and makes
none of them again. Reverse mode adds every reader's cotangent into the
maker's output. The trainer's carry between pipe stages
is ONE activation, so a model of this family is one period of all its runs
(`n_periods` 1) and trains on a pipe axis of one stage (`check`, and
ROADMAP R16).

Mixed precision as in the other families: matmul operands in the
activations' dtype with float32 accumulation; norms, the convolution's
sum, softplus, exp(dt A), the scan's state, both softmaxes, lam and the
sub-norm in float32.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from ...ops import embedding
from ...ops.selective_scan import selective_scan
from ...parallel import DATA_AXIS, PIPE_AXIS
from ...reliability.metrics import reliability_metrics
from ...telemetry import names as tnames
from .hybrid_layers import checkpoint_sublayers, chunked_loss, matmul
from .moe import gated_mlp
from .transformer import _layer_norm as layer_norm

# the mesh axes this family has a form for (the pipe axis at one stage)
AXES = (DATA_AXIS, PIPE_AXIS)
# what a stage counts for the host: nothing
STATS = ()
# leaves the per-step cast leaves in float32: vectors, the taps, the
# scan's own parameters
F32_LEAVES = frozenset({"scale", "bias", "conv_w", "conv_b", "dt_bias",
                        "A_log", "D", "lq1", "lk1", "lq2", "lk2", "subln",
                        "qkv_bias", "q_bias", "o_bias"})
MAMBAS = ("mamba", "memory_mamba")
SELF_ATTENTIONS = ("window_attention", "kv_attention")
KINDS = MAMBAS + SELF_ATTENTIONS + ("gmu", "cross_attention")
# what a reader reads, and the kind that makes it
READS = {"gmu": "memory_mamba", "cross_attention": "kv_attention"}
# differential attention's constants (arXiv:2410.05258): lam0 at layer l
_LAM0 = (0.8, 0.6, 0.3)
_LAMBDA_STD = 0.1


def check(spec) -> None:
    """The sizes these kinds need; every reader after its maker; one
    period of all the runs."""
    kinds = tuple(k for run in spec.runs for k in run.period * run.n)
    if not spec.runs or kinds != spec.period or spec.leading \
            or spec.period_ffn or any(run.n < 1 for run in spec.runs):
        raise ValueError(
            f"a state-space model is its runs ({spec.runs!r}), each "
            f"repeated at least once: `period` lists every layer's kind "
            f"({spec.period!r}) and leading, leading_ffn and period_ffn "
            f"stay empty")
    if spec.n_periods != 1:
        raise ValueError(
            f"n_periods is {spec.n_periods}: a model whose later layers "
            f"read arrays an earlier layer made is ONE period of all its "
            f"runs. The arrays would have to cross a pipe-stage boundary "
            f"with the activations, and the trainer's carry is one "
            f"activation, so it trains on a pipe axis of one stage")
    missing = [name for name, wanted, part in (
        ("mamba", set(kinds) & set(MAMBAS + ("gmu",)), spec.mamba),
        ("diff_attention",
         set(kinds) & set(SELF_ATTENTIONS + ("cross_attention",)),
         spec.diff_attention),
        ("d_ff", True, spec.d_ff)) if wanted and not part]
    if missing:
        raise ValueError(f"a state-space model needs its {missing} sizes")
    made = set()
    for run in spec.runs:
        for kind in run.period:
            if kind in READS and READS[kind] not in made:
                raise ValueError(
                    f"a {kind} layer reads what a {READS[kind]} layer "
                    f"made: none comes before it in {spec.runs!r}")
            if kind in READS.values():
                if run.n != 1 or kind in made:
                    raise ValueError(
                        f"one {kind} layer makes what later layers read: "
                        f"its run is repeated once, not {run!r}")
                made.add(kind)
    a = spec.diff_attention
    if a and (a.n_heads % 2 or a.n_kv_heads % 2
              or a.n_heads % a.n_kv_heads):
        raise ValueError(f"differential heads pair up: {a!r}")


def meta(spec) -> dict:
    """What a checkpoint must agree on to be resumed."""
    return {"d_model": spec.d_model,
            "runs": "; ".join(f"{'/'.join(r.period)} x{r.n}@{r.first}"
                              for r in spec.runs)}


def cast(p, dtype):
    return jax.tree_util.tree_map_with_path(
        lambda path, a: a if path[-1].key in F32_LEAVES else a.astype(dtype),
        p)


def embed(p, tokens, seq_off):
    """(mb, S) -> (mb, S, d): the lookup; the model has no positions."""
    return embedding.lookup(p["embed"], tokens)


def causal_conv(u, taps, bias):
    """silu(bias + sum_j taps[j] u[t - (width - 1) + j]) a channel on
    (B, S, C): causal, depthwise, zeros before the sequence; float32
    inside."""
    f32 = jnp.float32
    width, seq = taps.shape[0], u.shape[1]
    padded = jnp.pad(u.astype(f32), ((0, 0), (width - 1, 0), (0, 0)))
    conv = sum(padded[:, j:j + seq] * taps[j].astype(f32)
               for j in range(width))
    return jax.nn.silu(conv + bias.astype(f32)).astype(u.dtype)


def mamba_mixer(x, p, m):
    """The Mamba mixer on normed x (B, S, d) -> (out, the scan's output
    before its gate). `m`: the spec's Mamba sizes."""
    f32 = jnp.float32
    u, z = jnp.split(matmul(x, p["in_proj"]), 2, axis=-1)
    v = causal_conv(u, p["conv_w"], p["conv_b"])
    r, b, c = jnp.split(matmul(v, p["x_proj"]),
                        [m.dt_rank, m.dt_rank + m.d_state], axis=-1)
    dt = jax.nn.softplus(
        jnp.einsum("bsr,ri->bsi", r, p["dt_proj"], preferred_element_type=f32)
        + p["dt_bias"].astype(f32))
    with jax.named_scope(tnames.LM_SSM_SCAN):
        y = selective_scan(v, dt, -jnp.exp(p["A_log"].astype(f32)), b, c,
                           p["D"])
    gated = (y.astype(f32) * jax.nn.silu(z.astype(f32))).astype(x.dtype)
    return matmul(gated, p["out_proj"]), y


def gmu_mixer(x, p, memory):
    f32 = jnp.float32
    gate = jax.nn.silu(matmul(x, p["w1"]).astype(f32))
    return matmul((memory.astype(f32) * gate).astype(x.dtype), p["w2"])


def softmax_attention(q, k, v, attention: str, window):
    """Causal attention of q (B, S, H, D) over k (B, S, H, D) and
    v (B, S, H, Dv) -> (B, S, H, Dv); `window`: None, or how many positions
    back a query sees, its own included."""
    from ...ops.flash_attention import flash_attention
    b, s, h, _ = q.shape
    with jax.named_scope(tnames.LM_ATTN_FLASH):
        if attention == "flash":
            # the batch rides on the kernels' head axis, as in
            # `hybrid_layers.grouped_attention`
            def heads(t):
                return jnp.moveaxis(t, 0, 1).reshape(s, b * h, t.shape[-1])
            out = flash_attention(heads(q), heads(k), heads(v), causal=True,
                                  window=window)
            return jnp.moveaxis(out.reshape(s, b, h, -1), 0, 1)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                            preferred_element_type=jnp.float32) \
            * q.shape[-1] ** -0.5
        back = jnp.arange(s)[:, None] - jnp.arange(s)[None, :]
        seen = (back >= 0) if window is None else \
            (back >= 0) & (back < window)
        p = jax.nn.softmax(jnp.where(seen, scores, -1e30), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                          preferred_element_type=jnp.float32).astype(q.dtype)


def lam0_of(index):
    """0.8 - 0.6 exp(-0.3 l) at published layer index l (static or
    traced)."""
    top, fall, rate = _LAM0
    return top - fall * jnp.exp(-rate * jnp.asarray(index, jnp.float32))


def differential(q, k, v, p, lam0, eps: float, attention: str, window):
    """Differential attention of q (B, S, H, D) over k, v (B, S, KV, D) ->
    (B, S, H D): the two softmaxes of a pair through ONE attention call
    over H heads whose values are the pair's two heads side by side."""
    f32 = jnp.float32
    b, s, h, d = q.shape
    kv = k.shape[2]
    rep = h // kv
    # query head j: differential head j // 2, KV pair (j // 2) // rep; a
    # pair's keys and its V go to its 2 rep query heads by broadcast (a
    # gather's transpose would be a scatter-add over the slab)
    pairs = kv // 2
    k_heads = jnp.broadcast_to(k.reshape(b, s, pairs, 1, 2, d),
                               (b, s, pairs, rep, 2, d)).reshape(b, s, h, d)
    v_heads = jnp.repeat(v.reshape(b, s, pairs, 2 * d), 2 * rep, axis=2)
    out = softmax_attention(q, k_heads, v_heads, attention, window
                            ).astype(f32).reshape(b, s, h // 2, 2, 2 * d)
    lam = jnp.exp(jnp.sum(p["lq1"] * p["lk1"])) \
        - jnp.exp(jnp.sum(p["lq2"] * p["lk2"])) + lam0
    o = out[:, :, :, 0] - lam.astype(f32) * out[:, :, :, 1]
    o = o * jax.lax.rsqrt((o * o).mean(-1, keepdims=True) + eps) \
        * p["subln"].astype(f32) * (1.0 - lam0)
    return o.astype(q.dtype).reshape(b, s, h * d)


def attention_mixer(x, p, a, lam0, eps: float, attention: str, window,
                    kept=None):
    """Differential attention on normed x (B, S, d) -> (out, (k, v)).
    `kept`: another layer's (k, v) to attend over (cross-attention: `p`
    has a query projection only), else this layer's own."""
    b, s, _ = x.shape
    h, kv, d = a.n_heads, a.n_kv_heads, a.head_dim
    if kept is None:
        w, bias = p["qkv_proj"], p["qkv_bias"].astype(x.dtype)
        # split at the weights: q, and k and v, are slabs of their own
        q = matmul(x, w[:, :h * d]) + bias[:h * d]
        k, v = jnp.split(matmul(x, w[:, h * d:]) + bias[h * d:], 2, axis=-1)
        kept = (k.reshape(b, s, kv, d), v.reshape(b, s, kv, d))
    else:
        q = matmul(x, p["q_proj"]) + p["q_bias"].astype(x.dtype)
    out = differential(q.reshape(b, s, h, d), *kept, p, lam0, eps,
                       attention, window)
    return matmul(out, p["o_proj"]) + p["o_bias"].astype(x.dtype), kept


def layer(h, lp, kind: str, index, shared: dict, spec, attention: str,
          remat):
    """One layer on h (B, S, d) at published index `index` -> (h, what it
    made for later layers: {} | {"memory"} | {"k", "v"}). `shared`: what
    the layers before it made."""
    eps = spec.norm_eps
    a = spec.diff_attention

    def mix(h, lp, index, shared):
        x = layer_norm(h, lp["ln_1"], eps)
        made = {}
        if kind in MAMBAS:
            with jax.named_scope(tnames.LM_SSM):
                out, y = mamba_mixer(x, lp["mixer"], spec.mamba)
                if kind == "memory_mamba":
                    made = {"memory": checkpoint_name(y, tnames.KEEP_SHARED)}
                return h + out, made
        if kind == "gmu":
            with jax.named_scope(tnames.LM_GMU):
                return h + gmu_mixer(x, lp["mixer"], shared["memory"]), made
        with jax.named_scope(tnames.LM_ATTN):
            kept = (shared["k"], shared["v"]) \
                if kind == "cross_attention" else None
            out, (k, v) = attention_mixer(
                x, lp["mixer"], a, lam0_of(index), eps, attention,
                a.window if kind == "window_attention" else None, kept)
            if kind == "kv_attention":
                k, v = checkpoint_name((k, v), tnames.KEEP_SHARED)
                made = {"k": k, "v": v}
            return h + out, made

    def feed(h, lp):
        with jax.named_scope(tnames.LM_MLP):
            m = lp["mlp"]
            return h + gated_mlp(layer_norm(h, lp["ln_2"], eps), m["w1"],
                                 m["w3"], m["w2"])

    mix, feed = checkpoint_sublayers(
        mix, feed, remat, flash=kind not in MAMBAS + ("gmu",)
        and attention == "flash", routing=False)
    h, made = mix(h, lp, index, {k: shared[k] for k in (
        ("memory",) if kind == "gmu" else
        ("k", "v") if kind == "cross_attention" else ())})
    return feed(h, lp), made


def stage(x, layers, spec, attention: str, remat, tp_axis=None,
          cp_axis=None):
    """(mb, S, d) through the description's runs -> (x, no stats).
    `layers`: a run's layers by position in its period, leaves stacked
    (n, ...). The shared arrays are values of this function (module
    docstring); `remat` as `hybrid_layers.checkpoint_sublayers` says."""
    shared = {}
    for run, run_layers in zip(spec.runs, layers):
        readers = sum(kind in READS for kind in run.period)
        if readers:
            reliability_metrics.inc(tnames.LM_SHARED_READERS,
                                    readers * run.n)

        def one_period(h, xs, run=run):
            i, lps = xs
            made = {}
            for pos, (kind, lp) in enumerate(zip(run.period, lps)):
                h, new = layer(h, lp, kind,
                               run.first + i * len(run.period) + pos,
                               {**shared, **made}, spec, attention, remat)
                made.update(new)
            return h, made

        if run.n == 1:
            x, made = one_period(x, (0, jax.tree_util.tree_map(
                lambda leaf: leaf[0], run_layers)))
            shared.update(made)
        else:
            x, _ = jax.lax.scan(one_period, x,
                                (jnp.arange(run.n), run_layers))
    return x, STATS


def head_loss(p, y, targets, mask, spec):
    """The final LayerNorm and the head TIED to the embedding on the last
    stage's (mb, S, d), through the hybrid family's `chunked_loss`; the
    log-probabilities as `shortconv_layers.head_loss` makes them (no
    `log_softmax`: PERF.md section 6, PR 32)."""
    def log_probs_of(y_c):
        z = layer_norm(y_c, p["final_norm"], spec.norm_eps)
        logits = jnp.einsum("msd,vd->msv", z, p["embed"],
                            preferred_element_type=jnp.float32)
        return logits - jax.nn.logsumexp(logits, axis=-1, keepdims=True)
    return chunked_loss(y, targets, mask, log_probs_of)


def summary(stats) -> list:
    """What of a step's stats leaves the program with the loss: nothing."""
    return []


def report(values) -> None:
    """Nothing reaches the host but the loss."""


def init(spec, seed: int) -> dict:
    """Seeded host weights: normal(0, init_std) matrices and taps, biases
    at 0, norms at (1, 0), the sub-norm at 1, the four lambda vectors
    normal(0, 0.1); Mamba's own as Mamba-1 draws them: A_log = log(1..N) a
    channel, D = 1, dt_bias the inverse softplus of a step log-uniform in
    [1e-3, 1e-1], dt_proj uniform in +-dt_rank^-0.5 (a configuration file
    lists them as assumed)."""
    rng = np.random.default_rng(seed)
    d, std = spec.d_model, spec.init_std
    f32 = np.float32

    def dense(*shape):
        return rng.standard_normal(shape, dtype=f32) * f32(std)

    def norm():
        return {"scale": np.ones(d, f32), "bias": np.zeros(d, f32)}

    def lambdas(a):
        return {name: rng.standard_normal(a.head_dim, dtype=f32)
                * f32(_LAMBDA_STD) for name in ("lq1", "lk1", "lq2", "lk2")}

    def mixer(kind):
        m, a = spec.mamba, spec.diff_attention
        if kind in MAMBAS:
            step = np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), m.d_inner))
            return {"in_proj": dense(d, 2 * m.d_inner),
                    "conv_w": dense(m.conv_width, m.d_inner),
                    "conv_b": np.zeros(m.d_inner, f32),
                    "x_proj": dense(m.d_inner, m.dt_rank + 2 * m.d_state),
                    "dt_proj": rng.uniform(
                        -m.dt_rank ** -0.5, m.dt_rank ** -0.5,
                        (m.dt_rank, m.d_inner)).astype(f32),
                    "dt_bias": (step + np.log(-np.expm1(-step))).astype(f32),
                    "A_log": np.log(np.broadcast_to(
                        np.arange(1, m.d_state + 1, dtype=f32),
                        (m.d_inner, m.d_state))).copy(),
                    "D": np.ones(m.d_inner, f32),
                    "out_proj": dense(m.d_inner, d)}
        if kind == "gmu":
            return {"w1": dense(d, m.d_inner), "w2": dense(m.d_inner, d)}
        hq, hkv = a.n_heads * a.head_dim, a.n_kv_heads * a.head_dim
        proj = {"q_proj": dense(d, hq), "q_bias": np.zeros(hq, f32)} \
            if kind == "cross_attention" else \
            {"qkv_proj": dense(d, hq + 2 * hkv),
             "qkv_bias": np.zeros(hq + 2 * hkv, f32)}
        return {**proj, "o_proj": dense(hq, d), "o_bias": np.zeros(d, f32),
                **lambdas(a), "subln": np.ones(2 * a.head_dim, f32)}

    def one(kind):
        return {"ln_1": norm(), "ln_2": norm(), "mixer": mixer(kind),
                "mlp": {"w1": dense(d, spec.d_ff), "w3": dense(d, spec.d_ff),
                        "w2": dense(spec.d_ff, d)}}

    def stacked(kind, n):
        return jax.tree_util.tree_map(lambda *xs: np.stack(xs),
                                      *[one(kind) for _ in range(n)])

    return {"embed": dense(spec.vocab_size, d), "final_norm": norm(),
            "layers": [[stacked(kind, run.n) for kind in run.period]
                       for run in spec.runs]}
