"""The short-convolution decoder family (kinds `conv`, `full_attention`:
plain RMSNorm, a gated short convolution, grouped-KV attention with
per-head q/k norms and rotary embedding over the whole head, a dense SwiGLU
or sigmoid-routed experts with no shared expert, leading layers before the
periods, a chunked head tied to the embedding), on (B, S, d) activations,
as `PipelinedLMTrainer` runs it. `lm_spec.py` says which kinds a model is
made of (`lfm2_moe_spec` reads an `lfm2_moe` config.json);
`benchmark/reference/lfm2_moe.py` has the same equations in plain float32.
What a family supplies: docs/dnn.md "Model families".

A layer: h + mixer(operator_norm(h)), then h + ffn(ffn_norm(h)).
  conv mixer   (B, C, u) = thirds of x W_in; v = B * u;
               c[t] = k0 v[t-2] + k1 v[t-1] + k2 v[t] a channel (causal,
               zeros before the sequence); y = (C * c) W_out
  attention    q, k, v projections, RMSNorm over each head of q and of k,
               rotary on the whole head, causal softmax at head_dim^-0.5,
               query head j reads KV head j // (heads / kv heads); W_o
  dense ffn    w2(silu(w1 x) * w3 x)
  experts      `moe.moe_layer` with the description's scoring rule

The LEADING layers are replicated entries of the parameter tree (not
stacked, so the pipe axis does not shard them) and `embed` runs them after
the lookup: the trainer calls `embed` on the first pipe stage only and sums
every replicated entry's gradient over the pipe axis, which is all a
leading layer needs of it. `embed` gets neither the trainer's `remat` nor
its `attention`, so a leading layer's sublayers are always recomputed in
the backward pass and its mixer is the convolution; it counts nothing for
the host, so its feed-forward is the dense one (`check`).

Mixed precision as in the other families: matmul operands in the
activations' dtype with float32 accumulation; norms, rotary angles, the
gate pass (B * u, the taps, C *) and every softmax in float32.
"""
from __future__ import annotations

import sys

import jax
import jax.numpy as jnp
import numpy as np

from ...ops import embedding
from ...parallel import DATA_AXIS, PIPE_AXIS
from ...telemetry import names as tnames
from .hybrid_layers import (STATS, check_experts, checkpoint_sublayers,
                            chunked_loss, grouped_attention, matmul, report,
                            rotary, summary)
from .moe import gated_mlp, moe_layer

__all__ = ["AXES", "STATS", "bound", "cast", "check", "embed", "head_loss",
           "init", "meta", "report", "stage", "summary"]

# the mesh axes this family has a form for
AXES = (DATA_AXIS, PIPE_AXIS)
# leaves the per-step cast leaves in float32: vectors and the taps
F32_LEAVES = frozenset({"operator_norm", "ffn_norm", "final_norm",
                        "q_layernorm", "k_layernorm", "taps", "expert_bias"})
FFNS = ("dense", "experts")


def check(spec) -> None:
    """The sizes these kinds need, and what a leading layer can be."""
    if len(spec.period_ffn) != len(spec.period) \
            or len(spec.leading_ffn) != len(spec.leading) \
            or not set(spec.period_ffn + spec.leading_ffn) <= set(FFNS):
        raise ValueError(
            f"period_ffn {spec.period_ffn!r} and leading_ffn "
            f"{spec.leading_ffn!r} give each layer of period {spec.period!r}"
            f" and leading {spec.leading!r} one of {' | '.join(FFNS)}")
    kinds = spec.leading + spec.period
    ffns = spec.leading_ffn + spec.period_ffn
    missing = [name for name, wanted, part in (
        ("short_conv", "conv" in kinds, spec.short_conv),
        ("attention", "full_attention" in kinds, spec.attention),
        ("d_ff", "dense" in ffns, spec.d_ff),
        ("experts", "experts" in ffns, spec.experts)) if wanted and not part]
    if missing:
        raise ValueError(f"a short-convolution model needs its {missing} "
                         f"sizes")
    if set(spec.leading) - {"conv"} or set(spec.leading_ffn) - {"dense"}:
        raise ValueError(
            f"a leading layer is a conv mixer with a dense feed-forward "
            f"(the embedding runs it, with no attention route and nothing "
            f"counted), not {spec.leading!r} with {spec.leading_ffn!r}")
    if "experts" in ffns:
        check_experts(spec.experts)
        if spec.experts.shared_width:
            raise ValueError("a short-convolution model's expert layer has "
                             "no shared expert")


def meta(spec) -> dict:
    """What a checkpoint must agree on to be resumed."""
    def layers(kinds, ffns):
        return "/".join(f"{k}+{f}" for k, f in zip(kinds, ffns))
    return {"d_model": spec.d_model,
            "leading": layers(spec.leading, spec.leading_ffn),
            "period": layers(spec.period, spec.period_ffn),
            "n_periods": spec.n_periods,
            "experts_held": list(spec.experts.held) if spec.experts else []}


class _Bound:
    """This module as the trainer sees it for ONE description. The trainer
    hands `embed` no description, and the leading layers that `embed` runs
    need one; everything else is the module's own."""

    def __init__(self, spec):
        self.spec = spec
        self.__name__ = __name__

    def __getattr__(self, name):
        return getattr(sys.modules[__name__], name)

    def embed(self, p, tokens, seq_off):
        return embed(p, tokens, seq_off, self.spec)


def bound(spec):
    return _Bound(spec)


def cast(p, dtype):
    return jax.tree_util.tree_map_with_path(
        lambda path, a: a if path[-1].key in F32_LEAVES else a.astype(dtype),
        p)


def rms_norm(x, w, eps: float):
    """x * rsqrt(mean(x^2) + eps) * w, float32 inside."""
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt((x32 * x32).mean(-1, keepdims=True) + eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


def conv_gate(b, c, u, taps):
    """The gate pass of the short convolution on (B, S, d) slabs:
    C * conv(B * u), the convolution causal and depthwise over `taps`
    (width, d), the newest position's tap last. float32 inside, one
    rounding at the end."""
    f32 = jnp.float32
    with jax.named_scope(tnames.LM_CONV_GATE):
        width, seq = taps.shape[0], u.shape[1]
        v = jnp.pad(b.astype(f32) * u.astype(f32),
                    ((0, 0), (width - 1, 0), (0, 0)))
        conv = sum(v[:, j:j + seq] * taps[j].astype(f32)
                   for j in range(width))
        return (c.astype(f32) * conv).astype(u.dtype)


def conv_mixer(x, p):
    """The gated short convolution on normed x (B, S, d)."""
    b, c, u = jnp.split(matmul(x, p["in_proj"]), 3, axis=-1)
    return matmul(conv_gate(b, c, u, p["taps"]), p["out_proj"])


def attention_mixer(x, p, a, eps: float, attention: str):
    """Grouped-KV attention on normed x (B, S, d). `a`: the spec's
    attention sizes."""
    b, s, _ = x.shape
    h, kv, d = a.n_heads, a.n_kv_heads, a.head_dim
    q = matmul(x, p["q_proj"]).reshape(b, s, h, d)
    k = matmul(x, p["k_proj"]).reshape(b, s, kv, d)
    v = matmul(x, p["v_proj"]).reshape(b, s, kv, d)
    q = rotary(rms_norm(q, p["q_layernorm"], eps), a.rope_theta,
               a.rotary_dim)
    k = rotary(rms_norm(k, p["k_layernorm"], eps), a.rope_theta,
               a.rotary_dim)
    out = grouped_attention(q, k, v, attention)
    return matmul(out.reshape(b, s, h * d), p["o_proj"])


def layer(h, lp, kind: str, ffn: str, spec, attention: str, remat):
    """One layer on h (B, S, d) -> (h, the expert layer's stats or
    None)."""
    eps = spec.norm_eps

    def mix(h, lp):
        if kind == "full_attention":
            with jax.named_scope(tnames.LM_ATTN):
                return h + attention_mixer(
                    rms_norm(h, lp["operator_norm"], eps), lp["mixer"],
                    spec.attention, eps, attention)
        with jax.named_scope(tnames.LM_CONV):
            return h + conv_mixer(rms_norm(h, lp["operator_norm"], eps),
                                  lp["mixer"])

    def dense(h, lp):
        with jax.named_scope(tnames.LM_MLP):
            m = lp["mlp"]
            return h + gated_mlp(rms_norm(h, lp["ffn_norm"], eps), m["w1"],
                                 m["w3"], m["w2"]), None

    def experts(h, lp):
        e = spec.experts
        with jax.named_scope(tnames.LM_MOE_ROUTER):
            y = rms_norm(h, lp["ffn_norm"], eps)
        out, stats = moe_layer(y.reshape(-1, y.shape[-1]), lp["moe"],
                               e.top_k, e.held, e.renormalize, e.scoring,
                               e.scale)
        with jax.named_scope(tnames.LM_MOE_EXPERTS):
            return h + out.reshape(h.shape), stats

    feed = dense if ffn == "dense" else experts
    mix, feed = checkpoint_sublayers(
        mix, feed, remat,
        flash=kind == "full_attention" and attention == "flash",
        routing=ffn == "experts")
    return feed(mix(h, lp), lp)


def embed(p, tokens, seq_off, spec):
    """(mb, S) -> (mb, S, d): the lookup (positions are rotary, inside
    attention), then the leading layers."""
    h = embedding.lookup(p["embed"], tokens)
    for kind, ffn, lp in zip(spec.leading, spec.leading_ffn, p["leading"]):
        h, _ = layer(h, lp, kind, ffn, spec, "dense", True)
    return h


def stage(x, layers, spec, attention: str, remat, tp_axis=None,
          cp_axis=None):
    """(mb, S, d) through this stage's periods -> (x, `STATS`). `remat`:
    what the backward pass recomputes of each sublayer
    (`hybrid_layers.checkpoint_sublayers`)."""
    def one_period(h_x, lps):
        stats = jnp.zeros(STATS.shape, STATS.dtype)
        for kind, ffn, lp in zip(spec.period, spec.period_ffn, lps):
            h_x, counted = layer(h_x, lp, kind, ffn, spec, attention,
                                 remat)
            if counted is not None:
                stats = stats + jnp.concatenate([counted,
                                                 jnp.ones((1,), stats.dtype)])
        return h_x, stats
    x, stats = jax.lax.scan(one_period, x, layers)
    return x, stats.sum(0)


def head_loss(p, y, targets, mask, spec):
    """The final norm and the head TIED to the embedding on the last
    stage's (mb, S, d), through the hybrid family's `chunked_loss`: the
    embedding's gradient is the sum of its two uses.

    The log-probabilities are logits - logsumexp(logits), not
    `jax.nn.log_softmax`: at a vocabulary of 8,192 or fewer the v5e's
    compiler turns log_softmax's row maximum into a `reduce-window` 16,383
    wide over the row, and the chunk's exp-and-sum then takes 23.6 ms where
    the bytes ask for 0.4 (PERF.md section 6, PR 32: 189 ms of an 822 ms
    step). The same numbers either way."""
    def log_probs_of(y_c):
        z = rms_norm(y_c, p["final_norm"], spec.norm_eps)
        logits = jnp.einsum("msd,vd->msv", z, p["embed"],
                            preferred_element_type=jnp.float32)
        return logits - jax.nn.logsumexp(logits, axis=-1, keepdims=True)
    return chunked_loss(y, targets, mask, log_probs_of)


# the selection bias is drawn, not learned here: small against the spread
# of seeded sigmoid scores (0.5 +- 0.2), large enough to change some picks
_EXPERT_BIAS_STD = 0.01


def init(spec, seed: int) -> dict:
    """Seeded host weights: normal(0, init_std) matrices, norms at 1, taps
    normal(0, width^-0.5), `expert_bias` normal(0, 0.01) (the family's
    habit; a configuration file lists them as assumed)."""
    rng = np.random.default_rng(seed)
    d, std = spec.d_model, spec.init_std

    def dense(*shape):
        return rng.standard_normal(shape, dtype=np.float32) * np.float32(std)

    def ones(*shape):
        return np.ones(shape, np.float32)

    def mixer(kind):
        if kind == "full_attention":
            a = spec.attention
            return {"q_proj": dense(d, a.n_heads * a.head_dim),
                    "k_proj": dense(d, a.n_kv_heads * a.head_dim),
                    "v_proj": dense(d, a.n_kv_heads * a.head_dim),
                    "q_layernorm": ones(a.head_dim),
                    "k_layernorm": ones(a.head_dim),
                    "o_proj": dense(a.n_heads * a.head_dim, d)}
        width = spec.short_conv.width
        return {"in_proj": dense(d, 3 * d),
                "taps": rng.standard_normal((width, d), dtype=np.float32)
                * np.float32(width ** -0.5),
                "out_proj": dense(d, d)}

    def feed(ffn):
        if ffn == "dense":
            return {"mlp": {"w1": dense(d, spec.d_ff),
                            "w3": dense(d, spec.d_ff),
                            "w2": dense(spec.d_ff, d)}}
        e = spec.experts
        n = e.held[1] - e.held[0]
        moe = {"router": dense(d, e.n_experts),
               "w_gate": dense(n, d, e.width), "w_up": dense(n, d, e.width),
               "w_down": dense(n, e.width, d)}
        if e.scoring == "sigmoid_bias":
            moe["expert_bias"] = rng.standard_normal(
                e.n_experts, dtype=np.float32) * np.float32(_EXPERT_BIAS_STD)
        return {"moe": moe}

    def one(kind, ffn):
        return {"operator_norm": ones(d), "ffn_norm": ones(d),
                "mixer": mixer(kind), **feed(ffn)}

    def stacked(kind, ffn):
        return jax.tree_util.tree_map(
            lambda *xs: np.stack(xs),
            *[one(kind, ffn) for _ in range(spec.n_periods)])

    return {"embed": dense(spec.vocab_size, d), "final_norm": ones(d),
            "leading": [one(kind, ffn) for kind, ffn in
                        zip(spec.leading, spec.leading_ffn)],
            "layers": [stacked(kind, ffn) for kind, ffn in
                       zip(spec.period, spec.period_ffn)]}
