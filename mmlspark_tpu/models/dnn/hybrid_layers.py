"""The hybrid decoder family (kinds `gdn`, `attention`: zero-centred
RMSNorm, gated grouped-KV attention with partial rotary embedding, Gated
DeltaNet, sparse experts with a shared expert, an untied head), on
(B, S, d) activations, as `PipelinedLMTrainer` runs it. `lm_spec.py` says
which of the kinds a model's period is made of;
`benchmark/reference/qwen3_next.py` has the same equations in plain
float32. What a family supplies: docs/dnn.md "Model families".

Mixed precision as in the dense block: matmul operands in the activations'
dtype with float32 accumulation; norms, rotary angles, gates' decay, L2
normalisation, the convolution's sum and every softmax in float32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ...ops import embedding
from ...ops.gated_delta import gated_delta_slab
from ...ops.gdn_mixer import gdn_finish, gdn_prepare
from ...parallel import DATA_AXIS, PIPE_AXIS
from ...reliability.metrics import reliability_metrics
from ...telemetry import names as tnames
from .moe import moe_layer

# the mesh axes this family has a form for: its layers have no Megatron
# slicing and no ring form yet
AXES = (DATA_AXIS, PIPE_AXIS)
# what a stage counts for the host, summed over its expert-layer calls:
# (pairs routed, pairs held, held experts' max load over mean, calls)
STATS = jax.ShapeDtypeStruct((4,), jnp.float32)
# leaves the per-step cast leaves in float32: vectors and the convolution
# taps, whose arithmetic is float32 anyway
F32_LEAVES = frozenset({"norm_in", "norm_post", "final_norm", "q_norm",
                        "k_norm", "norm", "A_log", "dt_bias", "conv"})
# positions a chunk of the head's loss holds: float32 logits of a chunk are
# (microbatch, 2048, vocabulary)
_HEAD_CHUNK = 2048


def check(spec) -> None:
    """The sizes a period of these kinds needs."""
    missing = [name for name, part in (
        ("attention", spec.attention), ("gdn", spec.delta_net))
        if name in spec.period and part is None]
    if missing or spec.experts is None:
        raise ValueError(f"a hybrid period needs its "
                         f"{missing + ['experts']} sizes")
    check_experts(spec.experts)
    if spec.leading or spec.period_ffn:
        raise ValueError("a hybrid model has no leading layers and one "
                         "feed-forward kind: leading, leading_ffn and "
                         "period_ffn stay empty")
    if spec.experts.scoring != "softmax" or spec.experts.scale != 1.0:
        raise ValueError("a hybrid model's router is the softmax one, "
                         "unscaled")


def check_experts(experts) -> None:
    lo, hi = experts.held
    if not 0 <= lo < hi <= experts.n_experts:
        raise ValueError(f"experts held {experts.held} is no "
                         f"range of {experts.n_experts}")


def meta(spec) -> dict:
    """What a checkpoint must agree on to be resumed."""
    return {"d_model": spec.d_model, "period": "/".join(spec.period),
            "n_periods": spec.n_periods,
            "experts_held": list(spec.experts.held)}


def init(spec, seed: int) -> dict:
    # late-bound: compile_hybrid_v5e.py stands shapes in for `init_hybrid`
    return init_hybrid(spec, seed)


def cast(p, dtype):
    return jax.tree_util.tree_map_with_path(
        lambda path, a: a if path[-1].key in F32_LEAVES else a.astype(dtype),
        p)


def embed(p, tokens, seq_off):
    """(mb, S) -> (mb, S, d); positions are rotary, inside attention."""
    return embedding.lookup(p["embed"], tokens)


def rms_norm(x, w, eps: float):
    """x * rsqrt(mean(x^2) + eps) * (1 + w), float32 inside."""
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt((x32 * x32).mean(-1, keepdims=True) + eps)
    return (y * (1.0 + w.astype(jnp.float32))).astype(x.dtype)


def rotary(x, theta: float, rot: int):
    """Rotary embedding of positions 0 .. S-1 on the first `rot` of the
    last dimension of x (B, S, H, D); half-split ("rotate_half")."""
    seq = x.shape[1]
    inv = theta ** (-jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    ang = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], axis=-1)[None, :, None, :]
    xr = x[..., :rot].astype(jnp.float32)
    half = jnp.concatenate([-xr[..., rot // 2:], xr[..., :rot // 2]], -1)
    out = xr * jnp.cos(ang) + half * jnp.sin(ang)
    return jnp.concatenate([out.astype(x.dtype), x[..., rot:]], axis=-1)


def matmul(x, w):
    return jnp.einsum("...d,df->...f", x, w,
                      preferred_element_type=jnp.float32).astype(x.dtype)


def grouped_attention(q, k, v, attention: str):
    """Causal attention of q (B, S, H, D) over k, v (B, S, KV, D), query
    head j reading KV head j // (H / KV) -> (B, S, H, D). Grouped KV
    reaches the kernels by repeating K and V."""
    from ...ops.flash_attention import flash_attention
    from ...parallel.ring_attention import reference_attention
    b, s, h, d = q.shape
    k = jnp.repeat(k, h // k.shape[2], axis=2)
    v = jnp.repeat(v, h // v.shape[2], axis=2)
    with jax.named_scope(tnames.LM_ATTN_FLASH):
        # the batch rides on the kernels' head axis: (S, B x H, D). Under a
        # `vmap` the kernels' instructions would be named `vmap_flash_fwd_`
        # and the readers that match them by name would find nothing
        def heads(t):
            return jnp.moveaxis(t, 0, 1).reshape(s, b * h, d)
        attend = flash_attention if attention == "flash" \
            else reference_attention
        out = attend(heads(q), heads(k), heads(v), causal=True)
        return jnp.moveaxis(out.reshape(s, b, h, d), 0, 1)


def attention_mixer(x, p, a, eps: float, attention: str):
    """Gated grouped-KV attention on normed x (B, S, d). `a`: the spec's
    GatedAttention."""
    b, s, _ = x.shape
    h, kv, d = a.n_heads, a.n_kv_heads, a.head_dim
    qg = matmul(x, p["q_proj"]).reshape(b, s, h, 2 * d)
    q, gate = qg[..., :d], qg[..., d:].reshape(b, s, h * d)
    k = matmul(x, p["k_proj"]).reshape(b, s, kv, d)
    v = matmul(x, p["v_proj"]).reshape(b, s, kv, d)
    q = rotary(rms_norm(q, p["q_norm"], eps), a.rope_theta, a.rotary_dim)
    k = rotary(rms_norm(k, p["k_norm"], eps), a.rope_theta, a.rotary_dim)
    out = grouped_attention(q, k, v, attention)
    gate = jax.nn.sigmoid(gate.astype(jnp.float32)).astype(x.dtype)
    return matmul(out.reshape(b, s, h * d) * gate, p["o_proj"])


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def gdn_in_proj(x, w_qkvz, w_ba, n_qkv: int):
    """The DeltaNet mixer's two input projections of x (B, S, d): qkv
    (B, S, n_qkv) and z (the rest of `w_qkvz`'s columns) in x's dtype, ba
    float32. The split is made at the weights, so qkv and z are slabs of
    their own and no slice of a (B, S, .) array is ever copied; the
    backward sums x's three cotangents in float32 and rounds once."""
    f32 = jnp.float32
    return (matmul(x, w_qkvz[:, :n_qkv]), matmul(x, w_qkvz[:, n_qkv:]),
            jnp.einsum("bsd,df->bsf", x, w_ba, preferred_element_type=f32))


def _gdn_in_proj_fwd(x, w_qkvz, w_ba, n_qkv):
    return gdn_in_proj(x, w_qkvz, w_ba, n_qkv), (x, w_qkvz, w_ba)


def _gdn_in_proj_bwd(n_qkv, res, cts):
    x, w_qkvz, w_ba = res
    f32 = jnp.float32

    def dx(ct, w):
        return jnp.einsum("bsf,df->bsd", ct, w, preferred_element_type=f32)

    def dw(ct, w):
        return jnp.einsum("bsd,bsf->df", x, ct,
                          preferred_element_type=f32).astype(w.dtype)

    dqkv, dz, dba = cts
    return ((dx(dqkv, w_qkvz[:, :n_qkv]) + dx(dz, w_qkvz[:, n_qkv:])
             + dx(dba, w_ba)).astype(x.dtype),
            jnp.concatenate([dw(dqkv, w_qkvz), dw(dz, w_qkvz)], axis=1),
            dw(dba, w_ba))


gdn_in_proj.defvjp(_gdn_in_proj_fwd, _gdn_in_proj_bwd)


def gdn_mixer(x, p, g, eps: float):
    """Gated DeltaNet on normed x (B, S, d). `g`: the spec's
    GatedDeltaNet. Between the projections every array is a (B, S, H d)
    slab (docs/dnn.md "The DeltaNet mixer's layout")."""
    hk, hv, dk, dv = g.n_key_heads, g.n_value_heads, g.key_dim, g.value_dim
    f32 = jnp.float32
    qkv, z, ba = gdn_in_proj(x, p["in_proj_qkvz"], p["in_proj_ba"],
                             2 * hk * dk + hv * dv)
    # the recurrence reads the hk key heads as they are: value head h
    # takes key head h // (hv / hk), so nothing is repeated
    q, k, v = gdn_prepare(qkv, p["conv"], (hk, dk, hv, dv))
    beta = jax.nn.sigmoid(ba[..., :hv])
    decay = -jnp.exp(p["A_log"].astype(f32)) * jax.nn.softplus(
        ba[..., hv:] + p["dt_bias"].astype(f32))
    with jax.named_scope(tnames.LM_GDN_SCAN):
        # the batch goes in whole: it is a grid axis of the kernels, and
        # under a `vmap` here they would be named `vmap_gdn_fwd_` (the trap
        # `attention_mixer` describes)
        o = gated_delta_slab(q, k, v, decay, beta, dk, dv)
    return matmul(gdn_finish(o, z, p["norm"], dv, eps), p["out_proj"])


def checkpoint_sublayers(mix, feed, remat, flash: bool, routing: bool):
    """A layer's two sublayers (h, lp) -> ..., checkpointed as the
    trainer's `remat` says. True / "full": the backward pass recomputes
    each from its input, but for the residuals named in
    `tnames.REMAT_RESIDUALS` (the flash forward's output and row sums; an
    expert layer's scores, chosen ids and tile plan), which are kept:
    megabytes that cost milliseconds to make again. "save_attn": the mixer
    keeps what reverse-mode keeps, the feed-forward as under True. False:
    neither is checkpointed. `flash` / `routing`: `mix` makes a flash call, `feed` is
    an expert layer; they count, at trace time, the checkpoints whose
    policy finds something to keep (docs/dnn.md "What remat keeps")."""
    if not remat:
        return mix, feed
    keep = jax.checkpoint_policies.save_only_these_names(
        *tnames.REMAT_RESIDUALS)

    def checkpointed(fn, counter):
        if counter:
            reliability_metrics.inc(counter)
        return jax.checkpoint(fn, policy=keep)

    if remat != "save_attn":
        mix = checkpointed(mix, flash and tnames.LM_REMAT_KEEP_FLASH)
    return mix, checkpointed(feed, routing and tnames.LM_REMAT_KEEP_ROUTING)


def hybrid_layer(h, lp, kind: str, spec, attention: str, remat):
    """One layer on h (B, S, d): h + mixer(norm_in(h)), then
    h + experts(norm_post(h)). Returns (h, the expert layer's stats)."""
    eps = spec.norm_eps

    def mix(h, lp):
        if kind == "attention":
            with jax.named_scope(tnames.LM_ATTN):
                return h + attention_mixer(
                    rms_norm(h, lp["norm_in"], eps), lp["mixer"],
                    spec.attention, eps, attention)
        with jax.named_scope(tnames.LM_GDN):
            return h + gdn_mixer(rms_norm(h, lp["norm_in"], eps),
                                 lp["mixer"], spec.delta_net, eps)

    def experts(h, lp):
        e = spec.experts
        with jax.named_scope(tnames.LM_MOE_ROUTER):
            y = rms_norm(h, lp["norm_post"], eps)
        out, stats = moe_layer(y.reshape(-1, y.shape[-1]), lp["moe"],
                               e.top_k, e.held, e.renormalize)
        with jax.named_scope(tnames.LM_MOE_SHARED):
            return h + out.reshape(h.shape), stats

    mix, experts = checkpoint_sublayers(
        mix, experts, remat,
        flash=kind == "attention" and attention == "flash", routing=True)
    return experts(mix(h, lp), lp)


def stage(x, layers, spec, attention: str, remat, tp_axis=None,
          cp_axis=None):
    """(mb, S, d) through this stage's periods, each the description's
    sequence of layer kinds -> (x, `STATS`). `remat`: what the backward
    pass recomputes of each sublayer (`checkpoint_sublayers`)."""
    def one_period(h_x, lps):
        stats = jnp.zeros(STATS.shape, STATS.dtype)
        for kind, lp in zip(spec.period, lps):
            h_x, (routed, held, load) = hybrid_layer(
                h_x, lp, kind, spec, attention, remat)
            stats = stats + jnp.stack(
                [routed, held, load, jnp.float32(1.0)])
        return h_x, stats
    x, stats = jax.lax.scan(one_period, x, layers)
    return x, stats.sum(0)


def chunked_loss(y, targets, mask, log_probs_of):
    """The masked SUM of the next-token losses of (mb, S, d) activations,
    `_HEAD_CHUNK` positions at a time, each chunk's log-probabilities
    (`log_probs_of(y_c)`: (mb, C, d) -> float32 (mb, C, V)) recomputed in
    the backward pass: float32 logits and their gradient exist for one
    chunk, not for the microbatch."""
    seq = y.shape[1]
    n_chunks = -(-seq // _HEAD_CHUNK)
    pad = n_chunks * _HEAD_CHUNK - seq

    def chunked(a):      # (mb, S, ...) -> (chunks, mb, C, ...)
        a = jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        a = a.reshape((a.shape[0], n_chunks, _HEAD_CHUNK) + a.shape[2:])
        return jnp.moveaxis(a, 1, 0)

    @jax.checkpoint
    def one_chunk(acc, xs):
        y_c, tgt_c, mask_c = xs
        logp = log_probs_of(y_c)
        nll = -jnp.take_along_axis(logp, tgt_c[..., None], axis=-1)[..., 0]
        return acc + (nll * mask_c).sum(), None

    mask = jnp.broadcast_to(mask, targets.shape)
    total, _ = jax.lax.scan(one_chunk, jnp.float32(0.0),
                            (chunked(y), chunked(targets), chunked(mask)))
    return total


def head_loss(p, y, targets, mask, spec):
    """Final RMSNorm and the untied head on the last stage's (mb, S, d),
    through `chunked_loss`."""
    def log_probs_of(y_c):
        z = rms_norm(y_c, p["final_norm"], spec.norm_eps)
        logits = jnp.einsum("msd,vd->msv", z, p["head"],
                            preferred_element_type=jnp.float32)
        return jax.nn.log_softmax(logits, axis=-1)
    return chunked_loss(y, targets, mask, log_probs_of)


def summary(stats) -> list:
    """What of a step's summed `STATS` leaves the program with the loss:
    (pairs routed, pairs held) and the mean over expert-layer calls of max
    load over mean load."""
    return [stats[:2], stats[2:3] / stats[3]]


def report(values) -> None:
    """A step's `summary`, on the host, into the expert layers' counters."""
    reliability_metrics.inc(tnames.MOE_PAIRS_ROUTED, int(values[0]))
    reliability_metrics.inc(tnames.MOE_PAIRS_HELD, int(values[1]))
    reliability_metrics.set_gauge(tnames.MOE_LOAD_MAX_OVER_MEAN,
                                  float(values[2]))


def init_hybrid(spec, seed: int) -> dict:
    """Seeded host weights of a hybrid model: normal(0, init_std) matrices,
    zero-centred norms at 0, the gated output norm at 1, `A_log` the log of
    uniform(1, 16) and `dt_bias` the inverse softplus of a step
    log-uniform in [1e-3, 1e-1] (the family's habit; the configuration
    file lists them as assumed)."""
    rng = np.random.default_rng(seed)
    d, std = spec.d_model, spec.init_std

    def dense(*shape):
        return rng.standard_normal(shape, dtype=np.float32) * np.float32(std)

    def zeros(*shape):
        return np.zeros(shape, np.float32)

    def moe():
        e = spec.experts
        n = e.held[1] - e.held[0]
        return {"router": dense(d, e.n_experts),
                "w_gate": dense(n, d, e.width), "w_up": dense(n, d, e.width),
                "w_down": dense(n, e.width, d),
                "shared_gate": dense(d, e.shared_width),
                "shared_up": dense(d, e.shared_width),
                "shared_down": dense(e.shared_width, d),
                "shared_expert_gate": dense(d, 1)}

    def mixer(kind):
        if kind == "attention":
            a = spec.attention
            return {"q_proj": dense(d, a.n_heads * 2 * a.head_dim),
                    "k_proj": dense(d, a.n_kv_heads * a.head_dim),
                    "v_proj": dense(d, a.n_kv_heads * a.head_dim),
                    "q_norm": zeros(a.head_dim), "k_norm": zeros(a.head_dim),
                    "o_proj": dense(a.n_heads * a.head_dim, d)}
        g = spec.delta_net
        n_qkv = 2 * g.n_key_heads * g.key_dim + g.n_value_heads * g.value_dim
        step = np.exp(rng.uniform(np.log(1e-3), np.log(1e-1),
                                  g.n_value_heads))
        return {"in_proj_qkvz": dense(
                    d, n_qkv + g.n_value_heads * g.value_dim),
                "in_proj_ba": dense(d, 2 * g.n_value_heads),
                "conv": dense(g.conv_width, n_qkv) * np.float32(
                    1.0 / (std * np.sqrt(g.conv_width))),
                "A_log": np.log(rng.uniform(1.0, 16.0, g.n_value_heads)
                                ).astype(np.float32),
                "dt_bias": (step + np.log(-np.expm1(-step))
                            ).astype(np.float32),
                "norm": np.ones(g.value_dim, np.float32),
                "out_proj": dense(g.n_value_heads * g.value_dim, d)}

    def stacked(kind):
        layers = [{"norm_in": zeros(d), "norm_post": zeros(d),
                   "mixer": mixer(kind), "moe": moe()}
                  for _ in range(spec.n_periods)]
        return jax.tree_util.tree_map(lambda *xs: np.stack(xs), *layers)

    return {"embed": dense(spec.vocab_size, d),
            "head": dense(spec.vocab_size, d),
            "final_norm": zeros(d),
            "layers": [stacked(kind) for kind in spec.period]}
