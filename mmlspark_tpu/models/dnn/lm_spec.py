"""The description of a decoder that `PipelinedLMTrainer` trains.

A model is `n_periods` repetitions of a PERIOD: a fixed sequence of layer
kinds in their published order. The trainer stacks parameters by position
in the period (every leaf gains a leading `n_periods` axis, which is the
axis the pipeline shards) and scans periods. A dense GPT-2 block is a period
of one layer; a hybrid decoder's period is, for example, three Gated
DeltaNet layers and one full-attention layer. Before the first period a
model may have LEADING layers (`leading`, for example the dense layers a
sparse decoder starts with): they are not stacked, and the first pipe stage
runs them with the embedding. A layer kind names the layer's mixer; where a
family's layers differ in their feed-forward too, `period_ffn` and
`leading_ffn` give each layer's ("dense" | "experts"). A model whose
layers change kind along its depth, and whose later layers read arrays an
earlier layer made, is a sequence of RUNS (`runs`: each a period of its
own, repeated, from a published layer index on); it is then ONE period of
all its layers (`n_periods` 1), since what its layers share cannot
cross a pipe-stage boundary.

Layer kinds, each with the FAMILY (a module) that holds its layers and
their embedding, head, layout and counters (docs/dnn.md "Model families"):
  "dense"      LayerNorm, full multi-head attention, GELU MLP, learned
               positions, head tied to the embedding      (`dense_layers`)
  "gdn"        zero-centred RMSNorm, Gated DeltaNet, sparse experts
  "attention"  zero-centred RMSNorm, gated grouped-KV attention with partial
               rotary embedding, sparse experts      (`hybrid_layers`)
  "conv"       plain RMSNorm, gated short convolution
  "full_attention"  plain RMSNorm, grouped-KV attention with per-head q/k
               norms and rotary embedding over the whole head; both with a
               dense SwiGLU or sigmoid-routed experts, a chunked head tied
               to the embedding                    (`shortconv_layers`)
  "mamba" | "memory_mamba"  LayerNorm, a Mamba selective-scan mixer (the
               second also hands its scan output on as the MEMORY)
  "window_attention" | "kv_attention"  differential attention under a
               sliding window, or full causal with its k, v kept
  "gmu"        a Gated Memory Unit on the memory
  "cross_attention"  differential attention over the KV layer's k, v; all
               six with a dense SwiGLU, no positions, a chunked head tied
               to the embedding                          (`ssm_layers`)

What a description cannot say yet is in ROADMAP.md (queue R and D2).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

from . import dense_layers, hybrid_layers, shortconv_layers, ssm_layers

# layer kind -> the family that holds it; a model's kinds belong to one
FAMILIES = {"dense": dense_layers, "gdn": hybrid_layers,
            "attention": hybrid_layers, "conv": shortconv_layers,
            "full_attention": shortconv_layers,
            **{kind: ssm_layers for kind in ssm_layers.KINDS}}


@dataclasses.dataclass(frozen=True)
class GatedAttention:
    """A grouped-KV attention mixer's sizes (whether its output is gated
    is its family's business, not a size)."""
    n_heads: int
    n_kv_heads: int
    head_dim: int
    rope_theta: float
    rotary_dim: int


@dataclasses.dataclass(frozen=True)
class GatedDeltaNet:
    n_key_heads: int
    n_value_heads: int
    key_dim: int
    value_dim: int
    conv_width: int = 4


@dataclasses.dataclass(frozen=True)
class ShortConv:
    """The gated short convolution's taps a channel (`conv_L_cache`)."""
    width: int = 3


@dataclasses.dataclass(frozen=True)
class Mamba:
    """A Mamba mixer's sizes: channels, states a channel, the rank of the
    step's projection, the convolution's taps."""
    d_inner: int
    d_state: int
    dt_rank: int
    conv_width: int = 4


@dataclasses.dataclass(frozen=True)
class DiffAttention:
    """Differential attention's sizes; `window`: how many positions back a
    window layer's query sees, its own included."""
    n_heads: int
    n_kv_heads: int
    head_dim: int
    window: int


@dataclasses.dataclass(frozen=True)
class Run:
    """`n` repetitions of a period of layer kinds; `first` is the
    published index of the run's first layer (a layer keeps its published
    index where a cut drops the layers before it)."""
    period: tuple
    n: int
    first: int


@dataclasses.dataclass(frozen=True)
class Experts:
    """`held` is the contiguous range [lo, hi) of the `n_experts` that this
    chip holds; the router is `n_experts` wide whatever is held.
    `shared_width` 0: no shared expert. `scoring` is the router's rule
    (`moe.route`): "softmax", or "sigmoid_bias" (sigmoid scores, chosen by
    score + a selection bias, weighted by the scores themselves); `scale`
    multiplies the chosen weights (`routed_scaling_factor`)."""
    n_experts: int
    top_k: int
    width: int
    shared_width: int
    held: tuple
    renormalize: bool = True
    scoring: str = "softmax"
    scale: float = 1.0


@dataclasses.dataclass(frozen=True)
class LMSpec:
    vocab_size: int
    d_model: int
    period: tuple                 # layer kinds, in order
    n_periods: int
    # the dense block's sizes
    n_heads: int = 0
    d_ff: int = 0
    max_len: int = 0              # learned positions; 0 = none
    # the hybrid layers' sizes
    norm_eps: float = 1e-6
    attention: Optional[GatedAttention] = None
    delta_net: Optional[GatedDeltaNet] = None
    experts: Optional[Experts] = None
    init_std: float = 0.02
    # layers before the first period, and each layer's feed-forward kind
    # where the family's layers differ in it (() = the family's own)
    leading: tuple = ()
    leading_ffn: tuple = ()
    period_ffn: tuple = ()
    short_conv: Optional[ShortConv] = None
    # a model of several runs of periods (`period` lists every layer)
    runs: tuple = ()
    mamba: Optional[Mamba] = None
    diff_attention: Optional[DiffAttention] = None

    def __post_init__(self):
        kinds = self.leading + self.period
        if not self.period or not set(kinds) <= set(FAMILIES):
            raise ValueError(f"period {self.period!r}, leading "
                             f"{self.leading!r}: layer kinds are "
                             f"{' | '.join(FAMILIES)}")
        module = FAMILIES[self.period[0]]
        other = next((k for k in kinds if FAMILIES[k] is not module), None)
        if other is not None:
            raise ValueError(
                f"a model's kinds belong to one family: "
                f"{self.period[0]!r} ({module.__name__}) does not "
                f"mix with {other!r} ({FAMILIES[other].__name__})")
        if self.n_periods < 1:
            raise ValueError(f"n_periods must be >= 1, got {self.n_periods}")
        module.check(self)

    @property
    def family(self):
        """The module that holds this model's layer kinds; where the module
        has a `bound`, what that makes of it for this description (a family
        whose `embed` needs the description: the trainer passes it none)."""
        module = FAMILIES[self.period[0]]
        return module.bound(self) if hasattr(module, "bound") else module

    @property
    def meta(self) -> dict:
        """What a checkpoint must agree on to be resumed."""
        return self.family.meta(self)


def gpt2_spec(vocab_size: int, d_model: int, n_heads: int, n_layers: int,
              d_ff: int, max_len: int) -> LMSpec:
    """The dense block `PipelinedLMTrainer` has always trained, as a
    description: a period of one layer, `n_layers` periods."""
    return LMSpec(vocab_size=vocab_size, d_model=d_model, period=("dense",),
                  n_periods=n_layers, n_heads=n_heads, d_ff=d_ff,
                  max_len=max_len)


def qwen3_next_spec(cfg: dict, experts_held: tuple,
                    n_experts: Optional[int] = None,
                    n_periods: Optional[int] = None) -> LMSpec:
    """A `qwen3_next` config.json (Hugging Face keys) as a description.
    `cfg["vocab_size"]` is the vocabulary held here (a slice is a smaller
    vocabulary); `n_experts` is the router's width (the published count;
    default `cfg["num_experts"]`) and `experts_held` this chip's range of
    them; `n_periods` defaults to `num_layers` (the layers held here, else
    `num_hidden_layers`) over `full_attention_interval`."""
    interval = cfg["full_attention_interval"]
    layers = cfg.get("num_layers", cfg["num_hidden_layers"])
    if n_periods is None:
        if layers % interval:
            raise ValueError(f"{layers} layers are no whole number of "
                             f"periods of {interval}")
        n_periods = layers // interval
    return LMSpec(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        period=tuple("attention" if (i + 1) % interval == 0 else "gdn"
                     for i in range(interval)),
        n_periods=n_periods, norm_eps=cfg["rms_norm_eps"],
        attention=GatedAttention(
            n_heads=cfg["num_attention_heads"],
            n_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
            rope_theta=float(cfg["rope_theta"]),
            rotary_dim=int(cfg["head_dim"] * cfg["partial_rotary_factor"])),
        delta_net=GatedDeltaNet(
            n_key_heads=cfg["linear_num_key_heads"],
            n_value_heads=cfg["linear_num_value_heads"],
            key_dim=cfg["linear_key_head_dim"],
            value_dim=cfg["linear_value_head_dim"],
            conv_width=cfg["linear_conv_kernel_dim"]),
        experts=Experts(
            n_experts=cfg["num_experts"] if n_experts is None else n_experts,
            top_k=cfg["num_experts_per_tok"],
            width=cfg["moe_intermediate_size"],
            shared_width=cfg["shared_expert_intermediate_size"],
            held=tuple(experts_held),
            renormalize=bool(cfg["norm_topk_prob"])))


def lfm2_moe_spec(cfg: dict, experts_held: tuple,
                  n_experts: Optional[int] = None) -> LMSpec:
    """An `lfm2_moe` config.json (Hugging Face keys) as a description.
    `layer_types` is the published order; the layers held here are its
    first `num_layers` (default: all), counted with the `num_dense_layers`
    leading layers held here coming first and the published leading layers
    that are not held skipped. The leading layers carry a dense SwiGLU
    (`intermediate_size`), every other layer the experts. `vocab_size`,
    `n_experts` and `experts_held` as in `qwen3_next_spec`."""
    types = list(cfg["layer_types"])
    n_dense = cfg["num_dense_layers"]
    skipped = cfg.get("published", {}).get("num_dense_layers", n_dense) \
        - n_dense
    held = types[skipped:skipped + cfg.get("num_layers", len(types))]
    leading, rest = tuple(held[:n_dense]), held[n_dense:]
    if not rest:
        raise ValueError(f"no layer follows the {n_dense} leading ones "
                         f"among {held}")
    # the shortest repeating unit; a model whose last period is cut short
    # repeats only as one period of all its layers
    interval = next(n for n in range(1, len(rest) + 1)
                    if len(rest) % n == 0
                    and rest == rest[:n] * (len(rest) // n))
    heads = cfg["num_attention_heads"]
    head_dim = cfg["hidden_size"] // heads
    return LMSpec(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        period=tuple(rest[:interval]), n_periods=len(rest) // interval,
        period_ffn=("experts",) * interval,
        leading=leading, leading_ffn=("dense",) * len(leading),
        d_ff=cfg["intermediate_size"], norm_eps=cfg["norm_eps"],
        attention=GatedAttention(
            n_heads=heads, n_kv_heads=cfg["num_key_value_heads"],
            head_dim=head_dim,
            rope_theta=float(cfg["rope_parameters"]["rope_theta"]),
            rotary_dim=head_dim),
        short_conv=ShortConv(width=cfg["conv_L_cache"]),
        experts=Experts(
            n_experts=cfg["num_experts"] if n_experts is None else n_experts,
            top_k=cfg["num_experts_per_tok"],
            width=cfg["moe_intermediate_size"], shared_width=0,
            held=tuple(experts_held),
            renormalize=bool(cfg["norm_topk_prob"]),
            scoring="sigmoid_bias" if cfg["use_expert_bias"] else "sigmoid",
            scale=float(cfg["routed_scaling_factor"])))


def phi4flash_kind(index: int, n_layers: int, mb_per_layer: int) -> str:
    """The published rule for layer `index` of `n_layers`: the first half
    alternates Mamba and window attention, the second half's first Mamba
    is the memory layer and its first attention the KV layer, and after
    them a Gated Memory Unit stands where a Mamba would and
    cross-attention where attention would."""
    half = n_layers // 2
    ssm = index % mb_per_layer == 0
    if index < half:
        return "mamba" if ssm else "window_attention"
    if index < half + mb_per_layer:
        return "memory_mamba" if ssm else "kv_attention"
    return "gmu" if ssm else "cross_attention"


def phi4flash_spec(cfg: dict) -> LMSpec:
    """A `phi4flash` config.json (Hugging Face keys) as a description: the
    WHOLE published model from `num_hidden_layers` by the index rule, or
    the layers `held_layers` = [first, last] (published indices, both
    included) where the file gives that key of a cut's own; consecutive
    layers fold into runs of one repeating period of `mb_per_layer`
    layers. Mamba's sizes are the family's defaults unless the file
    carries them, at its top level or under `assumed` (`mamba_expand` 2,
    `mamba_d_state` 16, `mamba_dt_rank` hidden / 16, `mamba_d_conv` 4).
    `vocab_size` is the vocabulary held here."""
    def mamba_size(key, default):
        return cfg.get(key, cfg.get("assumed", {}).get(key, default))

    n_layers, per = cfg["num_hidden_layers"], cfg["mb_per_layer"]
    first, last = cfg.get("held_layers", (0, n_layers - 1))
    if not 0 <= first <= last < n_layers:
        raise ValueError(f"held_layers {first}..{last} is no range of the "
                         f"{n_layers} published layers")
    kinds = [phi4flash_kind(i, n_layers, per) for i in range(first, last + 1)]
    runs, at = [], 0
    while at < len(kinds):
        period = tuple(kinds[at:at + per])
        n = 1
        while tuple(kinds[at + n * per:at + (n + 1) * per]) == period:
            n += 1
        runs.append(Run(period=period, n=n, first=first + at))
        at += n * len(period)
    d = cfg["hidden_size"]
    heads = cfg["num_attention_heads"]
    return LMSpec(
        vocab_size=cfg["vocab_size"], d_model=d,
        period=tuple(kinds), n_periods=1, runs=tuple(runs),
        d_ff=cfg["intermediate_size"], norm_eps=cfg["layer_norm_eps"],
        init_std=cfg.get("initializer_range", 0.02),
        mamba=Mamba(d_inner=mamba_size("mamba_expand", 2) * d,
                    d_state=mamba_size("mamba_d_state", 16),
                    dt_rank=mamba_size("mamba_dt_rank", -(-d // 16)),
                    conv_width=mamba_size("mamba_d_conv", 4)),
        diff_attention=DiffAttention(
            n_heads=heads, n_kv_heads=cfg["num_key_value_heads"],
            head_dim=d // heads, window=cfg["sliding_window"]))
