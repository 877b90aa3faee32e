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
`leading_ffn` give each layer's ("dense" | "experts").

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

What a description cannot say yet is in ROADMAP.md (queue R and D2).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

from . import dense_layers, hybrid_layers, shortconv_layers

# layer kind -> the family that holds it; a model's kinds belong to one
FAMILIES = {"dense": dense_layers, "gdn": hybrid_layers,
            "attention": hybrid_layers, "conv": shortconv_layers,
            "full_attention": shortconv_layers}


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
