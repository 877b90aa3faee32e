"""Sparse expert layer for one chip's share of an expert-parallel layer.

The layer is told which experts it holds (`experts_held`, a contiguous
range of the published count), routes every token over ALL experts, and
computes the part of the routed sum that its own experts give, for the
tokens routed to them. A pair routed to an absent expert adds nothing here;
in a deployment the chip that holds that expert adds it, and an all-to-all
carries tokens and results between them. Nothing here stands in for those
chips or that exchange.

Dropless, with no capacity factor. The (token, expert) pairs are sorted by
expert and each held expert's run is cut into tiles of `TILE` rows; a tile's
tokens are gathered, run through the tile's expert's gated MLP and added
into the output. Work follows the pairs actually held, no shape depends on
the routing and no buffer grows with the skew. Two forms of that one walk,
chosen by `moe_layer` from what it can observe (the platform and the
shapes: `ops/moe_experts.pallas_fits`), with no knob, and counted
(`moe.experts.route.pallas` / `moe.experts.route.xla`):

**The kernels** `moe_fwd` / `moe_bwd` (`ops/moe_experts.py`), on a TPU: ONE
pipelined Pallas call a direction over a tile-aligned plan
(`dispatch_plan(..., top_p)`: the sort pads each run to whole tiles and
carries the pairs' weights), a grid as long as the dropless bound
N k / TILE + E whose steps past the tiles this routing made do nothing,
a tile's rows moved by DMA while the tile before's matrix products run,
an expert's weights resident in VMEM across its tiles.

**The XLA loop** (`_experts_xla`): a `fori_loop` with a DYNAMIC trip count
(a `while`, which reverse-mode cannot differentiate, so it carries its own
backward: the same loop over the same tiles, recomputing a tile's hidden
activations and accumulating the gradients). One fusion a tile, its
gather, weight fetch, products and scatter-add one after the other (on a
v5e 114 to 171 us a tile and direction, PR 35's records). The fallback for
every other platform and shape, and the plain form the kernels are tested
against (tests/test_moe_kernel.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ...ops import moe_experts
from ...reliability.metrics import reliability_metrics
from ...telemetry import names as tnames

# rows a tile. Swept on one layer alone on a v5e at both share cells' shapes
# (PR 36): the kernels take 7.3 / 7.8 / 8.2 ms forward + backward at 128 /
# 256 / 512 (32 experts of width 512, 320 pairs each) and 17.2 / 18.9 / 18.2
# (8 experts of width 1536, 2,048 pairs each); the XLA loop likes them larger
# (20.8 / 19.8 / 17.5 and 39.9 / 29.3 / 25.7 ms) and is no cell's hot path.
TILE = 128
# Telling XLA that a tile's ids are sorted and unique (they are) makes the
# v5e's scatters 4x SLOWER (PR 28: 112 -> 559 us a forward tile), so the
# scatters say nothing.
_SCATTER = {"mode": "drop"}


# added to the chosen sigmoid scores' sum before it divides them
_SIGMOID_NORM_EPS = 1e-6


def _chosen(idx, n_experts: int):
    """idx (N, k) -> bool (N, k, E): where expert e is a row's j-th choice.
    A row chooses an expert once, so a sum over either axis of values
    masked by it moves ONE value exactly; the v5e runs it as one fused pass
    where a gather or a scatter of N k scalars costs a millisecond."""
    return idx[..., None] == jnp.arange(n_experts, dtype=idx.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _choose(logits, bias, top_k: int, scoring: str):
    """Router logits (N, E) float32 -> (the chosen experts' ids (N, k)
    int32, their scores (N, k)) by `route`'s rule. Its own backward, so
    that what the backward reads is NAMED (`tnames.REMAT_RESIDUALS`): the
    scores and the ids. Reverse-mode through `lax.top_k` and the softmax
    reads their own untagged outputs, and a checkpoint that keeps names
    would run both again to have them."""
    return _choose_fwd(logits, bias, top_k, scoring)[0]


def _choose_fwd(logits, bias, top_k, scoring):
    if scoring == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
        top, idx = jax.lax.top_k(scores, top_k)
    else:
        scores = jax.nn.sigmoid(logits)
        _, idx = jax.lax.top_k(
            scores if bias is None else scores + bias, top_k)
        top = jnp.where(_chosen(idx, scores.shape[-1]), scores[:, None, :],
                        0.0).sum(-1)
    scores, idx, top = checkpoint_name(
        (scores, idx.astype(jnp.int32), top), tnames.KEEP_ROUTING)
    return (idx, top), (scores, idx)


def _choose_bwd(top_k, scoring, res, cts):
    scores, idx = res
    # the chosen scores' cotangent back at their places, zero elsewhere
    d_scores = jnp.where(_chosen(idx, scores.shape[-1]), cts[1][..., None],
                         0.0).sum(-2)
    if scoring == "softmax":
        weighted = scores * d_scores
        d_logits = weighted - scores * weighted.sum(-1, keepdims=True)
    else:
        d_logits = d_scores * scores * (1.0 - scores)
    return d_logits, None       # the selection bias moves no weight


_choose.defvjp(_choose_fwd, _choose_bwd)


def route(x, w_router, top_k: int, renormalize: bool = True,
          scoring: str = "softmax", bias=None, scale: float = 1.0):
    """x (N, d) -> (indices (N, k) int32, weights (N, k) float32), scored
    over all experts in float32 by the description's rule. "softmax": the
    k largest probabilities, renormalised to sum 1. "sigmoid": sigmoid
    scores; the k largest of score + `bias` (E,) are CHOSEN (the selection
    bias of an auxiliary-loss-free balancer: it moves no weight and gets no
    gradient), the weights are the chosen experts' UNBIASED scores,
    renormalised over the chosen. `scale` multiplies the weights."""
    with jax.named_scope(tnames.LM_MOE_ROUTER):
        logits = jnp.einsum("nd,de->ne", x, w_router,
                            preferred_element_type=jnp.float32)
        idx, top = _choose(logits, bias, top_k, scoring)
        if renormalize:
            norm = top.sum(-1, keepdims=True)
            top = top / (norm if scoring == "softmax"
                         else norm + _SIGMOID_NORM_EPS)
        if scale != 1.0:
            top = top * scale
        return idx, top


def dispatch_plan(idx, lo: int, hi: int, top_p=None):
    """The tile layout of one routing. idx (N, k) expert ids over all
    experts; [lo, hi) the experts held. Returns a dict of int32 arrays:
    `order` (N k,) pair ids sorted by held expert (absent pairs last),
    `starts` (E + 1,) the first sorted row of each held expert's run,
    `tile_ends` (E,) cumulative tiles through each expert, `counts` (E,).
    With `top_p` (N, k), the pairs' weights: the kernels' tile-aligned
    layout instead (`ops/moe_experts.tile_plan`: the sort pads each run to
    whole tiles and carries the weights), `counts` among its keys too."""
    with jax.named_scope(tnames.LM_MOE_DISPATCH):
        if top_p is not None:
            return checkpoint_name(
                moe_experts.tile_plan(idx, jax.lax.stop_gradient(top_p), lo,
                                      hi, TILE), tnames.KEEP_ROUTING)
        n_held = hi - lo
        flat = idx.reshape(-1)
        held = (flat >= lo) & (flat < hi)
        key = jnp.where(held, flat - lo, n_held)
        order = jnp.argsort(key, stable=True).astype(jnp.int32)
        counts = (key[:, None] == jnp.arange(n_held, dtype=key.dtype)).sum(
            0, dtype=jnp.int32)
        starts = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                                  jnp.cumsum(counts)]).astype(jnp.int32)
        tile_ends = jnp.cumsum((counts + TILE - 1) // TILE).astype(jnp.int32)
        # named: a checkpointed expert layer keeps the plan and sorts once
        return checkpoint_name(
            {"order": order, "starts": starts, "tile_ends": tile_ends,
             "counts": counts}, tnames.KEEP_ROUTING)


def _tile(t, plan, top_k: int, n_tokens: int):
    """Tile t of the plan: (expert, token ids (TILE,), pair ids (TILE,),
    valid (TILE,)). Rows past the end of the expert's run are invalid and
    their ids lie past the end (clipped by gathers, dropped by scatters)."""
    ends = plan["tile_ends"]
    e = jnp.sum(t >= ends).astype(jnp.int32)
    first = jnp.where(e > 0, ends[jnp.maximum(e - 1, 0)], 0)
    rows = plan["starts"][e] + (t - first) * TILE + jnp.arange(TILE)
    valid = rows < plan["starts"][e + 1]
    pair = plan["order"][jnp.clip(rows, 0, plan["order"].shape[0] - 1)]
    token = jnp.where(valid, pair // top_k, n_tokens)
    return e, token, jnp.where(valid, pair, n_tokens * top_k), valid


def _take(w, e):
    return jax.lax.dynamic_index_in_dim(w, e, axis=0, keepdims=False)


def _gather_tile(t, x, p_flat, plan, top_k: int):
    """(expert, token ids, pair ids, the tokens' rows (TILE, d), the
    pairs' weights (TILE,), zero where a row is invalid) of tile t."""
    e, token, pair, valid = _tile(t, plan, top_k, x.shape[0])
    with jax.named_scope(tnames.LM_MOE_DISPATCH):
        xt = jnp.take(x, token, axis=0, mode="clip")
        weight = jnp.where(valid, jnp.take(p_flat, pair, mode="clip"), 0.0)
    return e, token, pair, xt, weight


def _experts(x, top_p, w_gate, w_up, w_down, plan):
    """The routed sum of the held experts over `plan`'s tiles, (N, d) ->
    (N, d): the kernels where the plan is theirs, else the XLA loop."""
    if "pair" in plan:
        reliability_metrics.inc(tnames.MOE_EXPERTS_ROUTE_PALLAS)
        return moe_experts.experts_pallas(x, top_p, w_gate, w_up, w_down,
                                          plan)
    reliability_metrics.inc(tnames.MOE_EXPERTS_ROUTE_XLA)
    return _experts_xla(x, top_p, w_gate, w_up, w_down, plan)


@jax.custom_vjp
def _experts_xla(x, top_p, w_gate, w_up, w_down, plan):
    return _experts_fwd(x, top_p, w_gate, w_up, w_down, plan)[0]


def _experts_fwd(x, top_p, w_gate, w_up, w_down, plan):
    f32 = jnp.float32
    top_k = top_p.shape[1]
    p_flat = top_p.reshape(-1)

    def one_tile(t, out):
        e, token, _, xt, weight = _gather_tile(t, x, p_flat, plan, top_k)
        gate = jnp.dot(xt, _take(w_gate, e), preferred_element_type=f32)
        up = jnp.dot(xt, _take(w_up, e), preferred_element_type=f32)
        hidden = (jax.nn.silu(gate) * up).astype(xt.dtype)
        y = jnp.dot(hidden, _take(w_down, e), preferred_element_type=f32)
        with jax.named_scope(tnames.LM_MOE_DISPATCH):
            return out.at[token].add(y * weight[:, None], **_SCATTER)

    out = jax.lax.fori_loop(0, plan["tile_ends"][-1], one_tile,
                            jnp.zeros(x.shape, f32))
    return out.astype(x.dtype), (x, top_p, w_gate, w_up, w_down, plan)


def _experts_bwd(res, dout):
    x, top_p, w_gate, w_up, w_down, plan = res
    f32, cdt = jnp.float32, x.dtype
    top_k = top_p.shape[1]
    p_flat = top_p.reshape(-1)

    def one_tile(t, carry):
        dx, dp, dw_gate, dw_up, dw_down = carry
        e, token, pair, xt, weight = _gather_tile(t, x, p_flat, plan, top_k)
        with jax.named_scope(tnames.LM_MOE_DISPATCH):
            dy = jnp.take(dout, token, axis=0, mode="clip")
        wg, wu, wd = _take(w_gate, e), _take(w_up, e), _take(w_down, e)
        gate = jnp.dot(xt, wg, preferred_element_type=f32)
        up = jnp.dot(xt, wu, preferred_element_type=f32)
        sig = jax.nn.sigmoid(gate)
        act = gate * sig
        hidden = act * up
        # y = hidden @ wd; out += weight * y
        dh_unweighted = jnp.einsum("td,fd->tf", dy, wd,
                                   preferred_element_type=f32)
        dweight = (hidden * dh_unweighted).sum(-1)
        dh = dh_unweighted * weight[:, None]
        dwd = jnp.einsum("tf,td->fd", (hidden * weight[:, None]).astype(cdt),
                         dy, preferred_element_type=f32)
        dup = (dh * act).astype(cdt)
        dgate = (dh * up * (sig + act * (1.0 - sig))).astype(cdt)
        dwg = jnp.einsum("td,tf->df", xt, dgate, preferred_element_type=f32)
        dwu = jnp.einsum("td,tf->df", xt, dup, preferred_element_type=f32)
        dxt = jnp.einsum("tf,df->td", dgate, wg, preferred_element_type=f32) \
            + jnp.einsum("tf,df->td", dup, wu, preferred_element_type=f32)
        with jax.named_scope(tnames.LM_MOE_DISPATCH):
            dx = dx.at[token].add(dxt, **_SCATTER)
            dp = dp.at[pair].set(dweight, **_SCATTER)
        return (dx, dp, dw_gate.at[e].add(dwg), dw_up.at[e].add(dwu),
                dw_down.at[e].add(dwd))

    dx, dp, dw_gate, dw_up, dw_down = jax.lax.fori_loop(
        0, plan["tile_ends"][-1], one_tile,
        (jnp.zeros(x.shape, f32), jnp.zeros(p_flat.shape, f32),
         jnp.zeros(w_gate.shape, f32), jnp.zeros(w_up.shape, f32),
         jnp.zeros(w_down.shape, f32)))
    return (dx.astype(x.dtype), dp.reshape(top_p.shape).astype(top_p.dtype),
            dw_gate.astype(w_gate.dtype), dw_up.astype(w_up.dtype),
            dw_down.astype(w_down.dtype), None)


_experts_xla.defvjp(_experts_fwd, _experts_bwd)


def gated_mlp(x, w_gate, w_up, w_down):
    f32 = jnp.float32
    gate = jnp.dot(x, w_gate, preferred_element_type=f32)
    up = jnp.dot(x, w_up, preferred_element_type=f32)
    return jnp.dot((jax.nn.silu(gate) * up).astype(x.dtype), w_down,
                   preferred_element_type=f32).astype(x.dtype)


def moe_layer(x, p, top_k: int, experts_held: tuple,
              renormalize: bool = True, scoring: str = "softmax",
              scale: float = 1.0):
    """x (N, d) -> (y (N, d), stats (3,) float32). p: `router` (d, E_all),
    `w_gate`, `w_up` (E, d, f), `w_down` (E, f, d) of the experts held;
    where the description has a shared expert, `shared_gate`, `shared_up`
    (d, fs), `shared_down` (fs, d), `shared_expert_gate` (d, 1); where its
    router chooses by a biased score, `expert_bias` (E_all,) (`route`).
    stats = (pairs routed, pairs held, the fullest held expert's pairs over
    the mean of the held experts')."""
    lo, hi = experts_held
    idx, top_p = route(x, p["router"], top_k, renormalize, scoring,
                       p.get("expert_bias"), scale)
    top_p = top_p.astype(jnp.float32)
    kernels = (moe_experts.pallas_fits(x, p["w_gate"], top_k, TILE)
               and jax.devices()[0].platform == "tpu")
    plan = dispatch_plan(idx, lo, hi, top_p if kernels else None)
    with jax.named_scope(tnames.LM_MOE_EXPERTS):
        routed = _experts(x, top_p, p["w_gate"], p["w_up"], p["w_down"],
                          plan)
    shared = None
    if "shared_gate" in p:
        with jax.named_scope(tnames.LM_MOE_SHARED):
            shared = gated_mlp(x, p["shared_gate"], p["shared_up"],
                               p["shared_down"])
            gate = jax.nn.sigmoid(jnp.dot(
                x, p["shared_expert_gate"],
                preferred_element_type=jnp.float32))
            shared = (shared * gate).astype(x.dtype)
    counts = jax.lax.stop_gradient(plan["counts"]).astype(jnp.float32)
    stats = jnp.stack([jnp.float32(idx.size), counts.sum(),
                       counts.max() / jnp.maximum(counts.mean(), 1.0)])
    return (routed if shared is None else routed + shared), stats
