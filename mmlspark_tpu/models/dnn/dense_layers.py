"""The dense decoder family (kind `dense`: the GPT-2 block), as
`PipelinedLMTrainer` runs it: LayerNorm, full multi-head attention, a GELU
MLP, learned positions and a head tied to the embedding. The same math as
`transformer.transformer_apply`'s loop body (causal), kept in lockstep so
pipelined and unpipelined losses agree up to reduction order
(tests/test_pp_training.py). What a family supplies: docs/dnn.md "Model
families".
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ...ops import embedding
from ...parallel import DATA_AXIS, MODEL_AXIS, PIPE_AXIS, SEQ_AXIS
from ...parallel.megatron import tp_f, tp_g
from ...telemetry import names as tnames
from .transformer import _layer_norm, init_transformer

# the mesh axes this family has a form for: Megatron slices over the model
# axis, ring attention over the seq axis
AXES = (DATA_AXIS, PIPE_AXIS, MODEL_AXIS, SEQ_AXIS)
# what a stage counts for the host: nothing
STATS = ()


def check(spec) -> None:
    """A dense model is its period: one kind of block all the way."""
    if spec.leading or spec.period_ffn:
        raise ValueError("a dense model has no leading layers and one "
                         "feed-forward kind: leading, leading_ffn and "
                         "period_ffn stay empty")


def meta(spec) -> dict:
    """What a checkpoint must agree on to be resumed."""
    return {"n_heads": spec.n_heads, "d_model": spec.d_model}


def init(spec, seed: int) -> dict:
    """`init_transformer`'s seeded host weights, layers stacked."""
    raw = init_transformer(spec.vocab_size, spec.d_model, spec.n_heads,
                           spec.n_periods, spec.d_ff, spec.max_len, seed)
    stacked = jax.tree_util.tree_map(lambda *xs: np.stack(xs),
                                     *raw["layers"])     # leaves (L, ...)
    return {"layers": stacked, "embed": raw["embed"], "pos": raw["pos"],
            "final_ln": raw["final_ln"]}


def partition(spec, tp: int) -> dict:
    """PartitionSpecs of the stacked layers on a model axis of size `tp`:
    the stage dim over PIPE, the Megatron layout over MODEL (qkv/w1
    column-parallel on outputs, wo/w2 row-parallel on inputs)."""
    for name, size in (("n_heads", spec.n_heads), ("d_ff", spec.d_ff)):
        if size % tp:
            raise ValueError(
                f"{name} ({size}) must divide by the model axis ({tp})")
    col, row = P(PIPE_AXIS, None, MODEL_AXIS), P(PIPE_AXIS, MODEL_AXIS, None)
    ln = {"scale": P(PIPE_AXIS, None), "bias": P(PIPE_AXIS, None)}
    return {"ln1": dict(ln), "ln2": dict(ln), "wq": col, "wk": col,
            "wv": col, "wo": row, "w1": col, "b1": P(PIPE_AXIS, MODEL_AXIS),
            "w2": row, "b2": P(PIPE_AXIS, None)}


def cast(p, dtype):
    """Layer-norm scale/bias ride along in the compute dtype —
    `_layer_norm` upcasts its math to f32 internally either way."""
    return jax.tree_util.tree_map(
        lambda a: a.astype(dtype) if a.dtype == jnp.float32 else a, p)


def embed(p, tokens, seq_off):
    """(mb, S) -> (mb, S, d); `seq_off`: this shard's first position."""
    pos = jax.lax.dynamic_slice_in_dim(p["pos"], seq_off, tokens.shape[-1],
                                       axis=0)
    return embedding.lookup(p["embed"], tokens) + pos


def _norm(x, ln, tp_axis):
    y = _layer_norm(x, ln)
    return y if tp_axis is None else tp_f(y, tp_axis)


def _matmuls(y, ws):
    return tuple(y @ w for w in ws)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def normed_matmuls(x, ln, ws, tp_axis=None):
    """(ln(x) @ w for w in ws) on (mb, S, d) slabs, whose backward
    recomputes ln(x) from x: reverse mode would keep ln(x) and three
    float32 arrays of x's shape (x - mean, the normalised x) for every
    layer of the scan, 14 bytes a number beside x's 2 (docs/dnn.md "What a
    dense layer keeps"). `tp_axis`: the f operator between the norm and
    the column-parallel matrices."""
    return _matmuls(_norm(x, ln, tp_axis), ws)


def _normed_matmuls_fwd(x, ln, ws, tp_axis):
    return normed_matmuls(x, ln, ws, tp_axis), (x, ln, ws)


def _normed_matmuls_bwd(tp_axis, res, cts):
    x, ln, ws = res
    y, norm_vjp = jax.vjp(lambda x, ln: _norm(x, ln, tp_axis), x, ln)
    # ln(x) made once, in a pass of its own: fused into each weight
    # gradient's product as a prologue it slows every one of them
    y = jax.lax.optimization_barrier(y)
    dy, dws = jax.vjp(_matmuls, y, ws)[1](cts)
    return (*norm_vjp(dy), dws)


normed_matmuls.defvjp(_normed_matmuls_fwd, _normed_matmuls_bwd)


def _attend(q, k, v, dh: int, attention: str, cp_axis):
    """Causal attention of one sequence's (S, h, dh) q, k, v. The kernels'
    region is opened HERE, inside the caller's `vmap`: an instruction is
    named after the innermost element of its name stack, and the kernels
    must stay `flash_fwd` / `flash_dq` / `flash_dkv`, not
    `vmap_flash_fwd_`, for the readers that find them by name."""
    if cp_axis is not None:
        # context parallelism: the sequence is SHARDED over cp_axis; ring
        # attention rotates K/V blocks around that axis with the global
        # causal geometry carried by block offsets (attention="flash":
        # each block through the Pallas kernel)
        from ...parallel.ring_attention import _ring_attention_sharded
        with jax.named_scope(tnames.LM_ATTN_FLASH):
            return _ring_attention_sharded(
                q, k, v, axis_name=cp_axis, causal=True,
                scale=1.0 / float(np.sqrt(dh)),
                block_impl="flash" if attention == "flash" else "dense")
    if attention == "flash":
        from ...ops.flash_attention import flash_attention
        with jax.named_scope(tnames.LM_ATTN_FLASH):
            return flash_attention(q, k, v, causal=True)
    from ...parallel.ring_attention import reference_attention
    return reference_attention(q, k, v, causal=True)


def _block_attn(x, lp, h: int, dh: int, attention: str = "dense",
                tp_axis=None, cp_axis=None):
    """Attention sublayer of one transformer block on (mb, S, d) slabs:
    ln1 -> qkv -> (ring/flash/dense) attention -> wo -> residual add. Only
    the attention itself goes a sequence at a time (`jax.vmap`): the
    kernels then read q, k, v (mb, h, S, dh) as the projections wrote them
    (docs/dnn.md "What a dense layer keeps").

    attention="flash" routes through the Pallas kernel (with its flash
    BACKWARD — O(block) training memory): legal here because shard_map
    hands each pipeline stage per-device code, where a pallas_call is just
    a local op. The GSPMD dp x tp trainer (lm_training.py) keeps dense
    attention — pallas calls do not auto-partition under GSPMD.

    tp_axis: Megatron tensor parallelism INSIDE the stage. lp's weight
    leaves arrive column-sliced (wq/wk/wv/w1 on outputs, wo/w2 on inputs
    — h must be the LOCAL head count), activations stay replicated, and
    one psum over tp_axis closes each of the two row-parallel matmuls."""
    mb, seq, _ = x.shape
    with jax.named_scope(tnames.LM_ATTN):
        q, k, v = (t.reshape(mb, seq, h, dh) for t in normed_matmuls(
            x, lp["ln1"], (lp["wq"], lp["wk"], lp["wv"]), tp_axis))
        a = jax.vmap(lambda q, k, v: _attend(
            q, k, v, dh, attention, cp_axis))(q, k, v)
        att = a.reshape(mb, seq, h * dh) @ lp["wo"]
        if tp_axis is not None:
            att = tp_g(att, tp_axis)
        return x + att


def _block_ff(x, lp, tp_axis=None):
    """Feed-forward sublayer on (mb, S, d) slabs: ln2 -> gelu MLP ->
    residual add."""
    with jax.named_scope(tnames.LM_MLP):
        u, = normed_matmuls(x, lp["ln2"], (lp["w1"],), tp_axis)
        ff = jax.nn.gelu(u + lp["b1"]) @ lp["w2"]
        if tp_axis is not None:
            ff = tp_g(ff, tp_axis)
        # b2 is replicated across tp: OUTSIDE the psum or it counts tp x
        return x + ff + lp["b2"]


def stage(x, layers, spec, attention: str, remat, tp_axis=None,
          cp_axis=None):
    """(mb, S, d) through this stage's layers -> (x, no stats). A block is
    its attention and FF sublayers, so remat can trade them apart:
    remat=True (= "full") has the backward recompute a block from its
    (mb, S, d) input instead of keeping qkv/scores/gelu residents;
    remat="save_attn" recomputes only the FF sublayer and stores the
    attention sublayer's residuals (x, the kernels' q/k/v/out/lse, the wo
    input), because re-running the flash FORWARD is the costliest thing to
    recompute at long context (parity: test_remat_is_loss_invariant)."""
    dh = spec.d_model // spec.n_heads
    h_loc = layers["wq"].shape[-1] // dh     # local heads per model shard
    attn = lambda h_x, lp: _block_attn(h_x, lp, h_loc, dh,
                                       attention=attention, tp_axis=tp_axis,
                                       cp_axis=cp_axis)
    ff = lambda h_x, lp: _block_ff(h_x, lp, tp_axis=tp_axis)
    if remat == "save_attn":
        ff = jax.checkpoint(ff)
    blk = lambda h_x, lp: ff(attn(h_x, lp), lp)
    if remat and remat != "save_attn":
        blk = jax.checkpoint(blk)

    def one_layer(h_x, lp):
        return blk(h_x, lp), None
    x, _ = jax.lax.scan(one_layer, x, layers)
    return x, STATS


def head_loss(p, y, targets, mask, spec):
    """Final LayerNorm and the tied softmax head on the last stage's
    (mb, S, d): the masked SUM of the next-token losses. bf16 operands at
    the MXU's bf16 rate, but logits ACCUMULATE f32 (bf16 logits would feed
    log_softmax 8-bit mantissas at vocab-size dynamic range)."""
    z = _layer_norm(y, p["final_ln"])
    logits = jnp.einsum("msd,vd->msv", z, p["embed"],
                        preferred_element_type=jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return (nll * mask).sum()


def summary(stats) -> list:
    """What of a step's stats leaves the program with the loss: nothing."""
    return []


def report(values) -> None:
    """Nothing reaches the host but the loss."""
