"""Pipeline-parallel LM training: GPipe microbatching over a `pipe` mesh
axis, composing up to FULL 4D — dp x pp x tp x cp — in one shard_map
(SURVEY §2.10: TP, PP and CP all implemented AND composed here).
TPU-native design:

- The transformer's layers are STACKED on a leading axis and sharded over
  the `pipe` mesh axis — each device materializes only its stage's layers
  (true memory scaling, the reason PP exists).
- One `shard_map` runs the classic GPipe schedule: at tick t, stage s
  computes microbatch t-s; activations hop stage s -> s+1 through ONE
  `lax.ppermute` per tick (neighbor traffic rides ICI).
- Only the FORWARD schedule is written. `jax.value_and_grad` through the
  ppermute gives the reverse schedule for free — the transpose of a
  ppermute is the reverse ppermute, so backward activations flow s+1 -> s
  with no hand-written bubble bookkeeping.
- Composable axes: batch over "data" (grads pmean), Megatron tensor
  slices over "model" (f/g operators below), and sequence shards over
  "seq" (ring attention with global causal offsets; cross-shard
  next-token targets by ppermute). Any subset of axes works — see the
  PipelinedLMTrainer docstring.

The reference has no sequence models at all (SURVEY §5) — this file exists
because long-context/distributed training is first-class in the TPU build,
not because a Scala counterpart does.
"""
from __future__ import annotations

import numpy as np

from ...reliability.metrics import reliability_metrics
from ...telemetry import names as tnames
from ...telemetry.perf import register_program
from ...utils.tracing import annotate
from .lm_spec import LMSpec, gpt2_spec
from .transformer import init_transformer


def _stack_layers(layers: list) -> dict:
    """List of per-layer param dicts -> one dict with (L, ...) leaves."""
    import jax
    return jax.tree_util.tree_map(lambda *xs: np.stack(xs), *layers)


import functools as _functools

# positions a chunk of the untied head's loss holds: float32 logits of a
# chunk are (microbatch, 2048, vocabulary)
_HEAD_CHUNK = 2048


@_functools.lru_cache(maxsize=None)
def _tp_f(axis: str):
    """Megatron's `f` operator: identity forward, psum-over-tp backward.
    Placed at each sublayer input so activation COTANGENTS — partial per
    model shard after flowing back through that shard's weight slice — are
    summed back to full. With f in place, every replicated parameter's
    gradient comes out identical on all model shards and NO gradient
    collective over the model axis is needed; sharded weights' gradients
    are complete locally (the psum's own transpose broadcasts)."""
    import jax

    @jax.custom_vjp
    def f(x):
        return x

    def fwd(x):
        return x, None

    def bwd(_, g):
        return (jax.lax.psum(g, axis),)

    f.defvjp(fwd, bwd)
    return f


@_functools.lru_cache(maxsize=None)
def _tp_g(axis: str):
    """Megatron's `g` operator: psum forward, IDENTITY backward. Under
    shard_map with replication checking off, a bare psum's transpose is
    another psum — the already-replicated output cotangent would be summed
    again, overcounting every row-parallel weight's gradient tp times
    (non-uniformly vs the column side, so even Adam diverges). Pairing
    g (here) with f (above) pins both directions explicitly."""
    import jax

    @jax.custom_vjp
    def g(x):
        return jax.lax.psum(x, axis)

    def fwd(x):
        return jax.lax.psum(x, axis), None

    def bwd(_, ct):
        return (ct,)

    g.defvjp(fwd, bwd)
    return g


def _block_attn(x, lp, h: int, dh: int, attention: str = "dense",
                tp_axis=None, cp_axis=None):
    """Attention sublayer of one transformer block on a (S, d) sequence:
    ln1 -> qkv -> (ring/flash/dense) attention -> wo -> residual add.

    attention="flash" routes through the Pallas kernel (with its flash
    BACKWARD — O(block) training memory): legal here because shard_map
    hands each pipeline stage per-device code, where a pallas_call is just
    a local op. The GSPMD dp x tp trainer (lm_training.py) keeps dense
    attention — pallas calls do not auto-partition under GSPMD.

    tp_axis: Megatron tensor parallelism INSIDE the stage. lp's weight
    leaves arrive column-sliced (wq/wk/wv/w1 on outputs, wo/w2 on inputs
    — h must be the LOCAL head count), activations stay replicated, and
    one psum over tp_axis closes each of the two row-parallel matmuls."""
    import jax
    from ...parallel.ring_attention import reference_attention
    from .transformer import _layer_norm

    seq, d = x.shape
    with jax.named_scope(tnames.LM_ATTN):
        y = _layer_norm(x, lp["ln1"])
        if tp_axis is not None:
            y = _tp_f(tp_axis)(y)
        q = (y @ lp["wq"]).reshape(seq, h, dh)
        k = (y @ lp["wk"]).reshape(seq, h, dh)
        v = (y @ lp["wv"]).reshape(seq, h, dh)
        if cp_axis is not None:
            # context parallelism: the sequence is SHARDED over cp_axis;
            # ring attention rotates K/V blocks around that axis with the
            # global causal geometry carried by block offsets.
            # attention="flash" streams each rotating block through the
            # Pallas kernel.
            from ...parallel.ring_attention import _ring_attention_sharded
            with jax.named_scope(tnames.LM_ATTN_FLASH):
                a = _ring_attention_sharded(
                    q, k, v, axis_name=cp_axis, causal=True,
                    scale=1.0 / float(np.sqrt(dh)),
                    block_impl="flash" if attention == "flash" else "dense")
        elif attention == "flash":
            from ...ops.flash_attention import flash_attention
            with jax.named_scope(tnames.LM_ATTN_FLASH):
                a = flash_attention(q, k, v, causal=True)
        else:
            a = reference_attention(q, k, v, causal=True)
        att = a.reshape(seq, h * dh) @ lp["wo"]
        if tp_axis is not None:
            att = _tp_g(tp_axis)(att)
        return x + att


def _block_ff(x, lp, tp_axis=None):
    """Feed-forward sublayer: ln2 -> gelu MLP -> residual add."""
    import jax
    from .transformer import _layer_norm
    with jax.named_scope(tnames.LM_MLP):
        y = _layer_norm(x, lp["ln2"])
        if tp_axis is not None:
            y = _tp_f(tp_axis)(y)
        ff = jax.nn.gelu(y @ lp["w1"] + lp["b1"]) @ lp["w2"]
        if tp_axis is not None:
            ff = _tp_g(tp_axis)(ff)
        # b2 is replicated across tp: add OUTSIDE the psum or it counts
        # tp x
        return x + ff + lp["b2"]


def _block(x, lp, h: int, dh: int, attention: str = "dense",
           tp_axis=None, cp_axis=None):
    """One transformer block — the same math as transformer_apply's loop
    body (causal attention), kept in lockstep so pipelined and
    unpipelined losses agree bit-for-bit up to reduction order
    (parity-tested). Split into attention/FF sublayers so remat can trade
    them independently (see PipelinedLMTrainer remat="save_attn")."""
    return _block_ff(_block_attn(x, lp, h, dh, attention=attention,
                                 tp_axis=tp_axis, cp_axis=cp_axis),
                     lp, tp_axis=tp_axis)


class PipelinedLMTrainer:
    """dp x pp (x tp) (x cp) trainer: one jitted shard_map train step.

    The mesh's axes pick the composition — every combination is
    oracle-parity-tested (tests/test_pp_training.py):

        grid_mesh((dp, pp), (DATA_AXIS, PIPE_AXIS))                # 2D
        grid_mesh((dp, pp, tp), (..., MODEL_AXIS))                 # 3D
        grid_mesh((dp, pp, tp, cp), (..., SEQ_AXIS))               # 4D

    Layers stack-shard over PIPE (GPipe microbatch schedule, one ppermute
    per tick); weights Megatron-shard over MODEL (f/g operators); the
    SEQUENCE shards over SEQ with ring attention (attention="flash"
    streams rotating K/V blocks through the Pallas kernel + its flash
    backward). loss = t.step(tokens): (B, S) int32,
    B % (dp * n_microbatches) == 0, S % cp == 0.

    What is trained is a description (`model`, an `lm_spec.LMSpec`): a
    period of layer kinds and how often it repeats. The six integers
    (`vocab_size` .. `max_len`) are the dense GPT-2 block's description,
    built here when no `model` is given. Parameters stack by position in
    the period and the stage scans periods; `n_periods` must divide by the
    pipe axis. A hybrid description (Gated DeltaNet, gated grouped-KV
    attention, sparse experts: `hybrid_layers`) trains on the data and
    pipe axes; its layers have no Megatron or ring form yet.
    """

    def __init__(self, vocab_size: int = None, mesh=None,
                 n_microbatches: int = 4,
                 d_model: int = 128, n_heads: int = 8, n_layers: int = 4,
                 d_ff: int = 256, max_len: int = 512, lr: float = 1e-3,
                 seed: int = 0, attention: str = "dense",
                 optimizer: str = "adam",
                 compute_dtype: str = "float32", remat: bool = False,
                 model: LMSpec = None):
        """compute_dtype="bfloat16" trains mixed-precision: master weights
        and the Adam state stay f32; weights and activations are cast to
        bf16 for every matmul (MXU bf16 rate, ~4x f32 on v5e) while layer
        norm, softmax, and the loss accumulate in f32.

        remat=True (= "full") wraps each transformer block in
        jax.checkpoint so the backward recomputes block activations
        instead of storing them — O(L) layer BOUNDARIES instead of
        O(L x per-layer intermediates) of residency, the standard
        long-context memory trade. remat="save_attn" checkpoints only
        the FF sublayer and stores the attention sublayer's residuals
        (q/k/v/out/lse — ~L x 4 x S x d x 2 B, ~1.6 GB at 12L/16k/d1024
        bf16): at long context the step is attention-bound and full
        remat re-runs the flash FORWARD kernel once per layer inside the
        backward (~100 ms/step at the 201M/16k shape), which this mode
        buys back with memory the shape has to spare. Measured v5e at
        201M/16k: 0.472 -> 0.410 s/step (41 -> 46.9% MFU), identical
        loss trajectory; the 4D mesh matches (0.411). Parity-tested
        against full remat and no remat (test_remat_is_loss_invariant)."""
        if attention not in ("dense", "flash"):
            raise ValueError("attention must be dense|flash")
        if optimizer not in ("adam", "sgd"):
            raise ValueError("optimizer must be adam|sgd")
        # isinstance, not `in (True, False, ...)`: ints equal bools under
        # tuple membership, so remat=1 would silently mean full remat
        if not (isinstance(remat, bool) or remat in ("full", "save_attn")):
            raise ValueError("remat must be bool|'full'|'save_attn'")
        if compute_dtype not in ("float32", "bfloat16"):
            raise ValueError("compute_dtype must be float32|bfloat16")
        if model is None:
            if vocab_size is None:
                raise ValueError("give a model description or vocab_size")
            model = gpt2_spec(vocab_size, d_model, n_heads, n_layers, d_ff,
                              max_len)
        self.model = model
        n_periods = model.n_periods
        import jax
        import jax.numpy as jnp
        import optax
        from jax.sharding import NamedSharding, PartitionSpec as P
        from ...parallel import DATA_AXIS, MODEL_AXIS, PIPE_AXIS, grid_mesh
        from jax import shard_map

        if mesh is None:
            n = jax.device_count()
            pp = max(d for d in range(1, n_periods + 1)
                     if n_periods % d == 0 and n % d == 0)
            mesh = grid_mesh((n // pp, pp), (DATA_AXIS, PIPE_AXIS))
        n_stages = mesh.shape[PIPE_AXIS]
        if n_periods % n_stages:
            raise ValueError(
                f"n_periods ({n_periods}; for a dense block, n_layers) must "
                f"divide by the pipe axis ({n_stages}) so every stage holds "
                f"the same layer count")
        # optional third axis: Megatron tensor parallelism inside each stage
        tp = mesh.shape[MODEL_AXIS] if MODEL_AXIS in mesh.axis_names else 1
        if model.n_heads % tp:
            raise ValueError(
                f"n_heads ({model.n_heads}) must divide by the model axis "
                f"({tp})")
        if model.d_ff % tp:
            raise ValueError(
                f"d_ff ({model.d_ff}) must divide by the model axis ({tp})")
        # optional fourth axis: context parallelism — the SEQUENCE shards
        # over it and attention runs as a ring inside each stage
        from ...parallel import SEQ_AXIS
        cp = mesh.shape[SEQ_AXIS] if SEQ_AXIS in mesh.axis_names else 1
        if model.hybrid and (MODEL_AXIS in mesh.axis_names
                             or SEQ_AXIS in mesh.axis_names):
            raise ValueError(
                "a hybrid model trains on the data and pipe axes only: its "
                "layers have no Megatron slicing and no ring form yet")
        self.mesh = mesh
        self.n_stages = n_stages
        self.tp = tp
        self.cp = cp
        self.n_microbatches = n_microbatches
        if n_microbatches < n_stages:
            # fewer microbatches than stages: every stage idles
            # (P - 1) / (M + P - 1) >= 1/2 of the ticks and still stores each
            # tick's residuals and f32 logits. On a v5e the 12L/d1024/16k
            # step fits at M = P = 2 and is refused at M = 1 (16.76 of
            # 15.75 GB at compile time, PR 21; ROADMAP S6a)
            import warnings
            warnings.warn(
                f"PipelinedLMTrainer: n_microbatches ({n_microbatches}) < "
                f"pipe stages ({n_stages}); at least half of the schedule "
                f"is bubble, and each stage holds more activation memory "
                f"than n_microbatches >= {n_stages} would", stacklevel=2)

        if model.hybrid:
            from .hybrid_layers import F32_LEAVES, hybrid_layer, init_hybrid
            # "layers": one dict per position of the period, leaves (P, ...)
            params = init_hybrid(model, seed)
            self.meta = model.meta
        else:
            raw = init_transformer(model.vocab_size, model.d_model,
                                   model.n_heads, n_periods, model.d_ff,
                                   model.max_len, seed)
            self.meta = raw.pop("meta")
            params = {
                "layers": _stack_layers(raw["layers"]),   # leaves (L, ...)
                "embed": raw["embed"], "pos": raw["pos"],
                "final_ln": raw["final_ln"],
            }

        if tp == 1:
            layer_specs = jax.tree_util.tree_map(
                lambda _: P(PIPE_AXIS), params["layers"])
        else:
            # stage dim over PIPE + Megatron layout over MODEL:
            # qkv/w1 column-parallel (outputs), wo/w2 row-parallel (inputs)
            ln = {"scale": P(PIPE_AXIS, None), "bias": P(PIPE_AXIS, None)}
            layer_specs = {
                "ln1": dict(ln), "ln2": dict(ln),
                "wq": P(PIPE_AXIS, None, MODEL_AXIS),
                "wk": P(PIPE_AXIS, None, MODEL_AXIS),
                "wv": P(PIPE_AXIS, None, MODEL_AXIS),
                "wo": P(PIPE_AXIS, MODEL_AXIS, None),
                "w1": P(PIPE_AXIS, None, MODEL_AXIS),
                "b1": P(PIPE_AXIS, MODEL_AXIS),
                "w2": P(PIPE_AXIS, MODEL_AXIS, None),
                "b2": P(PIPE_AXIS, None),
            }
        replicated = [k for k in params if k != "layers"]
        self._param_specs = {
            "layers": layer_specs,
            **{k: jax.tree_util.tree_map(lambda _: P(), params[k])
               for k in replicated}}
        shardings = jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), self._param_specs,
            is_leaf=lambda x: isinstance(x, P))
        self.params = jax.tree_util.tree_map(
            lambda a, s: jax.device_put(jnp.asarray(a), s), params, shardings)
        # sgd exists for gradient-PARITY testing: Adam is invariant to
        # uniform gradient scaling, so only a scale-sensitive optimizer can
        # detect a collective-transpose overcount (e.g. a bare psum over
        # the pipe axis scaling every grad by pp)
        self._opt = optax.adam(lr) if optimizer == "adam" else optax.sgd(lr)
        self.opt_state = self._opt.init(self.params)
        batch_spec = (P(DATA_AXIS, SEQ_AXIS) if cp > 1
                      else P(DATA_AXIS, None))
        self._batch_sharding = NamedSharding(mesh, batch_spec)

        h_loc = model.n_heads // tp   # local heads per model shard (dense)
        d = model.d_model
        dh = d // model.n_heads if model.n_heads else 0
        M = n_microbatches
        S_P = n_stages
        # axis PRESENCE (not size) selects the sharded code paths: a mesh
        # with a size-1 model/seq axis runs the full Megatron f/g + ring
        # machinery over a singleton axis (psum/ppermute = identity).
        # That is what lets one real chip execute — and memory-validate —
        # the exact 4D program that a pod would run (BENCH_LM_MESH=4d).
        tp_axis = MODEL_AXIS if MODEL_AXIS in mesh.axis_names else None
        cp_axis = SEQ_AXIS if SEQ_AXIS in mesh.axis_names else None
        opt = self._opt
        cdt = jnp.dtype(compute_dtype)
        hybrid = model.hybrid
        # a hybrid model's step returns, with its loss, what its expert
        # layers counted: (pairs routed, pairs held, sum over expert-layer
        # calls of the held experts' max load over their mean, calls)
        n_stats = 4

        def device_loss(p, tokens):
            """Per-device GPipe forward; returns the replicated global loss
            (a hybrid model: and its expert layers' counts).
            p["layers"] leaves are this stage's (L/P, ...) slice; with cp,
            `tokens` is also a SEQUENCE shard and positions are global."""
            if cdt != jnp.float32 and hybrid:
                # as below, but vectors and the convolution's taps stay f32
                with jax.named_scope(tnames.LM_CAST):
                    p = jax.tree_util.tree_map_with_path(
                        lambda path, a: a if path[-1].key in F32_LEAVES
                        else a.astype(cdt), p)
            elif cdt != jnp.float32:
                # one differentiable downcast per step: grads flow back to
                # the f32 masters through the cast's transpose. Layer-norm
                # scale/bias ride along in bf16 — _layer_norm upcasts its
                # math to f32 internally either way
                with jax.named_scope(tnames.LM_CAST):
                    p = jax.tree_util.tree_map(
                        lambda a: a.astype(cdt)
                        if a.dtype == jnp.float32 else a, p)
            s_idx = jax.lax.axis_index(PIPE_AXIS)
            b_loc, S_loc = tokens.shape
            mb = b_loc // M
            mbs = tokens.reshape(M, mb, S_loc)
            seq_off = (jax.lax.axis_index(cp_axis) * S_loc if cp_axis
                       else 0)
            # next-token targets: shift by one GLOBAL position — the last
            # local position's target is the NEXT seq shard's first token
            # (computed once, outside the tick cond: a collective inside a
            # cond is only safe when the whole ring agrees on the branch)
            if cp_axis:
                first_next = jax.lax.ppermute(
                    mbs[:, :, 0], cp_axis,
                    [(j, (j - 1) % cp) for j in range(cp)])
            else:
                first_next = mbs[:, :, 0]
            tgt_mbs = jnp.concatenate([mbs[:, :, 1:],
                                       first_next[:, :, None]], axis=2)
            # the GLOBALLY last position has no target
            is_last_shard = (jax.lax.axis_index(cp_axis) == cp - 1) \
                if cp_axis else True
            pos_mask = jnp.where(
                (jnp.arange(S_loc) == S_loc - 1) & is_last_shard, 0.0, 1.0)

            def apply_hybrid_stage(x):
                """(mb, S, d) through this stage's periods, each the
                description's sequence of layer kinds; every sublayer is
                recomputed in the backward pass when `remat` is set."""
                def one_period(h_x, lps):
                    stats = jnp.zeros((n_stats,), jnp.float32)
                    for kind, lp in zip(model.period, lps):
                        h_x, (routed, held, load) = hybrid_layer(
                            h_x, lp, kind, model, attention, bool(remat))
                        stats = stats + jnp.stack(
                            [routed, held, load, jnp.float32(1.0)])
                    return h_x, stats
                x, stats = jax.lax.scan(one_period, x, p["layers"])
                return x, stats.sum(0)

            def apply_stage(x):      # (mb, S, d) through this stage's layers
                if remat == "save_attn":
                    # attention residuals stored (the flash forward is
                    # the costliest thing to re-run at long context);
                    # only the FF sublayer recomputes in backward
                    attn = lambda h_x, lp: jax.vmap(lambda xx: _block_attn(
                        xx, lp, h_loc, dh, attention=attention,
                        tp_axis=tp_axis, cp_axis=cp_axis))(h_x)
                    ffp = jax.checkpoint(
                        lambda h_x, lp: jax.vmap(lambda xx: _block_ff(
                            xx, lp, tp_axis=tp_axis))(h_x))
                    blk = lambda h_x, lp: ffp(attn(h_x, lp), lp)
                else:
                    blk = lambda h_x, lp: jax.vmap(lambda xx: _block(
                        xx, lp, h_loc, dh, attention=attention,
                        tp_axis=tp_axis, cp_axis=cp_axis))(h_x)
                    if remat:
                        # backward recomputes the block from its
                        # (mb, S, d) input instead of keeping
                        # qkv/scores/gelu residents
                        blk = jax.checkpoint(blk)

                def one_layer(h_x, lp):
                    return blk(h_x, lp), None
                x, _ = jax.lax.scan(one_layer, x, p["layers"])
                return x

            def embed_mb(tok):       # (mb, S) -> (mb, S, d)
                with jax.named_scope(tnames.LM_EMBED):
                    if hybrid:       # positions are rotary, inside attention
                        return p["embed"][tok]
                    pos = jax.lax.dynamic_slice_in_dim(
                        p["pos"], seq_off, S_loc, axis=0)
                    return p["embed"][tok] + pos

            def untied_loss(y, tgt):
                """Final RMSNorm and the untied head, `_HEAD_CHUNK`
                positions at a time, each chunk's logits recomputed in the
                backward pass: float32 logits and their gradient exist for
                one chunk, not for the microbatch."""
                from .hybrid_layers import rms_norm
                with jax.named_scope(tnames.LM_HEAD):
                    n_chunks = -(-S_loc // _HEAD_CHUNK)
                    pad = n_chunks * _HEAD_CHUNK - S_loc

                    def chunked(a):      # (mb, S, ...) -> (chunks, mb, C, ...)
                        a = jnp.pad(a, ((0, 0), (0, pad))
                                    + ((0, 0),) * (a.ndim - 2))
                        a = a.reshape((a.shape[0], n_chunks, _HEAD_CHUNK)
                                      + a.shape[2:])
                        return jnp.moveaxis(a, 1, 0)

                    @jax.checkpoint
                    def one_chunk(acc, xs):
                        y_c, tgt_c, mask_c = xs
                        z = rms_norm(y_c, p["final_norm"], model.norm_eps)
                        logits = jnp.einsum(
                            "msd,vd->msv", z, p["head"],
                            preferred_element_type=jnp.float32)
                        logp = jax.nn.log_softmax(logits, axis=-1)
                        nll = -jnp.take_along_axis(
                            logp, tgt_c[..., None], axis=-1)[..., 0]
                        return acc + (nll * mask_c).sum(), None

                    mask = jnp.broadcast_to(pos_mask, tgt.shape)
                    total, _ = jax.lax.scan(
                        one_chunk, jnp.float32(0.0),
                        (chunked(y), chunked(tgt), chunked(mask)))
                    return total

            def mb_loss(y, tgt):     # final-stage head: local masked SUM
                from .transformer import _layer_norm
                if hybrid:
                    return untied_loss(y, tgt)
                with jax.named_scope(tnames.LM_HEAD):
                    z = _layer_norm(y, p["final_ln"])
                    # tied softmax head: bf16 operands at the MXU's bf16
                    # rate, but logits ACCUMULATE f32 (bf16 logits would
                    # feed log_softmax 8-bit mantissas at vocab-size
                    # dynamic range)
                    logits = jnp.einsum("msd,vd->msv", z, p["embed"],
                                        preferred_element_type=jnp.float32)
                    logp = jax.nn.log_softmax(logits, axis=-1)
                    nll = -jnp.take_along_axis(logp, tgt[..., None],
                                               axis=-1)[..., 0]
                    return (nll * pos_mask).sum()

            def tick(carry, t):
                act, acc = carry[:2]
                # lax.cond, not where: where would run the embedding lookup
                # on every stage and the full vocab-width LM head on every
                # tick — cond pays each only where its result is consumed
                x_in = jax.lax.cond(
                    s_idx == 0,
                    lambda: embed_mb(mbs[jnp.clip(t, 0, M - 1)]),
                    lambda: act)
                if hybrid:
                    y, stats = apply_hybrid_stage(x_in)
                    # a stage counts the ticks in which it held a microbatch
                    live = (t >= s_idx) & (t - s_idx < M)
                    counts = carry[2] + jnp.where(live, stats, 0.0)
                else:
                    y = apply_stage(x_in)
                out_idx = t - (S_P - 1)
                valid = ((out_idx >= 0) & (out_idx < M)
                         & (s_idx == S_P - 1))
                tgt_out = tgt_mbs[jnp.clip(out_idx, 0, M - 1)]
                acc = acc + jax.lax.cond(
                    valid, lambda: mb_loss(y, tgt_out), lambda: 0.0)
                act = jax.lax.ppermute(
                    y, PIPE_AXIS,
                    [(i, (i + 1) % S_P) for i in range(S_P)])
                return ((act, acc, counts) if hybrid else (act, acc)), None

            act0 = jnp.zeros((mb, S_loc, d), cdt)
            carry0 = (act0, jnp.float32(0.0))
            if hybrid:
                carry0 += (jnp.zeros((n_stats,), jnp.float32),)
            (_, acc, *counts), _ = jax.lax.scan(tick, carry0,
                                                jnp.arange(M + S_P - 1))
            # loss lives on the last stage; g-operator (psum forward,
            # IDENTITY backward) over BOTH pipe and seq shards — a bare
            # psum's transpose under check_vma=False is another psum, which
            # would scale every parameter gradient by the pipe degree
            # (Adam masks it; SGD/weight-decay/grad-clip would not).
            # Normalize by the global valid-position count, average dp.
            loss = _tp_g(PIPE_AXIS)(acc)
            if cp_axis:
                loss = _tp_g(cp_axis)(loss)
            denom = M * mb * (S_loc * cp - 1)
            loss = jax.lax.pmean(loss / denom, DATA_AXIS)
            if not hybrid:
                return loss
            return loss, jax.lax.psum(jax.lax.stop_gradient(counts[0]),
                                      (PIPE_AXIS, DATA_AXIS))

        def fwd_bwd(p, tokens):
            loss, grads = jax.value_and_grad(device_loss, has_aux=hybrid)(
                p, tokens)
            if hybrid:
                # one vector leaves the program, so the host reads the
                # counts with the loss: (loss, pairs routed, pairs held,
                # mean over expert-layer calls of max load over mean load)
                loss, counts = loss
                loss = jnp.concatenate(
                    [loss[None], counts[:2], counts[2:3] / counts[3]])
            # dp gradient all-reduce; stage-sharded layer grads stay local
            # to their pipe coordinate; replicated leaves (embed/pos/
            # final_ln) are psum'd over pipe below — each stage holds a
            # DISJOINT partial (embed grads come only from stages 0 and
            # P-1), so the SUM is required, not a mean
            grads = jax.tree_util.tree_map(
                lambda g: jax.lax.pmean(g, DATA_AXIS), grads)
            if cp_axis:
                # every leaf's grad covers only the local sequence shard's
                # positions: sum the partitions
                grads = jax.tree_util.tree_map(
                    lambda g: jax.lax.psum(g, cp_axis), grads)
            rep = {k: jax.tree_util.tree_map(
                lambda g: jax.lax.psum(g, PIPE_AXIS), grads[k])
                for k in replicated}
            grads = {**grads, **rep}
            return loss, grads

        mapped = shard_map(
            fwd_bwd, mesh=mesh,
            in_specs=(self._param_specs, batch_spec),
            out_specs=(P(), self._param_specs), check_vma=False)

        # donate params + opt state ON TPU: without donation every step
        # allocates a fresh ~3x-model-size output tree while the old one
        # lingers — measured 2.14 s/step vs 0.46 s donated for a
        # 201M-param model on v5e (allocator churn, not compute). step()
        # reassigns self.params/opt_state from the outputs, so the donated
        # inputs are never reused. NOT donated on CPU: input-output buffer
        # aliasing under the multi-device CPU backend + shard_map
        # collectives SIGABRTs the process (observed on the 8-device test
        # mesh, jax 0.9), and CPU is only the test/dryrun vehicle anyway.
        # (Shared with run()'s multi-step executable.)
        self._donate = ((0, 1) if mesh.devices.flat[0].platform == "tpu"
                        else ())

        def train_step(params, opt_state, tokens):
            loss, grads = mapped(params, tokens)
            with jax.named_scope(tnames.LM_OPT):
                updates, opt_state = opt.update(grads, opt_state, params)
                params = optax.apply_updates(params, updates)
            return params, opt_state, loss

        self._fwd_bwd = jax.jit(mapped)

        def scalar_step(params, opt_state, tokens):
            params, opt_state, loss = train_step(params, opt_state, tokens)
            return params, opt_state, loss[0]

        # raw step kept for run()'s fori_loop body (whose carry is the
        # scalar loss); jitted once here
        self._step_fn = scalar_step if hybrid else train_step
        self._step = jax.jit(train_step, donate_argnums=self._donate)
        self._multi = None   # lazily-built multi-step executable (run())
        self._step_shape = None   # token shape of the last step() call

    def run(self, tokens: np.ndarray, n_steps: int) -> float:
        """n_steps chained updates with ONE host sync; returns the final
        loss. The steps run as a device-side `lax.scan`, so a slow or
        high-latency host never sits between consecutive updates — the
        standard TPU training-loop shape (the per-step `step()` pays a
        host round trip per update). Same batch every step; interleave
        `run` calls for fresh data."""
        import operator

        import jax.numpy as jnp
        self._check_batch(tokens)
        n_steps = operator.index(n_steps)   # 2.9 must raise, not run 2
        if n_steps < 1:
            raise ValueError(f"n_steps must be >= 1, got {n_steps}")
        if self._multi is None:
            from .lm_training import _build_multi_step
            self._multi = _build_multi_step(self._step_fn, self._donate)
        self.params, self.opt_state, loss = self._multi(
            self.params, self.opt_state, self._to_device(tokens),
            jnp.asarray(n_steps, jnp.int32))
        return float(loss)

    def _to_device(self, tokens):
        import jax
        import jax.numpy as jnp
        return jax.device_put(jnp.asarray(tokens, jnp.int32),
                              self._batch_sharding)

    def _check_batch(self, tokens) -> None:
        from ...parallel import DATA_AXIS
        dp = self.mesh.shape[DATA_AXIS]
        B = tokens.shape[0]
        if B % (dp * self.n_microbatches):
            raise ValueError(
                f"batch {B} must divide by dp*microbatches = "
                f"{dp * self.n_microbatches}")
        if tokens.shape[1] % self.cp:
            raise ValueError(
                f"sequence length {tokens.shape[1]} must divide by the "
                f"seq axis ({self.cp})")

    def step(self, tokens: np.ndarray) -> float:
        """One dp x pp (x tp) (x cp) update; returns the batch loss.

        Three host spans at the step's layer boundaries (`lm.step.h2d`,
        `lm.step.dispatch`, `lm.step.wait`; none adds a device sync) and
        the `lm.step.compiles` counter: what the step program itself
        compiled during the dispatch, by its own jit cache."""
        self._check_batch(tokens)
        with annotate(tnames.LM_STEP_H2D):
            d_tokens = self._to_device(tokens)
        if d_tokens.shape != self._step_shape:
            self._step_shape = d_tokens.shape
            self._register_step_program()
        held = self._step._cache_size()
        with annotate(tnames.LM_STEP_DISPATCH):
            self.params, self.opt_state, loss = self._step(
                self.params, self.opt_state, d_tokens)
        compiled = self._step._cache_size() - held
        if compiled:
            reliability_metrics.inc(tnames.LM_STEP_COMPILES, compiled)
        with annotate(tnames.LM_STEP_WAIT):
            out = np.asarray(loss)
        if out.ndim:
            # a hybrid model's step: the expert layers' counts came with it
            reliability_metrics.inc(tnames.MOE_PAIRS_ROUTED, int(out[1]))
            reliability_metrics.inc(tnames.MOE_PAIRS_HELD, int(out[2]))
            reliability_metrics.set_gauge(tnames.MOE_LOAD_MAX_OVER_MEAN,
                                          float(out[3]))
            return float(out[0])
        return float(out)

    def loss_and_grads(self, tokens: np.ndarray) -> tuple:
        """(loss, gradient tree) of one batch by the step's own forward and
        backward pass, with no update: what parity tests compare with a
        reference's `jax.grad`."""
        self._check_batch(tokens)
        loss, grads = self._fwd_bwd(self.params, self._to_device(tokens))
        loss = np.asarray(loss)
        return float(loss[0] if loss.ndim else loss), grads

    def _register_step_program(self) -> None:
        """Name the step program of the last `step()` call's shapes for
        readers of captures (`telemetry.perf.scope_maps`): one dict insert
        per change of shape. The thunk keeps the jitted step and the
        shapes of its arguments, not the trainer or an array, and lowers
        and compiles only when a reader asks (with a persistent compile
        cache that is a fetch), so it outlives the trainer at no device
        memory: a benchmark reads its capture after the driver returned."""
        import jax
        import jax.numpy as jnp
        step = self._step
        args = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=a.sharding),
            (self.params, self.opt_state))
        tokens = jax.ShapeDtypeStruct(self._step_shape, jnp.int32,
                                      sharding=self._batch_sharding)
        register_program(
            f"lm.step#{id(self):x}",
            lambda: step.lower(*args, tokens).compile().as_text())

    # -- checkpoint/resume ---------------------------------------------------
    # Shared implementation with ShardedLMTrainer (one format, one code
    # path); restore re-places every leaf with the LIVE stage/tensor
    # shardings — the live leaves carry the 3D layout — so the next step()
    # resumes exactly.
    def save_checkpoint(self, directory: str, step: int) -> None:
        from .lm_training import save_lm_checkpoint
        save_lm_checkpoint(directory, step, self.params, self.opt_state,
                           self.meta, tag="pp_ckpt")

    def restore_checkpoint(self, directory: str, step: int = None) -> int:
        from .lm_training import restore_lm_checkpoint
        self.params, self.opt_state, step = restore_lm_checkpoint(
            directory, step, self.params, self.opt_state, self.meta)
        return step
