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
  slices over "model" (f/g operators, `parallel/megatron.py`), and
  sequence shards over "seq" (ring attention with global causal offsets;
  cross-shard next-token targets by ppermute). Any subset of the axes a
  model family has a form for works — see the PipelinedLMTrainer
  docstring.

The reference has no sequence models at all (SURVEY §5) — this file exists
because long-context/distributed training is first-class in the TPU build,
not because a Scala counterpart does.
"""
from __future__ import annotations

import numpy as np

from ...parallel.megatron import tp_g
from ...reliability.metrics import reliability_metrics
from ...telemetry import names as tnames
from ...telemetry.perf import register_program
from ...telemetry.profiler import StepRecorder
from ...utils.tracing import annotate
from .lm_spec import LMSpec, gpt2_spec
from .lm_training import _build_multi_step


class PipelinedLMTrainer:
    """dp x pp (x tp) (x cp) trainer: one jitted shard_map train step.

    The mesh's axes pick the composition — every combination is
    oracle-parity-tested (tests/test_pp_training.py):

        grid_mesh((dp, pp), (DATA_AXIS, PIPE_AXIS))                # 2D
        grid_mesh((dp, pp, tp), (..., MODEL_AXIS))                 # 3D
        grid_mesh((dp, pp, tp, cp), (..., SEQ_AXIS))               # 4D

    What shards over which axis is in the module docstring.
    loss = t.step(tokens): (B, S) int32, B % (dp * n_microbatches) == 0,
    S % cp == 0.

    What is trained is a description (`model`, an `lm_spec.LMSpec`): a
    period of layer kinds and how often it repeats. The six integers
    (`vocab_size` .. `max_len`) are the dense GPT-2 block's description,
    built here when no `model` is given. Parameters stack by position in
    the period; `n_periods` must divide by the pipe axis.

    This class is the SCHEDULER: mesh, GPipe ticks, target shift, loss and
    gradient collectives, optimizer, host spans. What is inside the model
    belongs to the description's FAMILY (`model.family`, a module beside
    this one; docs/dnn.md "Model families"): parameters and their layout,
    an embedding, a stage of stacked periods, a head that returns a masked
    loss sum, what the stage counts for the host, and the mesh axes it
    has a form for (`family.AXES`).
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
        bf16 for every matmul while norms, softmax and the loss accumulate
        in f32. remat=True (= "full") has the backward pass recompute each
        sublayer from the layer boundaries instead of storing its
        activations; remat="save_attn" keeps what the family's `stage` says
        is worth keeping. What each costs on the chip: PERF.md section 5."""
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
        family = self._family = model.family
        n_periods = model.n_periods
        import jax
        import jax.numpy as jnp
        import optax
        from jax.sharding import NamedSharding, PartitionSpec as P
        from ...parallel import (DATA_AXIS, MODEL_AXIS, PIPE_AXIS, SEQ_AXIS,
                                 grid_mesh)
        from jax import shard_map

        if mesh is None:
            n = jax.device_count()
            pp = max(d for d in range(1, n_periods + 1)
                     if n_periods % d == 0 and n % d == 0)
            mesh = grid_mesh((n // pp, pp), (DATA_AXIS, PIPE_AXIS))
        formless = [a for a in mesh.axis_names if a not in family.AXES]
        if formless:
            raise ValueError(
                f"a {family.__name__} model trains on the "
                f"{' and '.join(family.AXES)} axes only: its layers have "
                f"no form for the mesh's {formless}")
        n_stages = mesh.shape[PIPE_AXIS]
        if n_periods % n_stages:
            raise ValueError(
                f"n_periods ({n_periods}; for a dense block, n_layers) must "
                f"divide by the pipe axis ({n_stages}) so every stage holds "
                f"the same layer count")
        # optional third axis: Megatron tensor parallelism inside a stage;
        # optional fourth: context parallelism, the SEQUENCE sharded
        tp = mesh.shape[MODEL_AXIS] if MODEL_AXIS in mesh.axis_names else 1
        cp = mesh.shape[SEQ_AXIS] if SEQ_AXIS in mesh.axis_names else 1
        self.mesh = mesh
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

        # "layers" is what the pipe axis shards (leaves (n_periods, ...)),
        # the model axis by the family's layout; all else is replicated
        params = family.init(model, seed)
        self.meta = model.meta
        replicated = [k for k in params if k != "layers"]
        param_specs = {
            "layers": (family.partition(model, tp) if tp > 1 else
                       jax.tree_util.tree_map(lambda _: P(PIPE_AXIS),
                                              params["layers"])),
            **{k: jax.tree_util.tree_map(lambda _: P(), params[k])
               for k in replicated}}
        shardings = jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), param_specs,
            is_leaf=lambda x: isinstance(x, P))
        self.params = jax.tree_util.tree_map(
            lambda a, s: jax.device_put(jnp.asarray(a), s), params, shardings)
        # sgd exists for gradient-PARITY testing: Adam is invariant to
        # uniform gradient scaling, so only a scale-sensitive optimizer can
        # detect a collective-transpose overcount (e.g. a bare psum over
        # the pipe axis scaling every grad by pp)
        opt = optax.adam(lr) if optimizer == "adam" else optax.sgd(lr)
        self.opt_state = opt.init(self.params)
        batch_spec = (P(DATA_AXIS, SEQ_AXIS) if cp > 1
                      else P(DATA_AXIS, None))
        self._batch_sharding = NamedSharding(mesh, batch_spec)

        M = n_microbatches
        S_P = n_stages
        # axis PRESENCE (not size) selects the sharded code paths: a mesh
        # with a size-1 model/seq axis runs the full Megatron f/g + ring
        # machinery over a singleton axis (psum/ppermute = identity).
        # That is what lets one real chip execute — and memory-validate —
        # the exact 4D program that a pod would run.
        tp_axis = MODEL_AXIS if MODEL_AXIS in mesh.axis_names else None
        cp_axis = SEQ_AXIS if SEQ_AXIS in mesh.axis_names else None
        cdt = jnp.dtype(compute_dtype)

        def device_loss(p, tokens):
            """Per-device GPipe forward; returns the replicated global loss
            and the family's stats, summed over every stage's live ticks.
            p["layers"] leaves are this stage's (L/P, ...) slice; with cp,
            `tokens` is also a SEQUENCE shard and positions are global."""
            if cdt != jnp.float32:
                # one differentiable downcast per step: grads flow back to
                # the f32 masters through the cast's transpose
                with jax.named_scope(tnames.LM_CAST):
                    p = family.cast(p, cdt)
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

            def embed_mb(tok):       # (mb, S) -> (mb, S, d)
                with jax.named_scope(tnames.LM_EMBED):
                    return family.embed(p, tok, seq_off)

            def mb_loss(y, tgt):     # final-stage head: local masked SUM
                with jax.named_scope(tnames.LM_HEAD):
                    return family.head_loss(p, y, tgt, pos_mask, model)

            def tick(carry, t):
                act, acc, counts = carry
                # lax.cond, not where: where would run the embedding lookup
                # on every stage and the full vocab-width LM head on every
                # tick — cond pays each only where its result is consumed
                x_in = jax.lax.cond(
                    s_idx == 0,
                    lambda: embed_mb(mbs[jnp.clip(t, 0, M - 1)]),
                    lambda: act)
                # every sublayer scopes itself and the innermost region
                # wins: what lands here is what the layer scans add
                with jax.named_scope(tnames.LM_LAYERS):
                    y, stats = family.stage(
                        x_in, p["layers"], model, attention=attention,
                        remat=remat, tp_axis=tp_axis, cp_axis=cp_axis)
                # a stage counts the ticks in which it held a microbatch
                live = (t >= s_idx) & (t - s_idx < M)
                counts = jax.tree_util.tree_map(
                    lambda c, s: c + jnp.where(live, s, 0.0), counts, stats)
                out_idx = t - (S_P - 1)
                valid = ((out_idx >= 0) & (out_idx < M)
                         & (s_idx == S_P - 1))
                tgt_out = tgt_mbs[jnp.clip(out_idx, 0, M - 1)]
                acc = acc + jax.lax.cond(
                    valid, lambda: mb_loss(y, tgt_out), lambda: 0.0)
                act = jax.lax.ppermute(
                    y, PIPE_AXIS,
                    [(i, (i + 1) % S_P) for i in range(S_P)])
                return (act, acc, counts), None

            carry0 = (jnp.zeros((mb, S_loc, model.d_model), cdt),
                      jnp.float32(0.0),
                      jax.tree_util.tree_map(
                          lambda s: jnp.zeros(s.shape, s.dtype),
                          family.STATS))
            with jax.named_scope(tnames.LM_TICKS):
                (_, acc, counts), _ = jax.lax.scan(tick, carry0,
                                                   jnp.arange(M + S_P - 1))
            # loss lives on the last stage; g-operator (psum forward,
            # IDENTITY backward) over BOTH pipe and seq shards — a bare
            # psum's transpose under check_vma=False is another psum, which
            # would scale every parameter gradient by the pipe degree
            # (Adam masks it; SGD/weight-decay/grad-clip would not).
            # Normalize by the global valid-position count, average dp.
            loss = tp_g(acc, PIPE_AXIS)
            if cp_axis:
                loss = tp_g(loss, cp_axis)
            denom = M * mb * (S_loc * cp - 1)
            loss = jax.lax.pmean(loss / denom, DATA_AXIS)
            return loss, jax.tree_util.tree_map(
                lambda c: jax.lax.psum(jax.lax.stop_gradient(c),
                                       (PIPE_AXIS, DATA_AXIS)), counts)

        def fwd_bwd(p, tokens):
            (loss, counts), grads = jax.value_and_grad(
                device_loss, has_aux=True)(p, tokens)
            # ONE array leaves the program, so the host reads what the
            # family makes of its counts with the loss: (loss, *summary)
            told = family.summary(counts)
            if told:
                loss = jnp.concatenate([loss[None], *told])
            # dp gradient all-reduce; stage-sharded layer grads stay local
            # to their pipe coordinate; replicated leaves (embedding, head)
            # are psum'd over pipe below — each stage holds a DISJOINT
            # partial (a tied embedding's grads come only from stages 0
            # and P-1), so the SUM is required, not a mean
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
            in_specs=(param_specs, batch_spec),
            out_specs=(P(), param_specs), check_vma=False)

        # donate params + opt state ON TPU: without donation every step
        # allocates a fresh ~3x-model-size output tree while the old one
        # lingers (allocator churn, several times the step). step()
        # reassigns self.params/opt_state from the outputs, so the donated
        # inputs are never reused. NOT donated on CPU: input-output buffer
        # aliasing under the multi-device CPU backend + shard_map
        # collectives SIGABRTs the process (observed on the 8-device test
        # mesh, jax 0.9), and CPU is only the test/dryrun vehicle anyway.
        donate = (0, 1) if mesh.devices.flat[0].platform == "tpu" else ()

        def train_step(params, opt_state, tokens):
            loss, grads = mapped(params, tokens)
            with jax.named_scope(tnames.LM_OPT):
                updates, opt_state = opt.update(grads, opt_state, params)
                params = optax.apply_updates(params, updates)
            return params, opt_state, loss

        def loss_only(params, opt_state, tokens):
            # run()'s fori_loop carries the scalar loss
            params, opt_state, out = train_step(params, opt_state, tokens)
            return params, opt_state, out.ravel()[0]

        self._fwd_bwd = jax.jit(mapped)
        self._step = jax.jit(train_step, donate_argnums=donate)
        self._multi = _build_multi_step(loss_only, donate)
        self._step_shape = None   # token shape of the last step() call
        self._record = StepRecorder()

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
        `lm.step.dispatch`, `lm.step.wait`; none adds a device sync), a
        fourth between two calls (`lm.step.gap`: the caller's batch), the
        `lm.step.compiles` counter (what the step program itself compiled
        during the dispatch, by its own jit cache), and one record a step
        of the four with what the host did meanwhile
        (`telemetry.profiler.step_records`; docs/observability.md "The
        step record")."""
        self._check_batch(tokens)
        record = self._record
        record.start()
        with annotate(tnames.LM_STEP_H2D):
            d_tokens = self._to_device(tokens)
        record.mark()
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
        record.mark()
        with annotate(tnames.LM_STEP_WAIT):
            out = np.asarray(loss).ravel()
        record.stop(compiled)
        # what the family's stages counted came with the loss
        self._family.report(out[1:])
        return float(out[0])

    def loss_and_grads(self, tokens: np.ndarray) -> tuple:
        """(loss, gradient tree) of one batch by the step's own forward and
        backward pass, with no update: what parity tests compare with a
        reference's `jax.grad`."""
        self._check_batch(tokens)
        loss, grads = self._fwd_bwd(self.params, self._to_device(tokens))
        return float(np.asarray(loss).ravel()[0]), grads

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
