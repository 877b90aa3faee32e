"""Sharded transformer LM training: one jitted dp x tp step over a mesh.

The GSPMD counterpart of the framework's shard_map engines: parameters are
laid out over the mesh's model axis (attention heads / FFN hidden), batches
over the data axis, and ONE `jax.jit` with sharding-annotated inputs lets
XLA insert the collectives (all-reduce of dp gradients, tp activation
all-gathers) — the "pick a mesh, annotate shardings, let XLA do the rest"
recipe. This is the training-side complement of parallel/ring_attention's
inference-side sequence parallelism.

Layout (Megatron-style):
- wq/wk/wv: (d, d) sharded on the OUTPUT dim (head-parallel);
  wo: (d, d) sharded on the INPUT dim (row-parallel, output all-reduced).
- w1: (d, d_ff) sharded on d_ff; w2: (d_ff, d) sharded on d_ff.
- embed/pos/layernorms replicated; batch sharded over the data axis.
"""
from __future__ import annotations

import numpy as np

from .transformer import init_transformer, transformer_apply
from ...telemetry.names import LM_RUN_STREAM_SPAN


def _param_shardings(params: dict, mesh):
    """NamedSharding tree for the Megatron layout above."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from ...parallel import MODEL_AXIS

    def ns(*spec):
        return NamedSharding(mesh, P(*spec))

    rep = ns()
    layer = {
        "ln1": {"scale": rep, "bias": rep},
        "wq": ns(None, MODEL_AXIS), "wk": ns(None, MODEL_AXIS),
        "wv": ns(None, MODEL_AXIS), "wo": ns(MODEL_AXIS, None),
        "ln2": {"scale": rep, "bias": rep},
        "w1": ns(None, MODEL_AXIS), "b1": ns(MODEL_AXIS),
        "w2": ns(MODEL_AXIS, None), "b2": rep,
    }
    return {
        "embed": rep, "pos": rep,
        "layers": [dict(layer) for _ in params["layers"]],
        "final_ln": {"scale": rep, "bias": rep},
    }


def _build_multi_step(step_fn, donate, out_shardings=None):
    """Jitted (params, opt_state, tok, n) -> (params, opt_state, last
    loss): n optimizer steps as a device-side fori_loop with n as a
    TRACED bound — one executable serves every chunk size (a static
    count would recompile the full program per distinct n). Shared by
    ShardedLMTrainer.run and PipelinedLMTrainer.run; step_fn is the
    UN-jitted single step so donation applies once, at this boundary.
    `out_shardings` pins outputs to the canonical layout (see
    ShardedLMTrainer's single-executable contract)."""
    import functools

    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, donate_argnums=donate,
                       out_shardings=out_shardings)
    def multi(params, opt_state, tok, n):
        def body(_, carry):
            p, o, _l = carry
            return step_fn(p, o, tok)
        return jax.lax.fori_loop(0, n, body,
                                 (params, opt_state, jnp.float32(0.0)))
    return multi


def _lm_loss(params, meta, tokens):
    """Mean next-token cross-entropy for a (B, S) batch (causal).
    The forward pass IS transformer_apply (causal, unit attention scale —
    the 1/sqrt(dh) is folded into it by its default) — one encoder
    implementation for inference and training."""
    import jax
    import jax.numpy as jnp

    full = dict(params)
    full["meta"] = meta
    emb = jax.vmap(lambda tok: transformer_apply(full, tok, causal=True)
                   )(tokens)                           # (B, S, d)
    logits = emb @ params["embed"].T                   # tied softmax
    targets = tokens[:, 1:]
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    return nll.mean()


class ShardedLMTrainer:
    """Owns sharded params + one compiled dp x tp train step.

    Usage:
        trainer = ShardedLMTrainer(vocab, mesh=grid_mesh((2, 4)))
        loss = trainer.step(tokens)   # (B, S) int32, B % dp == 0
    """

    def __init__(self, vocab_size: int, mesh=None, d_model: int = 128,
                 n_heads: int = 8, n_layers: int = 2, d_ff: int = 256,
                 max_len: int = 512, lr: float = 1e-3, seed: int = 0):
        import jax
        import jax.numpy as jnp
        import optax
        from jax.sharding import NamedSharding, PartitionSpec as P
        from ...parallel import DATA_AXIS, MODEL_AXIS, grid_mesh

        if mesh is None:
            n = jax.device_count()
            # largest divisor of n_heads that also divides the device count
            tp = max((d for d in range(1, n_heads + 1)
                      if n_heads % d == 0 and n % d == 0), default=1)
            mesh = grid_mesh((n // tp, tp))
        tp_size = mesh.shape[MODEL_AXIS]
        if n_heads % tp_size:
            raise ValueError(
                f"n_heads ({n_heads}) must divide by the model axis "
                f"({tp_size}) for head-parallel attention")
        if d_model % n_heads:
            raise ValueError(
                f"d_model ({d_model}) must divide by n_heads ({n_heads})")
        if d_ff % tp_size:
            raise ValueError(
                f"d_ff ({d_ff}) must divide by the model axis ({tp_size}) "
                f"for column-parallel FFN sharding")
        self.mesh = mesh
        raw = init_transformer(vocab_size, d_model, n_heads, n_layers,
                               d_ff, max_len, seed)
        self.meta = raw.pop("meta")
        shardings = _param_shardings({"layers": raw["layers"]}, mesh)
        self.params = jax.tree_util.tree_map(
            lambda a, s: jax.device_put(jnp.asarray(a), s), raw, shardings,
            is_leaf=lambda x: isinstance(x, np.ndarray))
        self._opt = optax.adam(lr)
        self.opt_state = self._opt.init(self.params)
        # optax init leaves its step-count scalar UNCOMMITTED while every
        # jitted step returns it committed replicated-on-mesh — two
        # different executables (cache keys differ), whose reduction
        # orders need not agree. Committing it replicated here makes the
        # first step, every later step, AND a checkpoint-restored step all
        # hit ONE executable — the precondition for bit-deterministic
        # crash-resume (lm_state_from_payload places restored leaves the
        # same way).
        rep = NamedSharding(mesh, P())
        self.opt_state = jax.tree_util.tree_map(
            lambda a: a if getattr(a, "committed", True)
            else jax.device_put(a, rep), self.opt_state)
        self._batch_sharding = NamedSharding(mesh, P(DATA_AXIS, None))

        opt = self._opt
        meta = self.meta

        import functools

        # donate params + opt state ON TPU: non-donated steps leave a
        # fresh ~3x-model-size output tree per call and measured 4.6x
        # slower on the dev chip (see pp_training.train_step for why CPU
        # must NOT donate — multi-device CPU aliasing
        # SIGABRTs under shard_map/collective programs)
        self._donate = ((0, 1) if mesh.devices.flat[0].platform == "tpu"
                        else ())

        def train_step(params, opt_state, tokens):
            loss, grads = jax.value_and_grad(
                lambda p: _lm_loss(p, meta, tokens))(params)
            updates, opt_state = opt.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), opt_state, loss

        # Single-executable contract: XLA's sharding propagation would
        # otherwise emit step outputs in ITS preferred layout (e.g. embed
        # resharded over the model axis), so the first step (constructor
        # placements in) and every later step (jit outputs in) compile two
        # different executables whose reduction orders need not agree —
        # which costs bit-determinism of checkpoint-resume (a restored
        # trainer replays on constructor-style placements). Pinning
        # out_shardings to the canonical Megatron layout makes fresh,
        # steady-state, and restored steps all hit ONE executable.
        self._out_shardings = (
            jax.tree_util.tree_map(lambda a: a.sharding, self.params),
            jax.tree_util.tree_map(lambda a: a.sharding, self.opt_state),
            NamedSharding(mesh, P()))
        # raw step kept for run()'s fori_loop body; jitted once here
        self._step_fn = train_step
        self._step = jax.jit(train_step, donate_argnums=self._donate,
                             out_shardings=self._out_shardings)
        self._multi = None   # lazily-built multi-step executable (run())

    def _to_device(self, tokens):
        import jax
        import jax.numpy as jnp
        return jax.device_put(jnp.asarray(tokens, jnp.int32),
                              self._batch_sharding)

    def step(self, tokens: np.ndarray) -> float:
        """One dp x tp update; returns the batch loss."""
        self.params, self.opt_state, loss = self._step(
            self.params, self.opt_state, self._to_device(tokens))
        return float(loss)

    def run(self, tokens: np.ndarray, n_steps: int) -> float:
        """n_steps chained updates with ONE host sync; returns the final
        loss. Same contract as PipelinedLMTrainer.run: a device-side
        fori_loop with n as a TRACED bound (one executable for every
        chunk size), one host round trip per chunk."""
        import operator

        import jax.numpy as jnp
        n_steps = operator.index(n_steps)
        if n_steps < 1:
            raise ValueError(f"n_steps must be >= 1, got {n_steps}")
        if self._multi is None:
            self._multi = _build_multi_step(self._step_fn, self._donate,
                                            self._out_shardings)
        self.params, self.opt_state, loss = self._multi(
            self.params, self.opt_state, self._to_device(tokens),
            jnp.asarray(n_steps, jnp.int32))
        return float(loss)

    def run_stream(self, batches, steps_per_batch: int = 1,
                   prefetch: int = 2, checkpoint_dir: str = None,
                   checkpoint_every: int = 10, resume: bool = True,
                   step_clock=None, **supervisor_kw) -> list:
        """Train over an iterable of host (B, S) token batches with the
        bounded ingest prefetcher (data.DevicePrefetcher): batch k+1 rides
        host->device transfer (and any upstream tokenize/load work the
        iterable does) WHILE batch k trains — the LM-side use of the
        parallel ingest pipeline's overlap contract. Returns the per-batch
        final losses; `steps_per_batch > 1` chains device-side steps per
        batch through the same fori_loop executable run() uses.

        `checkpoint_dir` turns on fault-tolerant supervision
        (reliability.TrainingSupervisor): params/opt-state are snapshotted
        every `checkpoint_every` batches and written ASYNCHRONOUSLY (the
        step thread never blocks on disk — though each snapshot still
        pays a host gather of params+opt state, so size checkpoint_every
        to your loss-tolerance, not to 1), SIGTERM/SIGINT trigger a final
        synchronous checkpoint then raise `reliability.Preempted`, failed
        steps restart from the last snapshot, and a killed run re-invoked
        with `resume=True` (the default) continues from the newest
        digest-valid checkpoint with BIT-IDENTICAL results to an
        uninterrupted run (the batch cursor and loss history ride in the
        payload). `batches` must then be a finite re-indexable sequence —
        the resumed/rewound run replays from the cursor. Extra kwargs
        (step_timeout, retry_policy, heartbeat, faults, ...) pass through
        to TrainingSupervisor.

        `step_clock` (telemetry.goodput.StepClock; created by default
        when supervised) rides the whole path: the prefetcher notes its
        data-wait on it, the loss fetch books as device-compute, and the
        supervisor decomposes every step into the goodput/MFU account."""
        import operator
        import time as _time

        import jax.numpy as jnp
        from ...data import DevicePrefetcher
        from ...telemetry.spans import get_tracer
        steps_per_batch = operator.index(steps_per_batch)
        if steps_per_batch < 1:
            raise ValueError(
                f"steps_per_batch must be >= 1, got {steps_per_batch}")
        _run_t0 = _time.perf_counter()
        clock = step_clock

        def fetch(loss):
            # float(loss) is THE block-until-ready boundary of a step:
            # the async dispatch's device time surfaces here
            if clock is not None:
                return clock.device_block(lambda: float(loss))
            return float(loss)

        def one_batch(tok_dev):
            if steps_per_batch == 1:
                self.params, self.opt_state, loss = self._step(
                    self.params, self.opt_state, tok_dev)
            else:
                if self._multi is None:
                    self._multi = _build_multi_step(self._step_fn,
                                                    self._donate,
                                                    self._out_shardings)
                self.params, self.opt_state, loss = self._multi(
                    self.params, self.opt_state, tok_dev,
                    jnp.asarray(steps_per_batch, jnp.int32))
            return fetch(loss)

        if checkpoint_dir is None:
            if supervisor_kw:
                raise TypeError(
                    f"supervisor options {sorted(supervisor_kw)} require "
                    f"checkpoint_dir")
            losses = []
            with DevicePrefetcher(batches, depth=prefetch,
                                  put=self._to_device,
                                  step_clock=clock) as pf:
                for tok_dev in pf:
                    losses.append(one_batch(tok_dev))
            get_tracer().record(
                LM_RUN_STREAM_SPAN,
                duration_ms=(_time.perf_counter() - _run_t0) * 1000.0,
                attrs={"steps": len(losses), "supervised": False})
            return losses

        from ...reliability.supervisor import TrainingSupervisor
        from ...telemetry.goodput import StepClock
        import jax
        if clock is None:
            clock = StepClock()
        if jax.process_count() > 1:
            # every process would race the same step dir (save_lm_checkpoint
            # gates on the leader + barriers; the async writer has no such
            # rendezvous yet) — refuse loudly rather than corrupt quietly
            raise NotImplementedError(
                "run_stream(checkpoint_dir=...) is single-process for now; "
                "multi-host jobs should checkpoint via save_lm_checkpoint "
                "(leader-only write + barrier)")
        batches = list(batches)   # rewind/resume needs random access

        def snapshot():
            return lm_state_payload(self.params, self.opt_state, self.meta)

        def restore(payload):
            self.params, self.opt_state = lm_state_from_payload(
                payload, self.params, self.opt_state, self.meta)

        stream = {"pf": None, "it": None}

        def seek(step):
            if stream["pf"] is not None:
                stream["pf"].close()
            pf = DevicePrefetcher(batches[step:], depth=prefetch,
                                  put=self._to_device, step_clock=clock)
            stream["pf"], stream["it"] = pf, iter(pf)

        def step_fn(step):
            return one_batch(next(stream["it"]))

        sup = TrainingSupervisor(checkpoint_dir, snapshot, restore,
                                 checkpoint_every=checkpoint_every,
                                 step_clock=clock, **supervisor_kw)
        try:
            out = sup.run(step_fn, len(batches), seek=seek, resume=resume)
            get_tracer().record(
                LM_RUN_STREAM_SPAN,
                duration_ms=(_time.perf_counter() - _run_t0) * 1000.0,
                attrs={"steps": len(out), "supervised": True,
                       "resumed_step": sup.resumed_step or 0})
            return out
        finally:
            if stream["pf"] is not None:
                stream["pf"].close()
            sup.close()

    # -- checkpoint/resume --------------------------------------------------
    # The reference has nothing comparable (SURVEY §5: "no mid-training
    # checkpointing" — flagged as a must-add); step checkpoints reuse the
    # framework's atomic CheckpointManager and re-place restored leaves with
    # the SAME sharding layout the constructor computes. The save/restore
    # machinery is shared with PipelinedLMTrainer (one implementation, one
    # format — see save_lm_checkpoint / restore_lm_checkpoint below).
    def save_checkpoint(self, directory: str, step: int) -> None:
        save_lm_checkpoint(directory, step, self.params, self.opt_state,
                           self.meta, tag="lm_ckpt")

    def restore_checkpoint(self, directory: str, step: int = None) -> int:
        """Load params + optimizer state from the latest (or given) step;
        returns the restored step. Leaves land back on the mesh with the
        live state's shardings, so the next step() resumes exactly."""
        self.params, self.opt_state, step = restore_lm_checkpoint(
            directory, step, self.params, self.opt_state, self.meta)
        return step


def lm_state_payload(params, opt_state, meta) -> dict:
    """Host-gathered checkpoint payload of an LM trainer's live state (the
    snapshot half of the shared on-disk format; multi-host gathers shards
    so every leaf is addressable from the leader)."""
    import jax
    from .model import tree_to_payload
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils
        params = multihost_utils.process_allgather(params, tiled=True)
        opt_state = multihost_utils.process_allgather(opt_state, tiled=True)
    payload = {"meta": dict(meta)}
    # params: dict/list tree, serialized with its treedef. opt_state:
    # optax NamedTuple nodes don't round-trip through the treedef
    # string — leaves only; restore rebuilds the structure from the
    # live optimizer state (same optimizer config = same structure)
    payload.update(tree_to_payload(params, "p"))
    payload.update(tree_to_payload(opt_state, "o", leaves_only=True))
    return payload


def save_lm_checkpoint(directory: str, step: int, params, opt_state, meta,
                       tag: str) -> None:
    """Leader-only write of host-gathered leaves (shared by the GSPMD and
    pipelined trainers — one implementation, one on-disk format)."""
    import jax
    from ...utils.checkpoint import CheckpointManager
    payload = lm_state_payload(params, opt_state, meta)
    if jax.process_index() == 0:
        CheckpointManager(directory).save(step, payload)
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils
        multihost_utils.sync_global_devices(f"{tag}_{step}")


def restore_lm_checkpoint(directory: str, step, live_params, live_opt_state,
                          meta):
    """Returns (params, opt_state, step) with every leaf re-placed onto the
    LIVE state's shardings — works unchanged for GSPMD and pipelined
    layouts (the live leaves carry the layout)."""
    from ...utils.checkpoint import CheckpointManager
    mgr = CheckpointManager(directory)
    if step is None:
        # latest mode rides restore's corrupt-step fallback (a torn or
        # digest-mismatched newest step must cost one interval, not the
        # run); with_step reports the step ACTUALLY loaded
        payload, step = mgr.restore(with_step=True)
    else:
        payload = mgr.restore(step)
    params, opt_state = lm_state_from_payload(payload, live_params,
                                              live_opt_state, meta)
    return params, opt_state, step


def lm_state_from_payload(payload, live_params, live_opt_state, meta):
    """Apply a checkpoint payload back onto live state: every leaf
    re-placed with the LIVE leaves' shardings (the restore half of the
    shared format; also the supervisor's `restore_fn` body)."""
    import jax
    import jax.numpy as jnp
    from .model import tree_from_payload
    saved_meta = payload.get("meta")
    if saved_meta is not None and dict(saved_meta) != dict(meta):
        raise ValueError(
            f"checkpoint was saved with model config {saved_meta} but "
            f"this trainer has {dict(meta)} — resuming would "
            f"silently train a different model")
    params = tree_from_payload(payload, "p")
    live_p, p_struct = jax.tree_util.tree_flatten(live_params)
    new_p, _ = jax.tree_util.tree_flatten(params)
    if len(new_p) != len(live_p):
        raise ValueError(
            f"checkpoint has {len(new_p)} parameter leaves but this "
            f"trainer expects {len(live_p)} — it was saved by a different "
            f"architecture or trainer layout")
    for i, (a, live) in enumerate(zip(new_p, live_p)):
        # leaf-count alone misses e.g. n_layers=1 stacked-vs-list layouts;
        # a shape check here beats an obscure in-jit rank error later
        if tuple(np.shape(a)) != tuple(live.shape):
            raise ValueError(
                f"checkpoint parameter leaf {i} has shape {np.shape(a)} "
                f"but this trainer expects {tuple(live.shape)} — saved by "
                f"a different architecture or trainer layout")
    restored_params = jax.tree_util.tree_unflatten(
        p_struct, [jax.device_put(a, live.sharding)
                   for a, live in zip(new_p, live_p)])
    # pour the saved leaves into the LIVE optimizer state's structure
    # and shardings (no throwaway init, no unsharded materialization)
    o_leaves = tree_from_payload(payload, "o", leaves_only=True)
    live_leaves, structure = jax.tree_util.tree_flatten(live_opt_state)
    if len(live_leaves) != len(o_leaves):
        raise ValueError(
            f"checkpoint has {len(o_leaves)} optimizer leaves but this "
            f"trainer's optimizer expects {len(live_leaves)} — "
            f"optimizer config changed since the save")
    # match each live leaf's placement. An UNCOMMITTED live leaf (fresh
    # optax init scalars) must not be committed to its CURRENT single
    # device (that conflicts with the sharded params in jit) — but leaving
    # it uncommitted makes the resumed step compile a DIFFERENT executable
    # than the one a continuously-running trainer uses (whose outputs are
    # committed replicated-on-mesh), and different reduction orders cost
    # bit-identity of crash-resume. Place it exactly where a jitted step
    # would: replicated over the params' mesh.
    from jax.sharding import NamedSharding, PartitionSpec
    mesh = next((lv.sharding.mesh for lv in live_p
                 if isinstance(getattr(lv, "sharding", None), NamedSharding)),
                None)
    replicated = (NamedSharding(mesh, PartitionSpec())
                  if mesh is not None else None)

    def place(a, live):
        if getattr(live, "committed", False):
            return jax.device_put(a, live.sharding)
        if replicated is not None:
            return jax.device_put(a, replicated)
        return jnp.asarray(a)

    placed = [place(a, live) for a, live in zip(o_leaves, live_leaves)]
    opt_state = jax.tree_util.tree_unflatten(structure, placed)
    return restored_params, opt_state


# --------------------------------------------------- semantic contract
# Registered in analysis/semantic/registry.py; the analyzer lowers the
# SAME train_step the trainer jits, at the three argument layouts that
# historically diverged (the PR-4 two-executables bug), and holds the
# lowered program to this declaration in tier-1.
from ...analysis.semantic import Case, hot_path_contract  # noqa: E402


@hot_path_contract(
    "lm.step",
    expected_executables=1,      # fresh == steady == restored
    # the analysis backend is CPU, where the trainer deliberately does
    # NOT donate (multi-device CPU aliasing SIGABRTs under collective
    # programs — see __init__); any donation appearing here is the
    # hazard, so the declared set is empty
    donate_expected=(),
    # the canonical (dp=4, tp=2) analysis-mesh lowering measured
    # all-reduce 29 ops/56804 B (TP matmul reductions + the dp gradient
    # psum), all-gather 3/24576 (embedding + output collection), and
    # all-to-all 6/12288 (head-parallel attention resharding); budgets
    # are those maxima with ~2x headroom — a NEW kind or a GSPMD
    # reshard inflating one fails --strict
    collective_budget={"all-reduce": {"ops": 40, "bytes": 120_000},
                       "all-gather": {"ops": 6, "bytes": 50_000},
                       "all-to-all": {"ops": 12, "bytes": 25_000}},
    # the host fetches ONE f32 loss scalar per step (trainer.step's
    # float(loss)); params/opt state stay on device
    host_fetch_outputs=(-1,),
    max_host_transfer_bytes=4,
)
def lm_step_contract():
    """fresh / steady / restored layouts of one LM step fingerprint."""
    import numpy as _np

    trainer = ShardedLMTrainer(vocab_size=64, mesh=None, d_model=32,
                               n_heads=2, n_layers=1, d_ff=64, max_len=16,
                               seed=0)
    tokens_np = _np.arange(8 * 16, dtype=_np.int32).reshape(8, 16) % 64
    tokens = trainer._to_device(tokens_np)
    kw = dict(donate_argnums=trainer._donate,
              out_shardings=trainer._out_shardings)
    fresh = (trainer.params, trainer.opt_state, tokens)
    trainer.step(tokens_np)        # params/opt_state become jit outputs
    steady = (trainer.params, trainer.opt_state, tokens)
    payload = lm_state_payload(trainer.params, trainer.opt_state,
                               trainer.meta)
    r_params, r_opt = lm_state_from_payload(payload, trainer.params,
                                            trainer.opt_state, trainer.meta)
    restored = (r_params, r_opt, tokens)
    return [Case("fresh", trainer._step_fn, fresh, kw),
            Case("steady", trainer._step_fn, steady, kw),
            Case("restored", trainer._step_fn, restored, kw)]
