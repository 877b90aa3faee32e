"""Distributed GBDT training over a jax.sharding.Mesh.

TPU-native replacement of the reference's distributed machinery (SURVEY.md
§2.10): no driver ServerSocket rendezvous (LightGBMUtils.scala:119-188), no
`LGBM_NetworkInit` socket ring (TrainUtils.scala:609-625), no port arithmetic.
The gang already exists as the mesh; rows are sharded over the "data" axis;
the per-level histogram all-reduce is a `lax.psum` inside `shard_map`, riding
ICI. Both tree learners the reference exposes are here:

- data_parallel: full histogram psum per level;
- voting_parallel (PV-tree): local top-k feature votes, global top-2k
  aggregation (trainer._voting_feature_mask).

Ragged row counts are handled by zero-weight padding (`pad_to_multiple`) —
the moral equivalent of the reference's empty-partition 'ignore' members
(TrainUtils.scala:577-580). Barrier semantics are inherent: a mesh collective
is all-or-nothing, which is what `useBarrierExecutionMode` approximates on
Spark (LightGBMParams.scala:58).
"""
from __future__ import annotations

import functools
import hashlib
from typing import Optional

import jax
import numpy as np
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from ...parallel import DATA_AXIS, data_mesh, pad_to_multiple
from . import trainer
from .boosting import fit_booster


def _stable_tag(*parts) -> str:
    """Process- and run-stable fingerprint suffix for a compile-log key
    (builtin hash() is PYTHONHASHSEED-salted — two hosts of one fleet
    would record the same executable under different rows, and the
    autotuner's per_key training rows could never be joined across
    runs)."""
    return hashlib.sha1(repr(parts).encode()).hexdigest()[:10]


def _mesh_tag(mesh) -> tuple:
    return tuple(sorted((str(k), int(v)) for k, v in mesh.shape.items()))


@functools.lru_cache(maxsize=128)
def _compiled_tree_fn(mesh, cfg, voting: Optional[int]):
    """Build the shard_map'd tree grower once per (mesh, config),
    AOT-compiled through the telemetry compile log (telemetry.perf
    AotCache): the executable actually used for every distributed tree
    carries its cost analysis AND collective ops/bytes (the psum
    histogram all-reduce) as a compile record — the COMM_TRAFFIC account
    riding every fit, not just the bench harness. Rebuilding per call
    would re-trace and recompile every tree."""
    from ...telemetry.perf import AotCache

    def fn(bins, grad, hess, fmask, count_w):
        return trainer.train_one_tree(bins, grad, hess, fmask, cfg=cfg,
                                      axis_name=DATA_AXIS, voting_top_k=voting,
                                      count_w=count_w)
    mapped = shard_map(
        fn, mesh=mesh,
        in_specs=(P(DATA_AXIS, None), P(DATA_AXIS), P(DATA_AXIS), P(),
                  P(DATA_AXIS)),
        out_specs=(trainer.Tree(P(), P(), P(), P(), P(), P(), P()),
                   P(DATA_AXIS)),
        check_vma=False)
    mode = "voting_parallel" if voting is not None else "data_parallel"
    # fingerprint carries the builder key: a DIFFERENT cfg compiling at
    # the same shapes is a new executable, not a recompile of this one
    return AotCache(mapped, label=f"gbdt.tree.{mode}",
                    fingerprint=f"gbdt.tree.{mode}#"
                                f"{_stable_tag(_mesh_tag(mesh), cfg, voting)}")


def make_sharded_tree_fn(mesh, parallelism: str = "data_parallel",
                         top_k: int = 20):
    """shard_map-wrapped train_one_tree: rows in, replicated tree out."""
    voting = top_k if parallelism == "voting_parallel" else None

    def tree_fn(bins, grad, hess, fmask, cfg, count_w=None):
        import jax.numpy as jnp
        if count_w is None:
            count_w = jnp.ones(bins.shape[0], jnp.float32)
        return _compiled_tree_fn(mesh, cfg, voting)(bins, grad, hess, fmask,
                                                    count_w)

    return tree_fn


@functools.lru_cache(maxsize=128)
def _compiled_chunk_fn(mesh, p, cfg, chunk_len: int, k_out: int,
                       has_valid: bool, multiclass: bool, voting):
    """shard_map-wrapped fused boosting chunk (see boosting._boost_chunk):
    rows sharded over the data axis, trees/metrics replicated out."""
    from .boosting import _boost_chunk
    fn = functools.partial(_boost_chunk, p=p, cfg=cfg, chunk_len=chunk_len,
                           k_out=k_out, axis_name=DATA_AXIS,
                           has_valid=has_valid, voting_top_k=voting)
    margin_spec = P(DATA_AXIS, None) if multiclass else P(DATA_AXIS)
    mapped = shard_map(
        fn, mesh=mesh,
        in_specs=(P(DATA_AXIS, None), P(DATA_AXIS), P(DATA_AXIS),
                  P(DATA_AXIS), margin_spec, margin_spec, P(), P(), P(), P(),
                  P()),
        out_specs=(margin_spec, P(), P(), P(), P(), P(), P(), P(), P(), P()),
        check_vma=False)
    # same AOT-through-the-compile-log treatment as the tree grower (see
    # _compiled_tree_fn): the fused chunk's collectives become records
    from ...telemetry.perf import AotCache
    mode = "voting_parallel" if voting is not None else "data_parallel"
    tag = _stable_tag(_mesh_tag(mesh), p, cfg, chunk_len, k_out,
                      has_valid, multiclass, voting)
    return AotCache(mapped, label=f"gbdt.chunk.{mode}",
                    fingerprint=f"gbdt.chunk.{mode}#{tag}")


def fit_booster_distributed(x, y, params, weights=None, init_scores=None,
                            group=None, valid=None, init_booster=None,
                            callbacks=None, parallelism: str = "data_parallel",
                            top_k: int = 20, num_tasks: int = 0,
                            checkpoint_fn=None, checkpoint_interval: int = 25,
                            init_base: float = 0.0, ingest=None, oocore=None,
                            init_margin=None, init_rng_key=None,
                            iter_offset: int = 0, mesh=None):
    """Same training loop as fit_booster, with rows sharded over the mesh.

    Split decisions are computed identically on every shard from the psum'd
    histograms, so trees come back replicated — the reference ships the
    booster from worker 0 through a kryo reduce (LightGBMBase.scala:256-264);
    here there is nothing to ship.

    `mesh` overrides the default device mesh — the elastic shrink-resume
    path (reliability/elastic.py) passes `ElasticPlan.mesh()` here so the
    survivors' fit compiles for THEIR device set; a new mesh is a new
    `AotCache` fingerprint, so those recompiles are recorded honestly.
    """
    if mesh is None:
        mesh = data_mesh(num_tasks if num_tasks > 1 else None)
    nsh = mesh.shape[DATA_AXIS]
    if isinstance(x, str):
        # out-of-core source: memory-map here; the f32 asarray below is a
        # view (no copy) when rows already divide the mesh, so the raw
        # matrix never materializes — ChunkStager streams its binning
        x = np.load(x, mmap_mode="r")
    n = x.shape[0]

    x_p, _ = pad_to_multiple(np.asarray(x, np.float32), nsh)
    y_p, _ = pad_to_multiple(np.asarray(y, np.float32), nsh)
    w = np.ones(n, np.float32) if weights is None else np.asarray(weights, np.float32)
    w_p, _ = pad_to_multiple(w, nsh)  # padding rows get weight 0
    # physical-presence channel: padding rows must not count toward
    # min_data_in_leaf, while user zero weights still do (LightGBM counts)
    pres_p, _ = pad_to_multiple(np.ones(n, np.float32), nsh)
    init_p = None
    if init_scores is not None:
        init_p, _ = pad_to_multiple(np.asarray(init_scores, np.float32), nsh)
    group_p = None
    if group is not None:
        # padding rows get a fresh group id so they pair with nothing
        group_p, _ = pad_to_multiple(np.asarray(group, np.int32), nsh,
                                     fill=int(group.max()) + 1)

    row_sharding = NamedSharding(mesh, P(DATA_AXIS))

    def put_rows(arr):
        arr = np.asarray(arr)
        spec = P(DATA_AXIS, *([None] * (arr.ndim - 1)))
        return jax.device_put(arr, NamedSharding(mesh, spec))

    tree_fn = make_sharded_tree_fn(mesh, parallelism, top_k)
    voting = top_k if parallelism == "voting_parallel" else None
    multiclass = params.objective == "multiclass"

    def chunk_fn(d_bins, y_j, w_j, pres_j, margin, margin_init, v_bins, vy,
                 v_margin, key, it_base, p, cfg, chunk_len, k_out,
                 has_valid=False):
        compiled = _compiled_chunk_fn(mesh, p, cfg, chunk_len, k_out,
                                      has_valid, multiclass, voting)
        import jax.numpy as jnp
        if pres_j is None:  # shard_map specs are fixed; materialize ones
            pres_j = jnp.ones(y_j.shape[0], jnp.float32)
        return compiled(d_bins, y_j, w_j, pres_j, margin, margin_init, v_bins,
                        vy, v_margin, key, jnp.int32(it_base))

    booster, base, hist = fit_booster(
        x_p, y_p, params, weights=w_p, init_scores=init_p, group=group_p,
        valid=valid, init_booster=init_booster, callbacks=callbacks,
        tree_fn=tree_fn, put_fn=put_rows, chunk_fn=chunk_fn,
        presence=pres_p, checkpoint_fn=checkpoint_fn,
        checkpoint_interval=checkpoint_interval, init_base=init_base,
        ingest=ingest, oocore=oocore, init_margin=init_margin,
        init_rng_key=init_rng_key, iter_offset=iter_offset)
    return booster, base, hist


# -------------------------------------------------- semantic contracts
# Registered in analysis/semantic/registry.py: the shard_map'd tree
# grower and fused chunk lowered on the canonical 8-device analysis
# mesh — the per-level histogram psum must appear as all-reduce traffic
# inside the declared budget, and NOTHING else (a GSPMD all-gather here
# would ride ICI on every tree of every fit).
from ...analysis.semantic import Case, hot_path_contract  # noqa: E402


def _contract_mesh():
    return data_mesh()


def _contract_rows(n: int, f: int):
    import jax.numpy as jnp
    rng = np.random.default_rng(0)
    return (jnp.asarray(rng.integers(0, 16, (n, f)), jnp.uint8),
            jnp.asarray(rng.normal(size=n), jnp.float32),
            jnp.asarray(rng.uniform(0.1, 1.0, n), jnp.float32))


@hot_path_contract(
    "gbdt.tree.distributed",
    expected_executables=1,
    donate_expected=(),
    # the measured 64x4x16 lowering on the 8-device mesh psums 7
    # all-reduce ops / 1620 B (per-level histogram triples + split
    # bookkeeping); budgets are those maxima with ~2x headroom
    collective_budget={"all-reduce": {"ops": 14, "bytes": 4_000}},
)
def gbdt_tree_distributed_contract():
    """Two identical-layout lowerings of the distributed tree grower."""
    import jax.numpy as jnp
    mesh = _contract_mesh()
    cfg = trainer.TreeConfig(n_features=4, n_bins=16, max_depth=2,
                             num_leaves=7, min_data_in_leaf=1)
    fn = _compiled_tree_fn(mesh, cfg, None).fn
    bins, grad, hess = _contract_rows(64, 4)
    args = (bins, grad, hess, jnp.ones(4, bool), jnp.ones(64, jnp.float32))
    return [Case("first-tree", fn, args), Case("next-tree", fn, args)]


@hot_path_contract(
    "gbdt.vote.distributed",
    expected_executables=1,
    donate_expected=(),
    # voting-parallel tree grower at the headline F=64 width: the int32
    # vote all-reduce + the ELECTED top-2k histogram psum measure 15
    # all-reduce ops / 3,192 B on the 8-device mesh — vs 24,660 B for
    # the full data_parallel psum at the same width (7.7x fewer bytes;
    # docs/gbdt.md "Out-of-core training" has the math). Budgets are the
    # voting maxima with ~2x headroom: a regression that sneaks the full
    # histogram back onto the wire blows the bytes budget immediately.
    collective_budget={"all-reduce": {"ops": 30, "bytes": 6_400}},
)
def gbdt_vote_distributed_contract():
    """The vote kernel (voting_parallel tree grower) pinned to ONE
    executable at F=64 — the shape where voting pays."""
    import jax.numpy as jnp
    mesh = _contract_mesh()
    cfg = trainer.TreeConfig(n_features=64, n_bins=16, max_depth=2,
                             num_leaves=7, min_data_in_leaf=1)
    fn = _compiled_tree_fn(mesh, cfg, 2).fn   # top_k=2 -> 4 elected of 64
    bins, grad, hess = _contract_rows(64, 64)
    args = (bins, grad, hess, jnp.ones(64, bool), jnp.ones(64, jnp.float32))
    return [Case("first-vote-tree", fn, args), Case("next-vote-tree", fn, args)]


@hot_path_contract(
    "gbdt.chunk.distributed",
    expected_executables=1,
    donate_expected=(),
    # the measured chunk_len=2 lowering psums 7 all-reduce ops /
    # 1620 B (the scan body compiles ONCE, so per-level psums do not
    # multiply by iteration count); maxima with ~2x headroom
    collective_budget={"all-reduce": {"ops": 14, "bytes": 4_000}},
)
def gbdt_chunk_distributed_contract():
    """The distributed fused chunk on the canonical analysis mesh."""
    import jax.numpy as jnp
    from .boosting import BoostParams
    mesh = _contract_mesh()
    p = BoostParams(objective="binary", num_iterations=2, num_leaves=7,
                    max_depth=2, max_bin=15, min_data_in_leaf=1)
    cfg = trainer.TreeConfig(n_features=4, n_bins=16, max_depth=2,
                             num_leaves=7, learning_rate=p.learning_rate,
                             min_data_in_leaf=1)
    fn = _compiled_chunk_fn(mesh, p, cfg, 2, 1, False, False, None).fn
    bins, _, _ = _contract_rows(64, 4)
    rng = np.random.default_rng(1)
    y_j = jnp.asarray(rng.integers(0, 2, 64), jnp.float32)
    margin = jnp.zeros(64, jnp.float32)
    args = (bins, y_j, None, jnp.ones(64, jnp.float32), margin, margin,
            jnp.zeros((1, 4), jnp.uint8), jnp.zeros(1, jnp.float32),
            jnp.zeros(1, jnp.float32), jax.random.PRNGKey(0),
            jnp.asarray(0, jnp.int32))
    return [Case("first-chunk", fn, args), Case("next-chunk", fn, args)]
