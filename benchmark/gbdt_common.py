"""gbdt_common.py - what the GBDT drivers share: the seeded table, the
program's parameters from the configuration file, and the checks that
decide `correct` (parity with the plain reference, AUC floor, routes)."""
import concurrent.futures

import numpy as np

PARITY_ROWS = 65_536
PARITY_TREES = 5
# Training log-loss after 5 trees, system against reference, same bins,
# same parameters, 65,536 rows. The system's histogram kernels round each
# gradient and hessian to bfloat16 (8 significand bits) before they are
# summed in f32, so a bin's sum is off by up to 2^-8 of its sum of |values|
# and a near-tie between two splits can go the other way; either split of a
# near-tie lowers the loss by nearly the same amount. With the Pallas
# kernels interpreted on the CPU the two read 3e-5 and 9e-5 apart (16,384
# rows, PR 25), and 3e-9 with the exact XLA scatter; NOT yet read on the
# chip. The band is twenty times the wider reading, and 100 times under the
# 0.2 the loss falls in 5 trees, so a wrong gradient, a lost level or a
# broken route cannot hide in it.
PARITY_LOGLOSS_TOL = 2e-3
# AUC of the full 20-tree fit on the scored slice must reach what the
# reference's 5 trees reach on its sample, less this margin for the
# difference between the two row sets.
AUC_MARGIN = 0.005


def boost_kwargs(cfg):
    return dict(num_iterations=cfg["num_iterations"],
                max_bin=cfg["max_bin"], max_depth=cfg["max_depth"],
                num_leaves=cfg["num_leaves"],
                min_data_in_leaf=cfg["min_data_in_leaf"],
                learning_rate=cfg["learning_rate"])


def host_table(seed, n_rows, n_features, noise, threads=4):
    """(x float32 (n, F), y float32 (n,)) from the seed: normal features,
    a seeded linear rule plus `noise` x normal noise (bench.py's problem),
    made in row blocks by a few threads (numpy's generators release the
    interpreter lock)."""
    root = np.random.SeedSequence(seed)
    n_blocks = max(threads, -(-n_rows // 2_000_000))
    kids = root.spawn(n_blocks + 1)
    w = np.random.default_rng(kids[0]).standard_normal(
        n_features, dtype=np.float32)
    x = np.empty((n_rows, n_features), np.float32)
    y = np.empty(n_rows, np.float32)
    edges = np.linspace(0, n_rows, n_blocks + 1).astype(np.int64)

    def block(i):
        a, b = edges[i], edges[i + 1]
        rng = np.random.default_rng(kids[i + 1])
        rng.standard_normal(out=x[a:b], dtype=np.float32)
        e = rng.standard_normal(b - a, dtype=np.float32)
        y[a:b] = (x[a:b] @ w + noise * e > 0)

    with concurrent.futures.ThreadPoolExecutor(threads) as pool:
        list(pool.map(block, range(n_blocks)))
    return x, y


def auc(y, score):
    """Area under the ROC curve by ranks (ties get the mean rank)."""
    y = np.asarray(y) > 0.5
    score = np.asarray(score, np.float64)
    order = np.argsort(score, kind="mergesort")
    ranks = np.empty(len(score), np.float64)
    s = score[order]
    starts = np.r_[0, np.nonzero(np.diff(s))[0] + 1, len(s)]
    for a, b in zip(starts[:-1], starts[1:]):
        ranks[order[a:b]] = (a + b + 1) / 2.0
    n_pos, n_neg = y.sum(), (~y).sum()
    return float((ranks[y].sum() - n_pos * (n_pos + 1) / 2.0)
                 / (n_pos * n_neg))


def logloss(y, margin):
    y = np.asarray(y, np.float64)
    z = np.asarray(margin, np.float64)
    return float(np.mean(np.logaddexp(0.0, z) - y * z))


def routes_taken():
    """{route: count} of the program's `gbdt.hist.route.*` counters."""
    from mmlspark_tpu.reliability.metrics import reliability_metrics
    prefix = "gbdt.hist.route."
    return {k[len(prefix):]: v
            for k, v in reliability_metrics.snapshot().items()
            if k.startswith(prefix) and v}


def check_routes(routes, platform, problems):
    """On the chip only the compiled direct/joint kernels may have run."""
    if platform == "tpu" and (not routes
                              or set(routes) - {"direct", "joint"}):
        problems.append(f"histogram routes {routes}: expected only the "
                        f"compiled direct/joint kernels")


def note_binning_memory(bench, mapper):
    """What the program's device binning of the whole table needs, by the
    chip compiler's account: the largest program of a fit, and most of it
    temporaries that the runtime's peak counter leaves out."""
    import jax
    import jax.numpy as jnp
    from mmlspark_tpu.ops import binning
    table = jax.ShapeDtypeStruct(
        (bench.cfg["n_rows"], bench.cfg["n_features"]), jnp.float32)
    bench.note_program_memory(jax.jit(
        lambda x: binning.apply_bins_device(mapper, x)).lower(
            table).compile().memory_analysis())


def parity(bench, mapper, x_head, y_head, problems, notes):
    """System against the plain reference on a seeded sample, same mapper
    and parameters, 5 trees; returns the reference's AUC on the sample."""
    import jax
    from mmlspark_tpu.models.gbdt.boosting import BoostParams, fit_booster
    from mmlspark_tpu.ops import binning
    from harness import load_module

    note_binning_memory(bench, mapper)
    ref = load_module("reference", bench.cfg["reference"], bench.bench_dir)
    n = min(PARITY_ROWS, x_head.shape[0])
    xs, ys = np.ascontiguousarray(x_head[:n]), np.asarray(y_head[:n])
    kw = dict(boost_kwargs(bench.cfg), num_iterations=PARITY_TREES)
    with jax.default_device(jax.devices()[0]):
        d_bins = binning.apply_bins_device(mapper, xs)
        booster, base, _ = fit_booster(
            xs, ys, BoostParams(objective="binary", **kw),
            prebinned=(mapper, d_bins, jax.device_put(ys)))
        sys_margin = booster.raw_score(xs)[:, 0] + base
    ref_margin = ref.fit_margins(np.asarray(d_bins), ys, bench.cfg,
                                 PARITY_TREES)
    ll_sys, ll_ref = logloss(ys, sys_margin), logloss(ys, ref_margin)
    notes.update(parity_logloss_system=ll_sys, parity_logloss_reference=ll_ref,
                 parity_rows=n)
    if not abs(ll_sys - ll_ref) <= PARITY_LOGLOSS_TOL:
        problems.append(f"log-loss after {PARITY_TREES} trees: system "
                        f"{ll_sys:.6f}, reference {ll_ref:.6f}, apart by "
                        f"more than {PARITY_LOGLOSS_TOL}")
    return auc(ys, ref_margin)


def check_auc(y, score, floor, problems, notes):
    got = auc(y, score)
    notes.update(auc=got, auc_floor=floor - AUC_MARGIN)
    if not got >= floor - AUC_MARGIN:
        problems.append(f"AUC {got:.5f} on the scored slice is under the "
                        f"reference's floor {floor - AUC_MARGIN:.5f}")
