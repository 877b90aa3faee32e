"""Plain reference of the `gbdt-dense-63bin` configurations: a level-wise
binary GBDT over a binned table, in numpy, with exact float32 histograms.

Independent of the package (imports numpy only). Semantics, as LightGBM's
with a level-wise grower and as the configuration file states them:

- objective: binary log-loss; gradient p - y, hessian p (1 - p), margin
  starts at log(mean(y) / (1 - mean(y)));
- one tree per iteration, grown level by level to `max_depth`; at each level
  every node's histogram of (gradient, hessian, count) per feature and bin
  is summed exactly (float64 accumulation, stored as float32);
- a split sends `bin <= b` left; its gain is
  0.5 (GL^2/HL + GR^2/HR - G^2/H); it is valid when both sides hold at
  least `min_data_in_leaf` rows and `min_sum_hessian_in_leaf` hessian and
  the gain is positive; the best (feature, bin) is the first maximum in
  feature-major order;
- at most `num_leaves` leaves: at each level the valid splits are applied in
  order of gain while the leaf budget lasts;
- leaf value: -learning_rate * G / H of the rows resting there.
"""
import numpy as np

MIN_SUM_HESSIAN = 1e-3


def _sigmoid(z):
    return 1.0 / (1.0 + np.exp(-z))


def grow_tree(bins, grad, hess, n_bins, max_depth, num_leaves,
              min_data_in_leaf, learning_rate):
    """One tree on binned rows; returns the per-row margin delta."""
    n, n_features = bins.shape
    node = np.zeros(n, np.int64)        # heap index of each row's node
    leaves = 1
    frozen = np.zeros(n, bool)          # rows whose node did not split
    for depth in range(max_depth):
        base = 2 ** depth - 1
        m = 2 ** depth
        local = node - base
        live = ~frozen
        g_hist = np.zeros((m, n_features, n_bins), np.float32)
        h_hist = np.zeros_like(g_hist)
        c_hist = np.zeros_like(g_hist)
        for j in range(n_features):
            idx = local[live] * n_bins + bins[live, j]
            size = m * n_bins
            g_hist[:, j] = np.bincount(idx, grad[live], size).reshape(
                m, n_bins)
            h_hist[:, j] = np.bincount(idx, hess[live], size).reshape(
                m, n_bins)
            c_hist[:, j] = np.bincount(idx, None, size).reshape(m, n_bins)
        gl, hl, cl = (np.cumsum(a, axis=-1, dtype=np.float32)
                      for a in (g_hist, h_hist, c_hist))
        gt = g_hist[:, 0].sum(-1)[:, None, None]
        ht = h_hist[:, 0].sum(-1)[:, None, None]
        ct = c_hist[:, 0].sum(-1)[:, None, None]
        gr, hr, cr = gt - gl, ht - hl, ct - cl
        with np.errstate(divide="ignore", invalid="ignore"):
            gain = 0.5 * (gl * gl / hl + gr * gr / hr - gt * gt / ht)
        ok = ((cl >= min_data_in_leaf) & (cr >= min_data_in_leaf)
              & (hl >= MIN_SUM_HESSIAN) & (hr >= MIN_SUM_HESSIAN) & (cr > 0))
        gain = np.where(ok, gain, -np.inf).reshape(m, -1)
        best = gain.argmax(-1)
        best_gain = gain[np.arange(m), best]
        valid = np.isfinite(best_gain) & (best_gain > 0.0)
        order = np.argsort(-np.where(valid, best_gain, -np.inf),
                           kind="stable")
        rank = np.empty(m, np.int64)
        rank[order] = np.arange(m)
        apply = valid & (rank < num_leaves - leaves)
        leaves += int(apply.sum())
        feat, thr = best // n_bins, best % n_bins
        rows = np.nonzero(live)[0]
        loc = local[rows]
        splits = apply[loc]
        go_left = bins[rows, feat[loc]] <= thr[loc]
        child = np.where(go_left, 2 * node[rows] + 1, 2 * node[rows] + 2)
        node[rows] = np.where(splits, child, node[rows])
        frozen[rows] = ~splits
    n_nodes = 2 ** (max_depth + 1) - 1
    g_leaf = np.bincount(node, grad, n_nodes)
    h_leaf = np.bincount(node, hess, n_nodes)
    with np.errstate(divide="ignore", invalid="ignore"):
        value = np.where(h_leaf > 0, -learning_rate * g_leaf / h_leaf, 0.0)
    return value[node].astype(np.float32)


def fit_margins(bins, y, cfg, num_iterations):
    """Training margins after `num_iterations` trees on (bins, y)."""
    y = np.asarray(y, np.float32)
    mean = float(np.clip(y.astype(np.float64).mean(), 1e-12, 1 - 1e-12))
    margin = np.full(y.shape[0], np.log(mean / (1 - mean)), np.float32)
    bins = np.asarray(bins).astype(np.int64)
    for _ in range(num_iterations):
        p = _sigmoid(margin.astype(np.float32))
        margin = margin + grow_tree(
            bins, (p - y).astype(np.float32),
            (p * (1 - p)).astype(np.float32), cfg["n_bins"],
            cfg["max_depth"], cfg["num_leaves"], cfg["min_data_in_leaf"],
            cfg["learning_rate"])
    return margin
