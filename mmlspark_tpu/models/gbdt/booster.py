"""Booster: the serializable trained GBDT ensemble.

Role-equivalent to the reference's LightGBMBooster
(lightgbm/booster/LightGBMBooster.scala): holds the trees, scores rows,
exposes leaf indices, SHAP-style contributions, feature importances, string
round-trip, and merge for batch-continuation training
(mergeBooster, LightGBMBooster.scala:237).

Representation: dense stacked arrays (n_trees, max_nodes) — no pointers, no
node objects — so predict is a single jitted scan (trainer.predict_raw) and
persistence is plain npz.
"""
from __future__ import annotations

import json
from typing import NamedTuple, Optional

import numpy as np

from . import trainer

# beyond this depth the vmapped device path's (2^d, d, chunk) working set
# and unrolled masked loops stop paying off; the host DFS takes over
_DEVICE_SHAP_MAX_DEPTH = 8

# raw_score batches under this row count score on the HOST (vectorized
# numpy descent): a serving microbatch must not pay a device dispatch
# round trip per batch — the reference's serving scenario is exactly
# executor-LOCAL model scoring (HTTPSourceV2 pipelines run on the
# executor, docs/mmlspark-serving.md:142-146). Host scoring of a
# 256-row batch through 20 trees is ~100 us. Large batches still take
# the jitted device scan (bulk inference throughput),
# and so do big ENSEMBLES on mid-size batches: the host loop is
# O(rows x trees x depth) python-dispatched numpy, so the auto route
# also caps total element-ops (a 2000-tree model on 4000 rows would be
# seconds on host vs milliseconds on device).
_HOST_PREDICT_MAX_ROWS = 4096
_HOST_PREDICT_MAX_WORK = 20_000_000   # rows * trees * depth element-ops


class Booster(NamedTuple):
    split_feature: np.ndarray   # (T, max_nodes) i32, -1 = leaf
    threshold: np.ndarray       # (T, max_nodes) f32 real-valued bounds
    split_bin: np.ndarray       # (T, max_nodes) i32 (train-time thresholds)
    leaf_value: np.ndarray      # (T, max_nodes) f32
    tree_class: np.ndarray      # (T,) i32 class id (0 for single-output)
    max_depth: int
    n_classes: int              # output width (1 for binary/regression margin)
    objective: str
    n_features: int
    best_iteration: int = -1    # early stopping; -1 = use all trees
    gain: Optional[np.ndarray] = None    # (T, max_nodes) f32 split gains
    cover: Optional[np.ndarray] = None   # (T, max_nodes) f32 node row counts
    # native categorical splits: nodes flagged here route by membership of
    # the (integer) raw value in the packed 16-bit category words instead of
    # a threshold compare (reference: categoricalSlotIndexes semantics,
    # lightgbm/params/LightGBMParams.scala:184-196)
    split_is_cat: Optional[np.ndarray] = None  # (T, max_nodes) bool
    cat_words: Optional[np.ndarray] = None     # (T, max_nodes, W16) i32

    @property
    def n_trees(self) -> int:
        return self.split_feature.shape[0]

    def _cat_args(self, s):
        """(split_is_cat, cat_words) slices for the predict kernels, or
        (None, None) for purely numeric ensembles."""
        if self.split_is_cat is None or self.cat_words is None:
            return None, None
        return self.split_is_cat[s], self.cat_words[s]

    def _used_trees(self):
        if self.best_iteration >= 0:
            per_iter = max(self.n_classes, 1)
            k = (self.best_iteration + 1) * per_iter
            return slice(0, k)
        return slice(None)

    # -- scoring -----------------------------------------------------------
    def raw_score(self, x, init_score: float = 0.0, backend: str = "auto"):
        """(n, F) f32 -> (n, n_classes) raw margins.

        backend: "auto" scores small batches (< _HOST_PREDICT_MAX_ROWS)
        on the host — the serving hot path must stay dispatch-free — and
        bulk batches on the device; "host"/"device" force a path. Both
        run the identical descent (go right unless x <= threshold, NaN
        right, categorical membership on identity bins) and agree
        bitwise (tests/test_gbdt.py::test_host_device_raw_score_parity).
        """
        if backend not in ("auto", "host", "device"):
            raise ValueError(
                f"backend must be auto|host|device, got {backend!r}")
        x = np.asarray(x, dtype=np.float32)
        s = self._used_trees()
        ic, cw = self._cat_args(s)
        n_used = len(range(*s.indices(self.split_feature.shape[0])))
        work = x.shape[0] * n_used * max(self.max_depth, 1)
        if backend == "host" or (backend == "auto"
                                 and x.shape[0] < _HOST_PREDICT_MAX_ROWS
                                 and work <= _HOST_PREDICT_MAX_WORK):
            out = _predict_raw_host(
                x, self.split_feature[s], self.threshold[s],
                self.leaf_value[s], self.tree_class[s], self.max_depth,
                self.n_classes, split_is_cat=ic, cat_words=cw)
        else:
            out = np.asarray(trainer.predict_raw(
                x, self.split_feature[s], self.threshold[s],
                self.leaf_value[s], self.tree_class[s], self.max_depth,
                self.n_classes, split_is_cat=ic, cat_words=cw))
        return out + init_score

    def scoring_plan(self, init_score: float = 0.0):
        """Prebuilt vectorized host scoring closure for the serving hot
        path: the used-tree slice, categorical args and init score resolve
        ONCE at build time, and the descent is TREE-PARALLEL — all trees
        step down one level per numpy op over an (n, T) node matrix, so a
        request batch costs `max_depth` (~5) vectorized ops instead of the
        `trees x depth` (~100) Python-dispatched ops of the per-tree loop.
        At serving batch sizes the per-tree loop is pure numpy dispatch
        overhead (~2 ms/batch for 20 trees measured on the CI host); this
        plan is the sub-microsecond-per-row shape of the workload
        ("Booster" accelerator paper, PAPERS.md). No device dispatch
        (reference: serving scores executor-local, HTTPSourceV2 pipelines
        on the executor; see io/plan.py for the cache that holds these).

        Margins match `raw_score` to float32 summation tolerance (tree
        contributions sum pairwise here, sequentially there); threshold/
        argmax outputs are identical for any non-degenerate margin."""
        s = self._used_trees()
        sf = np.ascontiguousarray(self.split_feature[s], np.int64)
        thr = np.ascontiguousarray(self.threshold[s], np.float32)
        lv = np.ascontiguousarray(self.leaf_value[s], np.float32)
        tc = np.ascontiguousarray(self.tree_class[s], np.int64)
        ic, cw = self._cat_args(s)
        depth, k = self.max_depth, self.n_classes
        n_trees, m = sf.shape
        offs = np.arange(n_trees, dtype=np.int64) * m     # flat tree bases
        sf_f, thr_f, lv_f = sf.ravel(), thr.ravel(), lv.ravel()
        has_cat = ic is not None and cw is not None and cw.shape[-1] > 0
        if has_cat:
            ic_f = np.ascontiguousarray(ic, bool).ravel()
            cw_f = np.ascontiguousarray(cw, np.int32).reshape(-1, cw.shape[-1])
            w16 = cw.shape[-1]
        # single-output ensembles (binary/regression/ranking) sum straight
        # across trees; multiclass scatters through a per-class one-hot
        class_mask = None
        if k > 1:
            class_mask = (tc[None, :] == np.arange(k)[:, None]).astype(
                np.float32)                                # (k, T)

        n_features = self.n_features

        def plan(x: np.ndarray) -> np.ndarray:
            x = np.asarray(x, dtype=np.float32)
            # the descent CLIPS feature indices, so a wrong-width row would
            # silently score against the wrong features — reject it here
            # (serving maps this to a per-row 400)
            if x.ndim != 2 or x.shape[1] != n_features:
                raise ValueError(
                    f"expected (n, {n_features}) features, got {x.shape}")
            n, n_feat = x.shape
            rows = np.arange(n)[:, None]
            node = np.zeros((n, n_trees), np.int64)
            for _ in range(depth):
                idx = node + offs
                f = sf_f[idx]                              # (n, T)
                is_leaf = f < 0
                xf = x[rows, np.clip(f, 0, n_feat - 1)]
                with np.errstate(invalid="ignore"):
                    go_left = xf <= thr_f[idx]
                if has_cat:
                    b = _raw_to_cat_bin_np(xf, w16)
                    words = np.take_along_axis(
                        cw_f[idx], (b >> 4)[..., None], axis=-1)[..., 0]
                    member = ((words >> (b & 15)) & 1) == 1
                    go_left = np.where(ic_f[idx], member, go_left)
                child = np.where(go_left, 2 * node + 1, 2 * node + 2)
                node = np.where(is_leaf, node, child)
            leaf = lv_f[node + offs]                       # (n, T)
            if class_mask is None:
                return leaf.sum(axis=1, keepdims=True) + init_score
            return leaf @ class_mask.T + init_score
        return plan

    def predict_leaf(self, x):
        s = self._used_trees()
        ic, cw = self._cat_args(s)
        return np.asarray(trainer.predict_leaf_index(
            np.asarray(x, dtype=np.float32),
            self.split_feature[s], self.threshold[s], self.max_depth,
            split_is_cat=ic, cat_words=cw))

    def feature_contributions(self, x, backend: str = "auto"):
        """Per-feature additive contributions via exact path-dependent
        TreeSHAP (Lundberg et al. 2018, Algorithm 2) — the same attribution
        LightGBM's predict(pred_contrib=True) / the reference's featuresShap
        column computes (lightgbm/booster/LightGBMBooster.scala featuresShap).

        Returns (n, n_features + 1); the last column is the expected value
        (bias). For multiclass boosters, contributions of all classes' trees
        are summed per feature (use tree_class to split if needed).
        Requires node covers (recorded during training); boosters loaded from
        pre-cover artifacts fall back to the Saabas approximation.

        backend: "auto" uses the jitted device implementation
        (shap_device.py — vmapped leaf paths, no host recursion) whenever
        the tree depth allows it, falling back to the host DFS; "host"
        forces the numpy oracle; "device" requires the device path.
        """
        if backend not in ("auto", "device", "host"):
            raise ValueError(
                f"backend must be auto|device|host, got {backend!r}")
        x = np.asarray(x, dtype=np.float32)
        n = x.shape[0]
        contrib = np.zeros((n, self.n_features + 1), dtype=np.float64)
        s = self._used_trees()
        sf, thr, lv = self.split_feature[s], self.threshold[s], self.leaf_value[s]
        ic, cw = self._cat_args(s)
        if self.cover is None:
            if backend == "device":
                # an explicit exact-path request must not silently degrade
                # to the Saabas approximation
                raise ValueError(
                    "device TreeSHAP needs node covers; this booster "
                    "predates cover recording (Saabas fallback only)")
            return self._saabas_contributions(x, sf, thr, lv, ic, cw)
        cover = self.cover[s]
        device_ok = self.max_depth <= _DEVICE_SHAP_MAX_DEPTH
        if backend == "device" and not device_ok:
            raise ValueError(
                f"device TreeSHAP supports max_depth <= "
                f"{_DEVICE_SHAP_MAX_DEPTH}; this booster has "
                f"{self.max_depth}")
        if backend in ("auto", "device") and device_ok and sf.shape[0]:
            from .shap_device import shap_contributions_device
            return shap_contributions_device(
                x, sf, thr, lv, cover, self.n_features, self.max_depth,
                split_is_cat=ic, cat_words=cw)
        for t in range(sf.shape[0]):
            phi = _tree_shap(sf[t], thr[t], lv[t], cover[t], x,
                             self.n_features,
                             is_cat=None if ic is None else ic[t],
                             cat_words=None if cw is None else cw[t])
            contrib += phi
        return contrib

    def _saabas_contributions(self, x, sf, thr, lv, ic=None, cw=None):
        """Legacy fallback: uniform-weight path attribution."""
        n = x.shape[0]
        contrib = np.zeros((n, self.n_features + 1), dtype=np.float64)
        for t in range(sf.shape[0]):
            node = np.zeros(n, dtype=np.int64)
            ev = _node_expectations(sf[t], lv[t])
            contrib[:, -1] += ev[0]
            for _ in range(self.max_depth):
                f = sf[t][node]
                leaf = f < 0
                xf = x[np.arange(n), np.clip(f, 0, self.n_features - 1)]
                go_left = xf <= thr[t][node]
                if ic is not None:
                    member = _cat_member_np(xf, cw[t][node])
                    go_left = np.where(ic[t][node], member, go_left)
                child = np.where(go_left, 2 * node + 1, 2 * node + 2)
                nxt = np.where(leaf, node, child)
                delta = ev[nxt] - ev[node]
                np.add.at(contrib,
                          (np.arange(n), np.clip(f, 0, self.n_features - 1)),
                          np.where(~leaf, delta, 0.0))
                node = nxt
        return contrib

    # -- introspection ------------------------------------------------------
    def feature_importances(self, importance_type: str = "split"):
        """'split' = split counts; 'gain' = summed split gains — exact
        LightGBM semantics (featureImportances, LightGBMBooster.scala)."""
        s = self._used_trees()
        sf = self.split_feature[s]
        if importance_type != "split" and self.gain is None:
            import warnings
            warnings.warn(
                "booster has no recorded split gains (pre-upgrade artifact "
                "or mixed merge); falling back to split counts",
                stacklevel=2)
        split_ids = sf[sf >= 0].ravel()
        if importance_type == "split" or self.gain is None:
            weights = None
        else:
            weights = self.gain[s][sf >= 0].ravel().astype(np.float64)
        return np.bincount(split_ids, weights=weights,
                           minlength=self.n_features).astype(np.float64)

    # -- persistence ---------------------------------------------------------
    def to_dict(self) -> dict:
        out = {
            "meta": json.dumps({
                "max_depth": self.max_depth, "n_classes": self.n_classes,
                "objective": self.objective, "n_features": self.n_features,
                "best_iteration": self.best_iteration}),
            "split_feature": self.split_feature,
            "threshold": self.threshold,
            "split_bin": self.split_bin,
            "leaf_value": self.leaf_value,
            "tree_class": self.tree_class,
        }
        if self.gain is not None:
            out["gain"] = self.gain
        if self.cover is not None:
            out["cover"] = self.cover
        if self.split_is_cat is not None:
            out["split_is_cat"] = self.split_is_cat
            out["cat_words"] = self.cat_words
        return out

    @classmethod
    def from_dict(cls, d: dict) -> "Booster":
        meta = json.loads(str(d["meta"]))
        return cls(split_feature=np.asarray(d["split_feature"]),
                   threshold=np.asarray(d["threshold"]),
                   split_bin=np.asarray(d["split_bin"]),
                   leaf_value=np.asarray(d["leaf_value"]),
                   tree_class=np.asarray(d["tree_class"]),
                   gain=(np.asarray(d["gain"]) if "gain" in d else None),
                   cover=(np.asarray(d["cover"]) if "cover" in d else None),
                   split_is_cat=(np.asarray(d["split_is_cat"], bool)
                                 if "split_is_cat" in d else None),
                   cat_words=(np.asarray(d["cat_words"], np.int32)
                              if "cat_words" in d else None),
                   **meta)

    def save_model_string(self) -> str:
        """Text round-trip (reference: saveToString, LightGBMBooster.scala:254)."""
        d = self.to_dict()
        return json.dumps({k: (v if isinstance(v, str) else np.asarray(v).tolist())
                           for k, v in d.items()})

    @classmethod
    def load_model_string(cls, s: str) -> "Booster":
        return cls.from_dict(json.loads(s))

    def merge(self, other: "Booster") -> "Booster":
        """Concatenate ensembles — batch-continuation training
        (reference: mergeBooster, LightGBMBooster.scala:237)."""
        assert self.n_classes == other.n_classes and self.n_features == other.n_features
        md = max(self.max_depth, other.max_depth)
        a, b = _pad_depth(self, md), _pad_depth(other, md)
        # preserve early-stopping truncation: if the continuation booster was
        # early-stopped, offset its best_iteration by our (fully used) iters
        per_iter = max(self.n_classes, 1)
        if other.best_iteration >= 0:
            best = self.n_trees // per_iter + other.best_iteration
        else:
            best = -1
        both_aux = self.gain is not None and other.gain is not None \
            and self.cover is not None and other.cover is not None
        any_cat = self.split_is_cat is not None or other.split_is_cat is not None
        if any_cat:
            ic = np.concatenate([a[6], b[6]])
            w16 = max(a[7].shape[2], b[7].shape[2])
            # widening a booster's membership words would MOVE its
            # overflow/NaN bin (raw_to_cat_bin's top = w16*16-1), silently
            # changing how unseen categories route through its trees; a side
            # can be padded harmlessly only if it has NO categorical nodes
            def _unsafe(side):
                return side[7].shape[2] < w16 and side[6].any()
            if _unsafe(a) or _unsafe(b):
                raise ValueError(
                    "cannot merge boosters with different categorical bin "
                    f"widths ({a[7].shape[2] * 16} vs {b[7].shape[2] * 16} "
                    "bins) when the narrower one contains categorical "
                    "splits: unseen-category/NaN routing would change; "
                    "retrain the continuation with the same max_bin")

            def pw(w):
                return np.pad(w, ((0, 0), (0, 0), (0, w16 - w.shape[2])))
            cw = np.concatenate([pw(a[7]), pw(b[7])])
        else:
            ic = cw = None
        return Booster(
            split_feature=np.concatenate([a[0], b[0]]),
            threshold=np.concatenate([a[1], b[1]]),
            split_bin=np.concatenate([a[2], b[2]]),
            leaf_value=np.concatenate([a[3], b[3]]),
            tree_class=np.concatenate([self.tree_class, other.tree_class]),
            max_depth=md, n_classes=self.n_classes, objective=self.objective,
            n_features=self.n_features, best_iteration=best,
            gain=np.concatenate([a[4], b[4]]) if both_aux else None,
            cover=np.concatenate([a[5], b[5]]) if both_aux else None,
            split_is_cat=ic, cat_words=cw)


def _raw_to_cat_bin_np(xf: np.ndarray, w16: int) -> np.ndarray:
    """Identity-bin assignment for raw categorical values, any shape —
    the ONE numpy copy of trainer.raw_to_cat_bin's mapping (overflow ids
    share the top bin, negatives bin 0, NaN -> last bin). Every host
    scoring path (per-tree descent, tree-parallel serving plan, SHAP
    membership) must route categories through this helper so a change to
    the bin mapping can never make them diverge."""
    top = w16 * 16 - 1
    b = np.clip(np.ceil(xf - 0.5), 0, top)
    return np.where(np.isnan(xf), top, b).astype(np.int64)


def _predict_raw_host(x, split_feature, threshold, leaf_value, tree_class,
                      max_depth: int, n_classes: int,
                      split_is_cat=None, cat_words=None):
    """Vectorized numpy ensemble descent — the host mirror of
    trainer._predict_raw_gather with identical routing semantics: go
    right unless x <= threshold (NaN compares False -> routes right,
    missing = largest), categorical nodes route by membership of the
    value's identity bin in the packed 16-bit words (raw_to_cat_bin).
    Exists for the serving hot path: executor-local scoring with no
    device dispatch (reference: HTTPSourceV2 pipelines score on the
    executor; LightGBM predict is likewise CPU-local)."""
    n = x.shape[0]
    rows = np.arange(n)
    scores = np.zeros((n, n_classes), np.float32)
    has_cat = (split_is_cat is not None and cat_words is not None
               and cat_words.shape[-1] > 0)
    for t in range(split_feature.shape[0]):
        sf_t, thr_t, lv_t = split_feature[t], threshold[t], leaf_value[t]
        node = np.zeros(n, np.int32)
        for _ in range(max_depth):
            f = sf_t[node]
            is_leaf = f < 0
            xf = x[rows, np.clip(f, 0, x.shape[1] - 1)]
            with np.errstate(invalid="ignore"):
                go_left = xf <= thr_t[node]
            if has_cat:
                b = _raw_to_cat_bin_np(xf, cat_words.shape[-1])
                words = cat_words[t][node]                    # (n, w16)
                member = ((words[rows, b >> 4] >> (b & 15)) & 1) == 1
                go_left = np.where(split_is_cat[t][node], member, go_left)
            child = np.where(go_left, 2 * node + 1, 2 * node + 2)
            node = np.where(is_leaf, node, child).astype(np.int32)
        scores[rows, tree_class[t]] += lv_t[node]
    return scores


def _pad_depth(b: Booster, max_depth: int):
    target = 2 ** (max_depth + 1) - 1
    cur = b.split_feature.shape[1]
    shape = (b.split_feature.shape[0], cur)
    gain = b.gain if b.gain is not None else np.zeros(shape, np.float32)
    cover = b.cover if b.cover is not None else np.zeros(shape, np.float32)
    ic = (b.split_is_cat if b.split_is_cat is not None
          else np.zeros(shape, bool))
    cw = (b.cat_words if b.cat_words is not None
          else np.zeros(shape + (0,), np.int32))
    if cur == target:
        return (b.split_feature, b.threshold, b.split_bin, b.leaf_value,
                gain, cover, ic, cw)
    pad = target - cur

    def p(a, fill):
        return np.pad(a, ((0, 0), (0, pad)), constant_values=fill)
    return (p(b.split_feature, -1), p(b.threshold, 0.0),
            p(b.split_bin, 0), p(b.leaf_value, 0.0),
            p(gain, 0.0), p(cover, 0.0), p(ic, False),
            np.pad(cw, ((0, 0), (0, pad), (0, 0))))


def _node_expectations(sf, lv):
    """Uniform-child-weight expected value per heap node (Saabas fallback)."""
    m = sf.shape[0]
    ev = np.array(lv, dtype=np.float64)
    for i in range(m - 1, -1, -1):
        l, r = 2 * i + 1, 2 * i + 2
        if sf[i] >= 0 and r < m:
            ev[i] = 0.5 * (ev[l] + ev[r])
    return ev


def _cat_member_np(xf, words_rows):
    """Vectorized numpy category-membership: xf (n,) raw values, words_rows
    (n, W16) packed 16-bit words. numpy oracle of trainer.raw_to_cat_bin +
    trainer.packed_member — identity bin assignment mirrors
    ops/binning.apply_bins (overflow ids share the top bin, negatives bin 0,
    NaN -> last bin) so SHAP walks the same paths the model scores."""
    w16 = words_rows.shape[-1]
    if w16 == 0:
        return np.zeros(xf.shape, bool)
    b = _raw_to_cat_bin_np(xf, w16)
    word = words_rows[np.arange(xf.shape[0]), b >> 4]
    return ((word >> (b & 15)) & 1) == 1


def _tree_shap(sf, thr, lv, cover, x, n_features, is_cat=None, cat_words=None):
    """Exact path-dependent TreeSHAP for one heap tree, vectorized over rows.

    Transcription of TreeSHAP (Lundberg, Erion & Lee 2018, 'Consistent
    Individualized Feature Attribution for Tree Ensembles', Algorithm 2 —
    the algorithm behind LightGBM/XGBoost pred_contrib and the shap
    package's tree_path_dependent mode). The tree's node sequence is
    identical for every sample — only the 'hot' (followed) child differs —
    so path state carries per-sample vectors: one_fraction and pweight are
    (n,)-wide per path slot while zero_fraction/feature are scalars. One
    DFS over <= 2^(d+1) nodes explains all rows at once.
    """
    n = x.shape[0]
    max_len = int(np.log2(sf.shape[0] + 1)) + 2
    phi = np.zeros((n, n_features + 1), dtype=np.float64)

    def extend(feats, zeros, ones, pweights, plen, pz, po, pi):
        """EXTEND: append (pi, pz, po) and update subset weights."""
        feats[plen] = pi
        zeros[plen] = pz
        ones[:, plen] = po
        pweights[:, plen] = 1.0 if plen == 0 else 0.0
        for i in range(plen - 1, -1, -1):
            pweights[:, i + 1] += po * pweights[:, i] * (i + 1) / (plen + 1)
            pweights[:, i] *= pz * (plen - i) / (plen + 1)

    def unwound_sum(zeros, ones, pweights, plen, idx):
        """UNWOUND_PATH_SUM: total pweight with path element idx removed."""
        one_f = ones[:, idx]                      # (n,)
        zero_f = float(zeros[idx])                # scalar
        nonzero = one_f != 0
        safe_one = np.where(nonzero, one_f, 1.0)
        nxt = pweights[:, plen].copy()
        total = np.zeros(n)
        for i in range(plen - 1, -1, -1):
            tmp_a = nxt * (plen + 1) / ((i + 1) * safe_one)
            nxt_a = pweights[:, i] - tmp_a * zero_f * (plen - i) / (plen + 1)
            if zero_f != 0:
                tmp_b = (pweights[:, i] / zero_f) / ((plen - i) / (plen + 1))
            else:
                tmp_b = np.zeros(n)
            total += np.where(nonzero, tmp_a, tmp_b)
            nxt = np.where(nonzero, nxt_a, nxt)
        return total

    def unwind(feats, zeros, ones, pweights, plen, idx):
        """UNWIND: remove path element idx in place; caller shortens plen."""
        one_f = ones[:, idx].copy()
        zero_f = float(zeros[idx])
        nonzero = one_f != 0
        safe_one = np.where(nonzero, one_f, 1.0)
        nxt = pweights[:, plen].copy()
        for i in range(plen - 1, -1, -1):
            old = pweights[:, i].copy()
            new_a = nxt * (plen + 1) / ((i + 1) * safe_one)
            if zero_f != 0:
                new_b = (old / zero_f) / ((plen - i) / (plen + 1))
            else:
                new_b = np.zeros(n)
            pweights[:, i] = np.where(nonzero, new_a, new_b)
            nxt = np.where(nonzero,
                           old - new_a * zero_f * (plen - i) / (plen + 1),
                           nxt)
        for i in range(idx, plen):
            feats[i] = feats[i + 1]
            zeros[i] = zeros[i + 1]
            ones[:, i] = ones[:, i + 1]

    def recurse(node, plen, feats, zeros, ones, pweights, pz, po, pi):
        feats = feats.copy()
        zeros = zeros.copy()
        ones = ones.copy()
        pweights = pweights.copy()
        extend(feats, zeros, ones, pweights, plen, pz, po, pi)
        f = int(sf[node])
        if f < 0 or 2 * node + 2 >= sf.shape[0]:  # leaf
            for i in range(1, plen + 1):
                w = unwound_sum(zeros, ones, pweights, plen, i)
                phi[:, feats[i]] += w * (ones[:, i] - zeros[i]) * float(lv[node])
            return
        left, right = 2 * node + 1, 2 * node + 2
        hot_is_left = x[:, f] <= thr[node]
        if is_cat is not None and is_cat[node]:
            wrow = np.broadcast_to(cat_words[node], (n, cat_words.shape[-1]))
            hot_is_left = _cat_member_np(x[:, f], wrow)
        c_node = max(float(cover[node]), 1e-12)
        rz_left = float(cover[left]) / c_node
        rz_right = float(cover[right]) / c_node
        # a feature revisited along the path: its prior element is unwound
        # and its fractions multiply into this split's (Algorithm 2 line 17)
        iz, io = 1.0, np.ones(n)
        sub_plen = plen
        dup = next((i for i in range(1, plen + 1) if feats[i] == f), -1)
        if dup >= 0:
            iz = float(zeros[dup])
            io = ones[:, dup].copy()
            unwind(feats, zeros, ones, pweights, sub_plen, dup)
            sub_plen -= 1
        recurse(left, sub_plen + 1, feats, zeros, ones, pweights,
                iz * rz_left, np.where(hot_is_left, io, 0.0), f)
        recurse(right, sub_plen + 1, feats, zeros, ones, pweights,
                iz * rz_right, np.where(hot_is_left, 0.0, io), f)

    # expected value (bias): cover-weighted mean over terminal nodes
    phi[:, -1] += _cover_weighted_expectation(sf, lv, cover)
    feats0 = np.full(max_len, -1, dtype=np.int64)
    zeros0 = np.ones(max_len)
    ones0 = np.ones((n, max_len))
    pweights0 = np.zeros((n, max_len))
    recurse(0, 0, feats0, zeros0, ones0, pweights0, 1.0, np.ones(n), -1)
    return phi


def _cover_weighted_expectation(sf, lv, cover):
    """E[f(x)] over the training distribution: cover-weighted leaf mean."""
    m = sf.shape[0]
    is_internal = np.zeros(m, bool)
    for i in range(m):
        if sf[i] >= 0 and 2 * i + 2 < m:
            is_internal[i] = True
    leaf_mask = ~is_internal & (cover > 0)
    total = cover[leaf_mask].sum()
    if total <= 0:
        return 0.0
    return float((lv[leaf_mask] * cover[leaf_mask]).sum() / total)
