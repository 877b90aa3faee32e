"""The yardstick's arithmetic at the shipped configurations, against numbers
worked out by hand, and the seeded host table."""
import json
import os

import numpy as np
import pytest

import gbdt_common
import harness
import work

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(kind, name):
    with open(os.path.join(BENCH, kind, name + ".json")) as f:
        return json.load(f)


def test_gpt2_medium_flops():
    cfg, mix = _load("configs", "gpt2-medium"), _load("traffic",
                                                       "steps-8x1024-zipf")
    q = work.quantities(cfg, mix)
    # 24 x (4 x 1024^2 + 2 x 1024 x 4096) = 302M matmul parameters; forward
    # per 1024-token sequence 6.18e11 + 1.05e11 (head) + 5.15e10 (attention)
    per_step = q["lm_flops_per_token"] * mix["batch"] * mix["seq"]
    assert per_step == pytest.approx(1.86e13, rel=0.005)
    # attention is 6 causal S x S x d matmuls a layer and sequence
    assert q["flash_flops_per_step"] == 6 * 1024 ** 3 * 24 * 8
    assert q["flash_flops_per_step"] / per_step == pytest.approx(0.0665,
                                                                 rel=0.01)
    assert "hist_bytes_per_iter" not in q


def test_gbdt_histogram_bytes():
    cfg = _load("configs", "gbdt-dense-63bin")
    q = work.quantities(cfg, _load("traffic", "fits-resident-20it"))
    assert q == {"hist_bytes_per_iter": 5 * cfg["n_rows"] * (32 + 12)}
    assert cfg["n_rows"] == 16_000_000
    assert cfg["n_bins"] == cfg["max_bin"] + 1 == 64
    assert (cfg["n_features"], cfg["max_depth"], cfg["num_leaves"]) == \
        (32, 5, 31)


def test_peaks_table_knows_the_v5e_only():
    row = harness.peaks_for("TPU v5 lite")
    assert (row["bf16_flops_per_s"], row["hbm_bytes_per_s"]) == (197e12,
                                                                 819e9)
    with pytest.raises(harness.BenchError):
        harness.peaks_for("TPU v9")


def test_host_table_is_the_seed_and_learnable():
    x1, y1 = gbdt_common.host_table(2 ** 31 + 5, 50_000, 8, 0.5)
    x2, y2 = gbdt_common.host_table(2 ** 31 + 5, 50_000, 8, 0.5)
    x3, _ = gbdt_common.host_table(2 ** 31 + 6, 50_000, 8, 0.5)
    assert np.array_equal(x1, x2) and np.array_equal(y1, y2)
    assert not np.array_equal(x1, x3)
    assert x1.dtype == np.float32 and abs(float(x1.mean())) < 0.01
    assert 0.4 < y1.mean() < 0.6


def test_auc_and_logloss():
    y = np.array([0, 0, 1, 1])
    assert gbdt_common.auc(y, [0.1, 0.4, 0.35, 0.8]) == 0.75
    assert gbdt_common.auc(y, [0.5, 0.5, 0.5, 0.5]) == 0.5
    assert gbdt_common.logloss(y, np.zeros(4)) == pytest.approx(np.log(2))


def test_reference_gbdt_learns_and_keeps_the_leaf_budget():
    ref = harness.load_module("reference", "gbdt_levelwise")
    rng = np.random.default_rng(0)
    bins = rng.integers(0, 64, (4096, 8)).astype(np.uint8)
    y = (bins[:, 0].astype(int) + bins[:, 1] > 64).astype(np.float32)
    cfg = dict(_load("configs", "gbdt-dense-63bin"))
    m0 = ref.fit_margins(bins, y, cfg, 0)
    m5 = ref.fit_margins(bins, y, cfg, 5)
    assert gbdt_common.logloss(y, m5) < gbdt_common.logloss(y, m0) - 0.2
    # one tree of a 31-leaf budget gives at most 31 distinct margins
    assert len(np.unique(ref.fit_margins(bins, y, cfg, 1))) <= 31
