"""The `lm_train_hybrid` driver, the `counter` reader and the metric files
of the `qwen3-next-80b-a3b` cell, on the CPU: a toy-manifest run end to end,
the readers on made-up values, and the work functions pinned to
hand-computed values at the published widths."""
import json
import os

import pytest

import toy
import toy_hybrid

BENCH = toy.BENCH
CELL = "qwen3next-train-2x8192"


def load(kind, name):
    with open(os.path.join(BENCH, kind, name + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def manifest(tmp_path_factory):
    return toy_hybrid.build(str(tmp_path_factory.mktemp("toyhybrid")))


def test_hybrid_driver_end_to_end(manifest):
    proc = toy.run(manifest, "toy-hybrid", seed=2 ** 31 + 11, seconds=8.0)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    notes = line["notes"]
    # a loaded host may not finish 20 steps in the window: nothing else
    # may be wrong
    assert all("steps completed" in p for p in line["problems"]), \
        line["problems"]
    assert line["failed"] == 0 and line["attempted"] >= 3
    assert set(line["metrics"]) == {"lm_tokens_per_s", "lm_step_p95_ms",
                                    "setup_s"}
    assert notes["compiles_in_window"] == 0
    assert abs(notes["loss_system"] - notes["loss_reference"]) < 1e-4
    errors = notes["grad_rel_error"]
    assert set(errors) == {
        "gdn.A_log", "gdn.dt_bias", "gdn.conv", "gdn.in_proj_qkvz",
        "moe.router", "moe.w_gate", "moe.w_up", "moe.w_down",
        "moe.shared_expert_gate", "attn.q_proj", "head"}
    assert all(err < 1e-4 < limit for err, limit in errors.values())
    # Adam's first step is lr in the sign of the gradient, so the leaves'
    # change differs where a gradient is as small as its rounding
    apart, limit = notes["param_change_error"]
    assert apart < 0.05 < limit
    assert notes["moe_pairs_routed_per_step"] == 2 * 96 * 4 * 4
    # 8 of 16 experts held; at a toy's rate the router drifts in a few steps
    assert 0.0 < notes["moe_pairs_held_share"] < 1.0
    assert notes["moe_pairs_held_first10"] > 0
    assert notes["moe_pairs_held_share_expected"] == 0.5
    # the experts were placed so that this chip of two carries half of each
    # layer's pairs on the first batch, to a few pairs of 768
    placed = notes["placed_share_by_layer"]
    assert len(placed) == 4 and all(abs(s - 0.5) < 0.02 for s in placed), \
        placed


@pytest.mark.parametrize("loads,per_chip,want", [
    # falling load, each to the least loaded chip with room
    ([9, 7, 6, 5, 4, 1], 3, [[0, 3, 5], [1, 2, 4]]),
    # a full chip takes no more, however light it is
    ([10, 1, 1, 1], 2, [[0, 3], [1, 2]]),
    # equal loads keep their order
    ([2, 2, 2, 2], 1, [[0], [1], [2], [3]]),
])
def test_deal_experts(loads, per_chip, want):
    from harness import load_module
    driver = load_module("drivers", "lm_train_hybrid")
    assert driver.deal_experts(loads, per_chip) == want


def test_deal_experts_evens_a_skewed_layer():
    """512 experts of which a few are wanted several times the mean: every
    chip of 16 gets 32 experts and within 1% of a sixteenth of the load."""
    import numpy as np
    from harness import load_module
    driver = load_module("drivers", "lm_train_hybrid")
    loads = np.random.default_rng(5).pareto(1.5, 512) * 300
    dealt = driver.deal_experts(loads, 32)
    assert sorted(e for chip in dealt for e in chip) == list(range(512))
    assert all(len(chip) == 32 for chip in dealt)
    carried = np.array([loads[chip].sum() for chip in dealt])
    assert np.abs(carried / loads.sum() * 16 - 1).max() < 0.01


def test_counter_reader():
    from harness import load_module
    reader = load_module("readers", "counter")
    spec = load("metrics", "moe_load_max_over_mean")
    program = {"moe.load.max_over_mean": 1.75}
    assert reader.read(spec, {"trace": object(), "program": program}) == 1.75
    assert reader.read(spec, {"trace": None, "program": program}) is None
    assert reader.read(spec, {"trace": object(), "program": {}}) is None


def test_new_metric_files_name_their_readers_and_regions():
    import sys
    sys.path.insert(0, os.path.dirname(BENCH))
    from mmlspark_tpu.telemetry import names as tnames
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        manifest = json.load(f)
    mine = [m for m in manifest["per_layer"]
            if CELL in m.get("workloads", [])]
    assert len(mine) == 23 and sum(
        m["workloads"] == [CELL] for m in mine) == 10
    for m in mine:
        spec = load("metrics", m["name"])
        assert os.path.isfile(os.path.join(BENCH, "readers",
                                           spec["reader"] + ".py"))
        regions = spec.get("region")
        for region in ([regions] if isinstance(regions, str)
                       else regions or []):
            assert region == "any" or region in tnames.DEVICE_REGIONS \
                or region in tnames.HOST_REGIONS


def test_rooflines_from_made_up_values():
    from harness import load_module
    derived = load_module("readers", "derived")
    names = {"peak_bf16_flops_per_s": 197e12,
             "gdn_scan_flops_per_step": 541165879296.0,
             "gdn_scan_ms_per_step": 27.47,
             "moe_experts_flops_per_step": 773094113280.0,
             "moe_experts_ms_per_step": 7.85,
             "flash_d256_flops_per_step": 3298534883328.0,
             "flash_fwd_ms_per_step": 10.0, "flash_dq_ms_per_step": 10.0,
             "flash_dkv_ms_per_step": 13.49,
             "lm_flops_per_token": 1386135552.0, "lm_tokens_per_s": 25000.0}
    read = lambda metric: derived.read(load("metrics", metric),
                                       {"names": names})
    assert read("gdn_scan_roofline") == pytest.approx(10.0, rel=1e-3)
    assert read("moe_experts_roofline") == pytest.approx(50.0, rel=1e-3)
    assert read("flash_d256_roofline") == pytest.approx(50.0, rel=1e-3)
    assert read("lm_mfu") == pytest.approx(17.59, rel=1e-3)
    assert derived.read(load("metrics", "gdn_scan_roofline"),
                        {"names": {}}) is None


@pytest.fixture(scope="module")
def published():
    return load("configs", "qwen3-next-80b-a3b")


@pytest.mark.parametrize("function,args,want", [
    # d 2048 x (q 2048 + k 2048 + v 4096 + z 4096) + 2048 x 64 + 4096 x 2048
    # multiply-adds, + 4 taps x 8192 channels, x 2
    ("gdn_projection_flops_per_token", (), 67436544.0),
    # 7 x 128 x 128 x 32 value heads
    ("gdn_scan_flops_per_token", (), 3670016.0),
    # forward 2 B x (2 x 2048 + 2 x 4096) + 256; backward the same again
    # + 2 B x (2 x 2048 + 4096) + 256
    ("gdn_scan_bytes_per_token", (), 66304.0),
    # 2 x (2048 x 8192 + 2 x 2048 x 512 + 4096 x 2048)
    ("attention_projection_flops_per_token", (), 54525952.0),
    # 2 x 8192 x 16 x 256
    ("causal_attention_flops_per_token", (8192,), 67108864.0),
    # 2 x (2048 x 512 + 3 x 2048 x 512 + 2048)
    ("moe_fixed_flops_per_token", (), 8392704.0),
    ("expert_flops_per_pair", (), 6291456.0),
    ("head_flops_per_token", (), 77791232.0),
    # 3 x (3 x 71,106,560 + 121,634,816 + 4 x 8,392,704 + 2.5 x 6,291,456
    # + 77,791,232), at 10 x 32 / 512 pairs a token and layer
    ("lm_flops_per_token", (8192, 2.5), 1386135552.0),
    ("gdn_scan_flops_per_step", (2, 8192), 541165879296.0),
    ("gdn_scan_bytes_per_step", (2, 8192), 3258974208.0),
    # 18 x 2048 x 512 x 40,960 pairs (16,384 tokens x 4 layers x 0.625)
    ("moe_experts_flops_per_step", (40960,), 773094113280.0),
    # 6 x 8192^2 x 4096 x 1 layer x 2 sequences
    ("flash_flops_per_step", (2, 8192), 3298534883328.0),
])
def test_work_at_the_published_widths(published, function, args, want):
    import work_qwen3_next as work
    assert getattr(work, function)(published, *args) == want


def test_layer_kinds_of_the_share(published):
    import work_qwen3_next as work
    assert work.layer_kinds(published) == ["gdn", "gdn", "gdn", "attention"]


def test_configuration_keeps_every_published_width(published):
    """Every number of the catalog's `config` is in the file under its key,
    but for the three that `reduced` lists."""
    catalog = {
        "decoder_sparse_step": 1, "full_attention_interval": 4,
        "head_dim": 256, "hidden_size": 2048, "intermediate_size": 5120,
        "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
        "linear_num_key_heads": 16, "linear_num_value_heads": 32,
        "linear_value_head_dim": 128, "max_position_embeddings": 262144,
        "moe_intermediate_size": 512, "num_attention_heads": 16,
        "num_experts": 512, "num_experts_per_tok": 10,
        "num_hidden_layers": 48, "num_key_value_heads": 2,
        "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06,
        "rope_theta": 10000000, "shared_expert_intermediate_size": 512,
        "vocab_size": 151936}
    differs = {k for k, v in catalog.items() if published.get(k) != v}
    assert differs == {"num_experts", "vocab_size"}
    assert published["published"]["num_experts"] == 512
    assert published["published"]["vocab_size"] == 151936
    assert published["num_layers"] == 4
    assert published["experts_held"] == [0, published["num_experts"]]
