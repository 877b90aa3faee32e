"""The `lm_train_lfm2` driver and the metric files of the `lfm2-24b-a2b`
cell, on the CPU: a toy-manifest run end to end, the new rooflines on
made-up values, and the work functions pinned to hand-computed values at
the published widths."""
import json
import os

import pytest

import toy
import toy_lfm2

BENCH = toy.BENCH
CELL = "lfm2moe-train-4x8192"
LEAVES = {"conv.in_proj", "conv.taps", "conv.out_proj", "attn.q_proj",
          "attn.q_layernorm", "mlp.w1", "moe.router", "moe.w1", "moe.w3",
          "moe.w2", "embed"}


def load(kind, name):
    with open(os.path.join(BENCH, kind, name + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def manifest(tmp_path_factory):
    return toy_lfm2.build(str(tmp_path_factory.mktemp("toylfm2")))


def test_lfm2_driver_end_to_end(manifest):
    proc = toy.run(manifest, "toy-lfm2", seed=2 ** 31 + 11, seconds=8.0)
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
    assert set(errors) == LEAVES
    assert all(err < 1e-4 < limit for err, limit in errors.values())
    apart, limit = notes["param_change_error"]
    assert apart < 0.05 < limit
    # the one limit that holds the precision is paired: under 0.995 of what
    # the reference reads in bfloat16 on the same batch
    paired = notes["paired_precision"]
    assert paired["leaf"] == "conv.taps"
    assert paired["system"] < paired["limit"] < paired["bfloat16_reference"]
    # one leading layer without experts, then four expert layers
    assert notes["moe_pairs_routed_per_step"] == 2 * 96 * 4 * 4
    assert 0.0 < notes["moe_pairs_held_share"] < 1.0
    assert notes["moe_pairs_held_share_expected"] == 0.5
    placed = notes["placed_share_by_layer"]
    assert len(placed) == 4 and all(abs(s - 0.5) < 0.02 for s in placed), \
        placed
    # the seeded selection bias changes some of each layer's picks, not most
    changed = notes["expert_bias_changed_share_by_layer"]
    assert len(changed) == 4 and all(0.0 < c < 0.3 for c in changed), changed


def test_new_metric_files_name_their_readers_and_regions():
    import sys
    sys.path.insert(0, os.path.dirname(BENCH))
    from mmlspark_tpu.telemetry import names as tnames
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        manifest = json.load(f)
    mine = [m for m in manifest["per_layer"]
            if CELL in m.get("workloads", [])]
    assert len(mine) == 23 and sorted(
        m["name"] for m in mine if m["workloads"] == [CELL]) == [
            "conv_gate_ms_per_step", "conv_gate_roofline",
            "flash_d64_roofline", "lm_conv_ms_per_step"]
    # `derived` reads what came before it
    order = [m["name"] for m in mine]
    assert order.index("conv_gate_ms_per_step") \
        < order.index("conv_gate_roofline")
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
    names = {"peak_bf16_flops_per_s": 197e12, "peak_hbm_bytes_per_s": 819e9,
             "conv_gate_bytes_per_step": 5905580032.0,
             "conv_gate_ms_per_step": 14.42,
             "flash_d64_flops_per_step": 3298534883328.0,
             "flash_fwd_ms_per_step": 10.0, "flash_dq_ms_per_step": 10.0,
             "flash_dkv_ms_per_step": 13.49}
    read = lambda metric: derived.read(load("metrics", metric),
                                       {"names": names})
    assert read("conv_gate_roofline") == pytest.approx(50.0, rel=1e-3)
    assert read("flash_d64_roofline") == pytest.approx(50.0, rel=1e-3)
    assert derived.read(load("metrics", "conv_gate_roofline"),
                        {"names": {}}) is None


@pytest.fixture(scope="module")
def published():
    return load("configs", "lfm2-24b-a2b")


@pytest.mark.parametrize("function,args,want", [
    # 2 x (2048 x 6144 + 2048 x 2048) + 7 x 2048 (B * u, 3 taps, C *)
    ("conv_flops_per_token", (), 33568768.0),
    # 2 B x 2048 x (B, C, u, y + B, C, u, dy, dB, dC, du)
    ("conv_gate_bytes_per_token", (), 45056.0),
    # 2 x (2048 x 2048 + 2 x 2048 x 512 + 2048 x 2048)
    ("attention_projection_flops_per_token", (), 20971520.0),
    # 2 x 8192 x 32 x 64
    ("causal_attention_flops_per_token", (8192,), 33554432.0),
    ("dense_mlp_flops_per_token", (), 144703488.0),
    ("router_flops_per_token", (), 262144.0),
    ("expert_flops_per_pair", (), 18874368.0),
    ("head_flops_per_token", (), 33554432.0),
    # 3 x (4 x 33,568,768 + 54,525,952 + 144,703,488 + 4 x 262,144
    # + 2 x 18,874,368 + 33,554,432), at 4 x 4 x 8 / 64 pairs a token
    ("lm_flops_per_token", (8192, 2.0), 1217568768.0),
    # 45,056 x 32,768 tokens x 4 conv layers
    ("conv_gate_bytes_per_step", (4, 8192), 5905580032.0),
    # 18 x 2048 x 1536 x 65,536 pairs (32,768 tokens x 4 layers x 0.5)
    ("moe_experts_flops_per_step", (65536,), 3710851743744.0),
    # 6 x 8192^2 x 2048 x 1 layer x 4 sequences
    ("flash_flops_per_step", (4, 8192), 3298534883328.0),
    # conv mixers 4 x 16,783,360; attention 10,485,888; dense MLP
    # 72,351,744; 4 x (75,497,472 held experts + 131,072 router + 64 bias);
    # 10 layer norms and the final one; 8192 x 2048 embedding
    ("parameter_count", (), 469285248),
])
def test_work_at_the_published_widths(published, function, args, want):
    import work_lfm2_moe as work
    assert getattr(work, function)(published, *args) == want


def test_layers_of_the_share(published):
    import work_lfm2_moe as work
    assert work.layers_held(published) == [
        ("conv", "dense"), ("full_attention", "experts"),
        ("conv", "experts"), ("conv", "experts"), ("conv", "experts")]
    assert "469,285,248 parameters" in published["deployment"]["this_chip"]


def test_configuration_keeps_every_published_width(published):
    """Every number of the catalog's `config` is in the file under its key,
    but for the three that `reduced` lists; nested groups whole."""
    catalog = {
        "conv_L_cache": 3, "hidden_size": 2048, "intermediate_size": 11776,
        "max_position_embeddings": 128000, "moe_intermediate_size": 1536,
        "norm_eps": 1e-05, "num_attention_heads": 32, "num_dense_layers": 2,
        "num_experts": 64, "num_experts_per_tok": 4,
        "num_hidden_layers": 40, "num_key_value_heads": 8,
        "routed_scaling_factor": 1, "vocab_size": 65536}
    differs = {k for k, v in catalog.items() if published.get(k) != v}
    assert differs == {"num_dense_layers", "num_experts", "vocab_size"}
    assert published["published"]["num_dense_layers"] == 2
    assert published["published"]["num_experts"] == 64
    assert published["published"]["vocab_size"] == 65536
    assert published["rope_parameters"] == {"rope_theta": 1000000,
                                            "rope_type": "default"}
    types = published["layer_types"]
    assert len(types) == 40 and types.count("full_attention") == 10 \
        and types[:3] == ["conv", "conv", "full_attention"]
    assert published["conv_bias"] is False \
        and published["norm_topk_prob"] and published["use_expert_bias"]
    assert published["num_layers"] == 5
    assert published["experts_held"] == [0, published["num_experts"]]
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == "lfm2-24b-a2b")
    assert sorted(entry["reduced"]) == ["num_dense_layers", "num_experts",
                                        "num_layers", "vocab_size"]
