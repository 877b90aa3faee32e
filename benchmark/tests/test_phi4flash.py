"""The `lm_train_phi4flash` driver and the metric files of the
`phi-4-mini-flash-reasoning` cell, on the CPU: a toy-manifest run end to
end, the new rooflines on made-up values, and the work functions pinned to
hand-computed values at the published widths."""
import json
import os

import pytest

import toy
import toy_phi4flash

BENCH = toy.BENCH
CELL = "phi4flash-train-2x8192"
LEAVES = {"memory.A_log", "memory.x_proj", "memory.in_proj", "mamba.A_log",
          "gmu.w1", "kv.qkv_proj", "cross.q_proj", "cross.lq1",
          "cross.subln", "window.qkv_proj", "embed"}
NEW = ["flash_diff_roofline", "flash_window_ms_per_step",
       "flash_window_roofline", "lm_gmu_ms_per_step", "lm_ssm_ms_per_step",
       "ssm_scan_ms_per_step", "ssm_scan_roofline"]


def load(kind, name):
    with open(os.path.join(BENCH, kind, name + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def manifest(tmp_path_factory):
    return toy_phi4flash.build(str(tmp_path_factory.mktemp("toyphi4")))


def test_phi4flash_driver_end_to_end(manifest):
    proc = toy.run(manifest, "toy-phi4flash", seed=2 ** 31 + 11,
                   seconds=8.0)
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
    # off the TPU every scan call takes the XLA form, and is counted; the
    # GMU and the cross layer read what two earlier layers made
    routes = notes["ssm_scan_routes"]
    assert routes["pallas"] == 0 and routes["xla"] >= 2
    assert routes["shared_readers"] >= 2


def test_new_metric_files_name_their_readers_and_regions():
    import sys
    sys.path.insert(0, os.path.dirname(BENCH))
    from mmlspark_tpu.telemetry import names as tnames
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        manifest = json.load(f)
    mine = [m for m in manifest["per_layer"]
            if CELL in m.get("workloads", [])]
    assert len(mine) == 21 and sorted(
        m["name"] for m in mine if m["workloads"] == [CELL]) == NEW
    # `derived` reads what came before it
    order = [m["name"] for m in mine]
    assert order.index("ssm_scan_ms_per_step") \
        < order.index("ssm_scan_roofline")
    assert order.index("flash_window_ms_per_step") \
        < order.index("flash_window_roofline")
    assert order.index("flash_dkv_ms_per_step") \
        < order.index("flash_diff_roofline")
    for m in mine:
        spec = load("metrics", m["name"])
        assert os.path.isfile(os.path.join(BENCH, "readers",
                                           spec["reader"] + ".py"))
        regions = spec.get("region")
        for region in ([regions] if isinstance(regions, str)
                       else regions or []):
            assert region == "any" or region in tnames.DEVICE_REGIONS \
                or region in tnames.HOST_REGIONS


def test_the_window_pattern_reads_the_win_kernels_and_no_other():
    """The accepted readers' pattern (`^%flash_fwd[.0-9]* =`) does not
    catch a windowed kernel, and the new one catches nothing else."""
    import re
    new = re.compile(load("metrics", "flash_window_ms_per_step")["pattern"])
    old = {k: re.compile(load("metrics", f"flash_{k}_ms_per_step")
                         ["pattern"]) for k in ("fwd", "dq", "dkv")}
    call = ' = (bf16[40,8192,128]) custom-call(), ' \
           'custom_call_target="tpu_custom_call"'
    for k in old:
        win, plain = f"%flash_{k}_win.3{call}", f"%flash_{k}.12{call}"
        assert new.search(win) and not new.search(plain)
        assert old[k].search(plain) and not old[k].search(win)
    assert not new.search(f"%ssm_fwd.1{call}")


def test_rooflines_from_made_up_values():
    """Each share reads 50% where the time is twice what the peak needs,
    and is absent where a name is."""
    from harness import load_module
    derived = load_module("readers", "derived")
    names = {"peak_bf16_flops_per_s": 197e12, "peak_hbm_bytes_per_s": 819e9,
             "ssm_scan_bytes_per_step": 2690646016.0,
             "ssm_scan_ms_per_step": 6.5706,
             "flash_window_flops_per_step": 374491054080.0,
             "flash_window_ms_per_step": 3.80194,
             "flash_diff_flops_per_step": 6185507880960.0,
             "flash_fwd_ms_per_step": 20.0, "flash_dq_ms_per_step": 20.0,
             "flash_dkv_ms_per_step": 22.797}
    read = lambda metric: derived.read(load("metrics", metric),
                                       {"names": names})
    for metric in ("ssm_scan_roofline", "flash_window_roofline",
                   "flash_diff_roofline"):
        assert read(metric) == pytest.approx(50.0, rel=1e-3), metric
        assert derived.read(load("metrics", metric), {"names": {}}) is None


@pytest.fixture(scope="module")
def published():
    return load("configs", "phi-4-mini-flash-reasoning")


@pytest.mark.parametrize("function,args,want", [
    # W(W + 1) / 2 + (S - W) W at W = 512; S (S + 1) / 2
    ("visible_pairs", (8192, 512), 4063488),
    ("visible_pairs", (8192,), 33558528),
    ("visible_pairs", (8192, 8192), 33558528),
    # 2 x (3 x 64 + 3 x 128)
    ("flash_ops_per_pair", (), 1152.0),
    # 4,063,488 pairs x 40 heads x 1,152 x 2 sequences x 1 layer
    ("flash_window_flops_per_step", (2, 8192), 374491054080.0),
    # 33,558,528 x 40 x 1,152 x 2 sequences x 2 layers
    ("flash_diff_flops_per_step", (2, 8192), 6185507880960.0),
    # 2 B x ((3 x 5120 + 32) + (5 x 5120 + 64)) x 16,384 tokens x 2 layers
    ("ssm_scan_bytes_per_step", (2, 8192), 2690646016.0),
    ("parameter_count", (), 697094272),
])
def test_work_at_the_published_widths(published, function, args, want):
    import work_phi4_flash as work
    fn = getattr(work, function)
    got = fn(*args) if function == "visible_pairs" else fn(published, *args)
    assert got == want


def test_flops_per_token_is_needed_work_only(published):
    """6 x (the layers' matmul parameters + the head) + attention over the
    visible pairs alone: 4.585 GFLOP a token, of which 0.40 attention; no
    share can pass 100% for a cause in this file: every count is a lower
    bound of what any implementation runs (no remat, no masked pair, no
    second q.k or dO.V of the flash backward, K and V not repeated)."""
    import work_phi4_flash as work
    total = work.lm_flops_per_token(published, 8192)
    assert total == pytest.approx(4.5853e9, rel=1e-4)
    attention = (work.flash_window_flops_per_step(published, 1, 8192)
                 + work.flash_diff_flops_per_step(published, 1, 8192)) / 8192
    assert attention == pytest.approx(0.4004e9, rel=1e-3)
    matmul_params = work.parameter_count(published)
    assert total - attention == pytest.approx(6.0 * matmul_params, rel=5e-3)
    assert work.layers_held(published) == [
        "mamba", "window_attention", "memory_mamba", "kv_attention", "gmu",
        "cross_attention"]
    assert "697,094,272 parameters" in published["deployment"]["this_chip"]


def test_configuration_keeps_every_published_width(published):
    """Every number of the catalog's `config` is in the file under its key,
    but for `vocab_size`, which `reduced` lists with the depth key of the
    cut's own."""
    catalog = {
        "embd_pdrop": 0, "hidden_size": 2560, "intermediate_size": 10240,
        "layer_norm_eps": 1e-05, "max_position_embeddings": 262144,
        "mb_per_layer": 2, "num_attention_heads": 40,
        "num_hidden_layers": 32, "num_key_value_heads": 20,
        "resid_pdrop": 0, "sliding_window": 512, "vocab_size": 200064}
    differs = {k for k, v in catalog.items() if published.get(k) != v}
    assert differs == {"vocab_size"}
    assert published["published"]["vocab_size"] == 200064
    assert published["vocab_size"] * 8 == 200064
    assert published["tie_word_embeddings"] is True \
        and published["mlp_bias"] is False \
        and published["lm_head_bias"] is False \
        and published["hidden_act"] == "silu" \
        and published["model_type"] == "phi4flash"
    assert published["held_layers"] == [14, 19]
    assert published["assumed"]["mamba_dt_rank"] == 160
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == "phi-4-mini-flash-reasoning")
    assert sorted(entry["reduced"]) == ["held_layers", "vocab_size"]
