"""toy_lfm2.py - the toy tree of `toy.py` with a toy `lfm2_moe`
configuration, its traffic mix and its cell ADDED: what `test_lfm2.py` runs
the `lm_train_lfm2` driver and the new metric files on, on the CPU."""
import json
import os
import shutil

import toy

TOY_LFM2 = {
    "hidden_size": 64, "intermediate_size": 96, "num_attention_heads": 4,
    "num_key_value_heads": 2, "moe_intermediate_size": 32, "num_experts": 8,
    "num_experts_per_tok": 4, "vocab_size": 257, "experts_held": [8, 16],
    "published": {"num_experts": 16, "num_hidden_layers": 40,
                  "num_dense_layers": 2, "vocab_size": 65536},
}


def build(tmp):
    """`toy.build(tmp)` plus the LFM2 toy; returns the manifest's path."""
    toy.build(tmp)
    bench = os.path.join(tmp, "benchmark")
    with open(os.path.join(bench, "configs", "lfm2-24b-a2b.json")) as f:
        cfg = dict(json.load(f), **TOY_LFM2)
    cfg["trainer"] = dict(cfg["trainer"], compute_dtype="float32")
    # the cell's rate is for 32,768 tokens a step; a toy's loss falls
    # inside a few steps only at a toy's rate
    cfg["assumed"] = dict(cfg["assumed"], lr=1e-3)
    with open(os.path.join(bench, "configs", "toy-lfm2.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(bench, "traffic", "toy-lfm2-steps.json"),
              "w") as f:
        json.dump({"driver": "lm_train_lfm2", "batch": 2, "seq": 96,
                   "warmup_steps": 3, "zipf_exponent": 1.0}, f)
    path = os.path.join(tmp, "BENCHMARK.json")
    shutil.copy(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "data", "toy_lfm2_manifest.json"), path)
    return path
