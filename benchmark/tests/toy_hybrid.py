"""toy_hybrid.py - the toy tree of `toy.py` with a toy `qwen3_next`
configuration, its traffic mix and its cell ADDED: what `test_hybrid.py`
runs the `lm_train_hybrid` driver, the `counter` reader and the new metric
files on, on the CPU."""
import json
import os
import shutil

import toy

TOY_QWEN = {
    "hidden_size": 64, "head_dim": 32, "num_attention_heads": 4,
    "num_key_value_heads": 2, "linear_num_key_heads": 2,
    "linear_num_value_heads": 4, "linear_key_head_dim": 16,
    "linear_value_head_dim": 16, "moe_intermediate_size": 32,
    "shared_expert_intermediate_size": 32, "num_experts": 8,
    "num_experts_per_tok": 4, "num_layers": 4, "vocab_size": 257,
    "experts_held": [8, 16],
    "published": {"num_experts": 16, "num_hidden_layers": 48,
                  "vocab_size": 151936},
}


def build(tmp):
    """`toy.build(tmp)` plus the hybrid toy; returns the manifest's path."""
    toy.build(tmp)
    bench = os.path.join(tmp, "benchmark")
    with open(os.path.join(bench, "configs",
                           "qwen3-next-80b-a3b.json")) as f:
        cfg = dict(json.load(f), **TOY_QWEN)
    cfg["trainer"] = dict(cfg["trainer"], compute_dtype="float32")
    # the cell's rate is for 16,384 tokens a step over 85 steps; a toy's
    # loss falls inside a few steps only at a toy's rate
    cfg["assumed"] = dict(cfg["assumed"], lr=1e-3)
    with open(os.path.join(bench, "configs", "toy-qwen.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(bench, "traffic", "toy-hybrid-steps.json"),
              "w") as f:
        json.dump({"driver": "lm_train_hybrid", "batch": 2, "seq": 96,
                   "warmup_steps": 3, "zipf_exponent": 1.0}, f)
    path = os.path.join(tmp, "BENCHMARK.json")
    shutil.copy(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "data", "toy_hybrid_manifest.json"), path)
    return path
