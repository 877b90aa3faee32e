"""toy_phi4flash.py - the toy tree of `toy.py` with a toy `phi4flash`
configuration, its traffic mix and its cell ADDED: what `test_phi4flash.py`
runs the `lm_train_phi4flash` driver and the new metric files on, on the
CPU."""
import json
import os
import shutil

import toy

# six layers of twelve, one period of each run, at toy widths (the Mamba
# sizes at the top level override the file's `assumed`)
TOY_PHI4FLASH = {
    "hidden_size": 64, "intermediate_size": 96, "num_attention_heads": 8,
    "num_key_value_heads": 4, "sliding_window": 24, "vocab_size": 257,
    "num_hidden_layers": 12, "held_layers": [4, 9],
    "mamba_d_state": 8, "mamba_dt_rank": 4,
}


def build(tmp):
    """`toy.build(tmp)` plus the phi4flash toy; returns the manifest's
    path."""
    toy.build(tmp)
    bench = os.path.join(tmp, "benchmark")
    with open(os.path.join(bench, "configs",
                           "phi-4-mini-flash-reasoning.json")) as f:
        cfg = dict(json.load(f), **TOY_PHI4FLASH)
    cfg["trainer"] = dict(cfg["trainer"], compute_dtype="float32")
    # the cell's rate is for 16,384 tokens a step; a toy's loss falls
    # inside a few steps only at a toy's rate
    cfg["assumed"] = dict(cfg["assumed"], lr=1e-3)
    with open(os.path.join(bench, "configs", "toy-phi4flash.json"),
              "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(bench, "traffic", "toy-phi4flash-steps.json"),
              "w") as f:
        json.dump({"driver": "lm_train_phi4flash", "batch": 2, "seq": 96,
                   "warmup_steps": 3, "zipf_exponent": 1.0}, f)
    path = os.path.join(tmp, "BENCHMARK.json")
    shutil.copy(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "data", "toy_phi4flash_manifest.json"), path)
    return path
