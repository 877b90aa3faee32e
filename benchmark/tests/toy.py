"""toy.py - a toy benchmark tree for the tests: a copy of `benchmark/` in a
temporary directory with toy configurations, one toy traffic mix, one more
per-layer metric and four toy cells ADDED as files and manifest entries, no
file of the benchmark edited. It is what a later PR does when it adds a cell,
and it is never shipped as one."""
import json
import os
import shutil
import subprocess
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)

TOY_GBDT = {"n_rows": 16384, "n_features": 8}
TOY_GPT = {"n_layer": 2, "n_embd": 64, "n_head": 2, "n_inner": 128,
           "n_positions": 64, "n_ctx": 64, "vocab_size": 257}


def build(tmp):
    """Copy the benchmark into `tmp`, add the toy files, return the path of
    the toy manifest (`data/toy_manifest.json`: the toy cells with every
    metric the drivers and readers serve)."""
    bench = os.path.join(tmp, "benchmark")
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        ".jax_cache", ".traces", "__pycache__", "data", "_*"))

    def config(src, name, changes):
        with open(os.path.join(bench, "configs", src + ".json")) as f:
            cfg = dict(json.load(f), **changes)
        with open(os.path.join(bench, "configs", name + ".json"), "w") as f:
            json.dump(cfg, f)

    config("gbdt-dense-63bin", "toy-gbdt", TOY_GBDT)
    config("gbdt-dense-63bin", "toy-gbdt-dp4", TOY_GBDT)
    config("gpt2-medium", "toy-gpt", TOY_GPT)
    with open(os.path.join(bench, "traffic", "toy-steps.json"), "w") as f:
        json.dump({"driver": "lm_train", "batch": 2, "seq": 64,
                   "warmup_steps": 3, "zipf_exponent": 1.0}, f)
    with open(os.path.join(bench, "metrics", "toy_step_s.json"), "w") as f:
        json.dump({"reader": "timer", "span": "lm_step"}, f)
    path = os.path.join(tmp, "BENCHMARK.json")
    shutil.copy(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "data", "toy_manifest.json"), path)
    return path


_CHILD = """
import json, sys, jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", {devices})
sys.path.insert(0, {bench!r})
import harness
line = harness.run_cell({manifest!r}, {cell!r}, {seed}, {seconds}, {trace},
                        bench_dir={bench!r}, require_tpu=False)
print(json.dumps(line))
"""


def run(manifest, cell, devices=1, seed=3, seconds=1.0, trace=0):
    """Run one toy cell in a process of its own, on `devices` virtual CPU
    devices with the histogram kernels interpreted; returns the process."""
    bench = os.path.join(os.path.dirname(manifest), "benchmark")
    env = dict(os.environ, JAX_PLATFORMS="cpu", MMLSPARK_TPU_HIST="pallas",
               MMLSPARK_TPU_HIST_INTERPRET="1",
               JAX_COMPILATION_CACHE_DIR=os.path.join(
                   os.path.dirname(manifest), "jax_cache"),
               PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""))
    code = _CHILD.format(devices=devices, bench=bench, manifest=manifest,
                         cell=cell, seed=seed, seconds=seconds, trace=trace)
    return subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                          capture_output=True, text=True, timeout=900)
