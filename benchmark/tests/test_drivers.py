"""Each driver end to end at a toy size on the CPU (histogram kernels
interpreted, `gbdt_fit` also on four virtual devices), from a toy tree that
ADDS its configurations, a mix, a metric and its cells to a copy of the
benchmark without editing a file of it; and the real command off-TPU."""
import json
import os
import subprocess

import pytest

import toy

RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


@pytest.fixture(scope="module")
def manifest(tmp_path_factory):
    return toy.build(str(tmp_path_factory.mktemp("toybench")))


def _line(proc):
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("cell,devices,metrics", [
    ("toy-resident", 1, {"gbdt_mrow_iters_per_s", "setup_s"}),
    ("toy-fit", 1, {"gbdt_fit_raw_s", "setup_s"}),
    ("toy-fit-dp4", 4, {"gbdt_fit_raw_s", "setup_s"}),
    ("toy-lm", 1, {"lm_tokens_per_s", "lm_step_p95_ms", "setup_s"}),
])
def test_driver_end_to_end(manifest, cell, devices, metrics):
    # a seed past 2**31, as the driver's are
    line = _line(toy.run(manifest, cell, devices, seed=2 ** 31 + 11,
                         seconds=3.0))
    assert RESULT_KEYS <= set(line)
    assert line["correct"], line["problems"]
    assert line["failed"] == 0 and line["attempted"] >= 3
    assert set(line["metrics"]) == metrics
    assert all(m["value"] > 0 and m["unit"]
               for m in line["metrics"].values())
    assert line["device"]["count"] == devices
    assert line["notes"]["compiles_in_window"] == 0


def test_same_seed_same_work(manifest):
    a = _line(toy.run(manifest, "toy-lm", seed=5, seconds=0.5))
    b = _line(toy.run(manifest, "toy-lm", seed=5, seconds=0.5))
    c = _line(toy.run(manifest, "toy-lm", seed=6, seconds=0.5))
    assert a["notes"]["loss_reference"] == b["notes"]["loss_reference"]
    assert a["notes"]["loss_reference"] != c["notes"]["loss_reference"]


def test_wrong_device_count_is_refused(manifest):
    proc = toy.run(manifest, "toy-fit-dp4", devices=1)
    assert proc.returncode != 0 and "asks for 4 chip" in proc.stderr


def test_traced_run_with_no_device_operation_is_refused(manifest):
    """On the CPU the profiler writes no device plane, so `--trace 1` cannot
    report: it fails and never prints a per-layer metric from the host."""
    proc = toy.run(manifest, "toy-lm", trace=1, seconds=0.5)
    assert proc.returncode != 0 and "no device plane" in proc.stderr


def test_real_command_exits_non_zero_off_tpu():
    repo = toy.REPO
    with open(os.path.join(repo, "BENCHMARK.json")) as f:
        command = json.load(f)["command"]
    proc = subprocess.run(
        command + ["--workload", "gpt2m-train-8x1024", "--seed", "1",
                   "--seconds", "1", "--trace", "0"],
        cwd=repo, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "not a TPU" in proc.stderr
    assert not proc.stdout.strip().endswith("}")


def test_per_layer_readers_on_recorded_trace():
    """The three readers and the derived arithmetic, fed the recorded LM
    trace and made-up facts: a metric whose inputs are missing is absent."""
    import harness
    import xplane
    data = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
    trace = xplane.Trace.from_file(os.path.join(data, "lm_toy.xplane.pb"))
    timer = harness.load_module("readers", "timer")
    derived = harness.load_module("readers", "derived")
    ctx = {"spans": {"lm_step": [0.2, 0.4, 0.3]},
           "program": {"data.fit_bins.seconds": 3.0,
                       "data.fit_bins.count": 2},
           "names": {"a": 6.0, "b": 2.0, "trace_busy_s": trace.busy_s()}}
    assert timer.read({"span": "lm_step"}, ctx) == 0.3
    assert timer.read({"span": "absent"}, ctx) is None
    assert timer.read({"timer": "data.fit_bins"}, ctx) == 1.5
    assert timer.read({"timer": "data.absent"}, ctx) is None
    assert derived.read({"expr": "100 * a / b - 1"}, ctx) == 299.0
    assert derived.read({"expr": "a / missing"}, ctx) is None
    with pytest.raises(ValueError):
        derived.read({"expr": "__import__('os')"}, ctx)
