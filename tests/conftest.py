"""Shared test session: 8 virtual CPU devices as the fake cluster.

The reference's key testing idea (SURVEY.md §4): no real cluster anywhere —
local[*] with partition-as-node exercises real distributed code paths. Here the
equivalent is an 8-device virtual CPU mesh: every psum/all_gather/shard_map runs
the real collective lowering, just on one host.
"""
import os

# Tests run on the CPU whatever the machine holds: select it before the
# backend initializes.
os.environ["JAX_PLATFORMS"] = "cpu"
# XLA CPU aborts the PROCESS (LOG(FATAL) in rendezvous.cc) when the 8
# per-device threads of a collective don't all reach the rendezvous within
# 40 s — on a 1-core CI host, thread starvation under suite load trips
# that constantly (observed: "Expected 8 threads to join the rendezvous,
# but only 6 of them arrived on time"). Starvation is not deadlock: raise
# the termination timeout so slow scheduling finishes instead of killing
# the run. Must be in XLA_FLAGS before the backend initializes. (XLA_FLAGS
# are hashed into every persistent-cache key: changing this string makes
# the whole suite's compile cache cold.)
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_cpu_collective_call_terminate_timeout_seconds=1200"
    + " --xla_cpu_collective_call_warn_stuck_timeout_seconds=120")
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)
# persistent compile cache: the suite compiles thousands of XLA programs in
# one process; re-runs load them from disk instead (also sidesteps a
# rare LLVM crash observed when the same program recompiles late in a
# long suite process). The helper is loaded by PATH so the package
# __init__ doesn't run before the backend config above is set — and by
# THIS path spelling, which the cache keys depend on (see hostcache.py).
import importlib.util as _ilu  # noqa: E402

_spec = _ilu.spec_from_file_location(
    "_hostcache", os.path.join(os.path.dirname(__file__), "..",
                               "mmlspark_tpu", "utils", "hostcache.py"))
_hostcache = _ilu.module_from_spec(_spec)
_spec.loader.exec_module(_hostcache)
_hostcache.enable_compile_cache()

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(autouse=True, scope="module")
def _clear_jax_caches_between_modules():
    """Bound in-process compile-cache growth across the suite (hundreds of
    jitted programs otherwise accumulate in one process)."""
    yield
    jax.clear_caches()


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(42)


@pytest.fixture(scope="session")
def binary_table():
    """Synthetic linearly-separable-ish binary classification table."""
    from mmlspark_tpu import Table
    rng = np.random.default_rng(0)
    n = 2000
    x = rng.normal(size=(n, 10)).astype(np.float32)
    w = rng.normal(size=10)
    logits = x @ w + 0.5 * np.sin(3 * x[:, 0]) * x[:, 1]
    y = (logits + rng.normal(scale=0.5, size=n) > 0).astype(np.float32)
    return Table({"features": x, "label": y}, npartitions=4)


@pytest.fixture(scope="session")
def regression_table():
    from mmlspark_tpu import Table
    rng = np.random.default_rng(1)
    n = 2000
    x = rng.normal(size=(n, 8)).astype(np.float32)
    y = (x[:, 0] * 2 - x[:, 1] + 0.5 * x[:, 2] * x[:, 3]
         + rng.normal(scale=0.1, size=n)).astype(np.float32)
    return Table({"features": x, "label": y}, npartitions=4)


@pytest.fixture
def bench_rounds(tmp_path):
    """Five driver-format headline rounds (`{"n", "parsed", "tail"}`
    wrappers around a GBDT headline record, all measured on a TPU) plus a
    builder-format extras file from a CPU run. The shape telemetry.benchdiff
    reads; the values are fixtures, with an hbm_utilization dip from round 4
    to round 5 so that a 10% gate fires."""
    import json
    rows = [(6.6e6, None), (5.9e7, None), (6.9e7, 0.0217), (6.8e7, 0.0227),
            (8.8e7, 0.0176)]
    files = []
    for n, (value, hbm) in enumerate(rows, start=1):
        rec = {"metric": "gbdt_train_rows_iters_per_sec", "value": value,
               "unit": "rows*iters/s", "vs_baseline": value / 2e7}
        if hbm is not None:
            rec.update(shape="8000000x32x64bins x20it", hbm_utilization=hbm)
        path = tmp_path / f"BENCH_r0{n}.json"
        path.write_text(json.dumps({"n": n, "parsed": rec, "tail": ""}))
        files.append(str(path))
    extra = tmp_path / "BENCH_EXTRA_r06.json"
    extra.write_text(json.dumps({
        "backend": "cpu",
        "gbdt_train_headline_8m_32f": {
            "metric": "gbdt_train_rows_iters_per_sec", "value": 48931.4,
            "backend": "cpu", "shape": "20000x32x64bins x2it",
            "vs_baseline": 0.0024, "hbm_utilization": 0.0025}}))
    return files, str(extra)

