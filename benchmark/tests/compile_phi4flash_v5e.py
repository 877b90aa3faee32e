#!/usr/bin/env python3
"""compile_phi4flash_v5e.py - compile the phi4flash cell's step program at
the cell's shapes for a DESCRIBED v5e, here, without the chip, as
`compile_hybrid_v5e.py` does for the hybrid cell (why the stand-ins, and
why it is a scratch tool and no test: there). Run by hand from the root of
the checkout:

    JAX_PLATFORMS=cpu python3 benchmark/tests/compile_phi4flash_v5e.py
    MICRO=1 FIRST=15 B=2 S=8192 ... ; DUMP=<file> writes the optimized HLO

It prints the compiler's memory analysis (`peak_memory_in_bytes` is what
the harness reports as `memory_peak_bytes`): the number that decided
between six held layers (14 to 19) and the five-layer fall-back (FIRST=15).
"""
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ["JAX_PLATFORMS"] = "cpu"
HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, os.path.dirname(BENCH))


def main():
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    jax.config.update("jax_enable_compilation_cache", False)
    import mmlspark_tpu.models.dnn.pp_training as pp
    import mmlspark_tpu.models.dnn.ssm_layers as layers
    from mmlspark_tpu.models.dnn.lm_spec import phi4flash_spec
    from mmlspark_tpu.parallel import DATA_AXIS, PIPE_AXIS

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    mesh = Mesh(np.array([topo.devices[0]]).reshape(1, 1),
                (DATA_AXIS, PIPE_AXIS))
    with open(os.path.join(BENCH, "configs",
                           "phi-4-mini-flash-reasoning.json")) as f:
        cfg = json.load(f)
    cfg["held_layers"] = [int(os.environ.get("FIRST", cfg["held_layers"][0])),
                          cfg["held_layers"][1]]
    micro = int(os.environ.get("MICRO", cfg["trainer"]["n_microbatches"]))
    batch, seq = int(os.environ.get("B", 2)), int(os.environ.get("S", 8192))
    spec = phi4flash_spec(cfg)

    real_init, real_rng = layers.init, np.random.default_rng

    class Shapes:
        """numpy's generator with every draw a zero-stride view."""
        def standard_normal(self, shape, dtype=np.float32):
            return np.broadcast_to(np.zeros((), dtype), shape)

        def uniform(self, lo, hi, shape):
            return np.broadcast_to(np.float64(0.05), shape)

    def abstract_init(spec_, seed):
        """The parameter tree's shapes: `init` on draws that hold no
        memory, each leaf then a zero-stride array."""
        np.random.default_rng = lambda seed: Shapes()
        try:
            tree = real_init(spec_, seed)
        finally:
            np.random.default_rng = real_rng
        return jax.tree_util.tree_map(
            lambda a: np.broadcast_to(np.zeros((), np.float32), a.shape),
            tree)

    class ShapeOnlyAdam:
        def __init__(self, lr):
            self.inner = real_adam(lr)

        def init(self, params):
            return jax.eval_shape(self.inner.init, params)

        def update(self, *args, **kwargs):
            return self.inner.update(*args, **kwargs)

    real_put, real_asarray, real_adam = jax.device_put, jnp.asarray, optax.adam
    real_devices = jax.devices
    layers.init = abstract_init
    jax.device_put = lambda a, s=None: jax.ShapeDtypeStruct(
        np.shape(a), getattr(a, "dtype", np.float32), sharding=s)
    jnp.asarray = lambda a, *args, **kwargs: a if isinstance(
        a, (np.ndarray, jax.ShapeDtypeStruct)) and not args and not kwargs \
        else real_asarray(a, *args, **kwargs)
    optax.adam = ShapeOnlyAdam
    jax.devices = lambda *args: topo.devices   # kernels: not interpreted
    try:
        opts = cfg["trainer"]
        trainer = pp.PipelinedLMTrainer(
            model=spec, mesh=mesh, n_microbatches=micro,
            lr=cfg["assumed"]["lr"], attention=opts["attention"],
            compute_dtype=opts["compute_dtype"], remat=opts["remat"])
    finally:
        jax.device_put, jnp.asarray = real_put, real_asarray
        optax.adam, layers.init = real_adam, real_init
    replicated = NamedSharding(mesh, P())
    opt_state = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=replicated),
        trainer.opt_state)
    tokens = jax.ShapeDtypeStruct((batch, seq), jnp.int32,
                                  sharding=trainer._batch_sharding)
    n_params = sum(int(np.prod(a.shape))
                   for a in jax.tree_util.tree_leaves(trainer.params))
    print(f"parameters {n_params}; layers held {cfg['held_layers']}; "
          f"microbatches {micro}; tokens {batch} x {seq}", flush=True)
    t0 = time.time()
    try:
        compiled = trainer._step.lower(trainer.params, opt_state,
                                       tokens).compile()
    finally:
        jax.devices = real_devices
    analysis = compiled.memory_analysis()
    print(f"compiled in {time.time() - t0:.1f} s")
    print(analysis)
    print("peak_memory_in_bytes",
          getattr(analysis, "peak_memory_in_bytes", None))
    if os.environ.get("DUMP"):
        with open(os.environ["DUMP"], "w") as f:
            f.write(compiled.as_text())


if __name__ == "__main__":
    main()
