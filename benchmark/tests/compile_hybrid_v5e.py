#!/usr/bin/env python3
"""compile_hybrid_v5e.py - compile the hybrid cell's step program at the
cell's shapes for a DESCRIBED v5e, here, without the chip: what the chip's
compiler refuses (a kernel's VMEM, the program's HBM) it refuses at no chip
time. Run by hand from the root of the checkout:

    JAX_PLATFORMS=cpu python3 benchmark/tests/compile_hybrid_v5e.py
    MICRO=2 HELD=16 ... (microbatches, experts held; B, S the batch)

It prints the compiler's memory analysis (`peak_memory_in_bytes` is what
the harness reports as `memory_peak_bytes`). PR 28 read 16.61 GB at one
microbatch and 15.69 GB at two, of the 16.91 GB (15.75 GiB) a program gets.

`PipelinedLMTrainer` builds its mesh from real devices and places real
parameters, so this script stands in for both while the trainer is built:
shapes for arrays, the described device for `jax.devices()`. Nothing runs.
It is a scratch tool, not a test: only one process at a time can load the
TPU's library, which is why it is not collected by pytest.
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
    import mmlspark_tpu.models.dnn.hybrid_layers as layers
    import mmlspark_tpu.models.dnn.pp_training as pp
    from mmlspark_tpu.models.dnn.lm_spec import qwen3_next_spec
    from mmlspark_tpu.parallel import DATA_AXIS, PIPE_AXIS

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    mesh = Mesh(np.array([topo.devices[0]]).reshape(1, 1),
                (DATA_AXIS, PIPE_AXIS))
    with open(os.path.join(BENCH, "configs", "qwen3-next-80b-a3b.json")) as f:
        cfg = json.load(f)
    held = int(os.environ.get("HELD", cfg["num_experts"]))
    micro = int(os.environ.get("MICRO", cfg["trainer"]["n_microbatches"]))
    batch, seq = int(os.environ.get("B", 2)), int(os.environ.get("S", 8192))
    spec = qwen3_next_spec(cfg, (0, held),
                           n_experts=cfg["published"]["num_experts"])

    real_init = layers.init_hybrid

    def abstract_init(_spec, seed):
        """The parameter tree's shapes, from a tiny model of the same
        widths grown to size with zero-stride arrays."""
        tiny = qwen3_next_spec({**cfg, "vocab_size": 8}, (0, 1),
                               n_experts=cfg["published"]["num_experts"])

        def grow(path, a):
            shape = list(a.shape)
            if path[-1].key in ("w_gate", "w_up", "w_down"):
                shape[1] = held
            if path[-1].key in ("embed", "head"):
                shape[0] = cfg["vocab_size"]
            return np.broadcast_to(np.zeros((), np.float32), shape)

        return jax.tree_util.tree_map_with_path(grow, real_init(tiny, seed))

    class ShapeOnlyAdam:
        def __init__(self, lr):
            self.inner = real_adam(lr)

        def init(self, params):
            return jax.eval_shape(self.inner.init, params)

        def update(self, *args, **kwargs):
            return self.inner.update(*args, **kwargs)

    real_put, real_asarray, real_adam = jax.device_put, jnp.asarray, optax.adam
    real_devices = jax.devices
    layers.init_hybrid = abstract_init
    jax.device_put = lambda a, s=None: jax.ShapeDtypeStruct(
        np.shape(a), getattr(a, "dtype", np.float32), sharding=s)
    jnp.asarray = lambda a, *args, **kwargs: a if isinstance(
        a, (np.ndarray, jax.ShapeDtypeStruct)) and not args and not kwargs \
        else real_asarray(a, *args, **kwargs)
    optax.adam = ShapeOnlyAdam
    jax.devices = lambda *args: topo.devices   # flash: not interpreted
    try:
        opts = cfg["trainer"]
        trainer = pp.PipelinedLMTrainer(
            model=spec, mesh=mesh, n_microbatches=micro,
            lr=cfg["assumed"]["lr"], attention=opts["attention"],
            compute_dtype=opts["compute_dtype"], remat=opts["remat"])
    finally:
        jax.device_put, jnp.asarray = real_put, real_asarray
        optax.adam, layers.init_hybrid = real_adam, real_init
    replicated = NamedSharding(mesh, P())
    opt_state = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=replicated),
        trainer.opt_state)
    tokens = jax.ShapeDtypeStruct((batch, seq), jnp.int32,
                                  sharding=trainer._batch_sharding)
    n_params = sum(int(np.prod(a.shape))
                   for a in jax.tree_util.tree_leaves(trainer.params))
    print(f"parameters {n_params}; experts held {held}; microbatches "
          f"{micro}; tokens {batch} x {seq}", flush=True)
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
