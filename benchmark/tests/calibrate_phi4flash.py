#!/usr/bin/env python3
"""calibrate_phi4flash.py - both readings behind the limits of the
`lm_train_phi4flash` driver's correctness check (PERF.md section 4), at the
cell's own size (2 x 8192 tokens, the published widths), as
`calibrate_hybrid.py` reads them for the hybrid cell (whose `readings` this
uses): what the system's first timed step gives, and what the plain
reference gives when it is computed in bfloat16 throughout, each compared
with the float32 reference exactly as the driver compares. The second has
to come out as not correct by at least one limit, on every seed. Run by
hand on the chip:

    python3 benchmark/tests/calibrate_phi4flash.py [seed ...]

One JSON line a seed, in `calibrate_hybrid.py`'s form.
"""
import json
import os
import sys
import time
import types

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [os.path.dirname(BENCH), BENCH, HERE]


def main(seeds, config="phi-4-mini-flash-reasoning",
         traffic="steps-2x8192-zipf-slice25k"):
    import jax.numpy as jnp
    from calibrate_hybrid import readings
    from harness import load_json, load_module
    driver = load_module("drivers", "lm_train_phi4flash")
    helpers = driver.hybrid()
    # what `readings` asks of a driver: this one's limits, that one's sums
    limits = types.SimpleNamespace(
        relative_errors=helpers.relative_errors,
        adam_first_change=helpers.adam_first_change,
        change_error=helpers.change_error, GRAD_LIMIT=driver.GRAD_LIMIT,
        LOSS_BAND=driver.LOSS_BAND, CHANGE_LIMIT=driver.CHANGE_LIMIT)
    cfg = load_json(os.path.join(BENCH, "configs", config + ".json"))
    mix = load_json(os.path.join(BENCH, "traffic", traffic + ".json"))
    reference = load_module("reference", cfg["reference"])
    lr = cfg["assumed"]["lr"]
    for seed in seeds:
        t0 = time.perf_counter()
        trainer = driver.build_trainer(cfg, seed)
        tokens = helpers.zipf_stream(seed, cfg["vocab_size"],
                                     mix["zipf_exponent"], mix["batch"],
                                     mix["seq"])()
        t1 = time.perf_counter()
        loss32, g32 = driver.reference_readings(reference, trainer, cfg,
                                                tokens)
        t2 = time.perf_counter()
        loss16, g16 = driver.reference_readings(reference, trainer, cfg,
                                                tokens, dtype=jnp.bfloat16)
        t3 = time.perf_counter()
        loss, grads, change = driver.first_step_readings(trainer, tokens)
        t4 = time.perf_counter()
        print(json.dumps({
            "seed": seed, "loss_float32": loss32,
            "seconds": {"build": t1 - t0, "reference": t2 - t1,
                        "bfloat16_reference": t3 - t2, "step": t4 - t3},
            "system": readings(limits, loss, grads, change, loss32, g32, lr),
            "bfloat16_reference": readings(
                limits, loss16, g16, helpers.adam_first_change(g16, lr),
                loss32, g32, lr)}), flush=True)
        del trainer


if __name__ == "__main__":
    main([int(s) for s in sys.argv[1:]] or [3500000039])
