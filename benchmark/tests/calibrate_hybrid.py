#!/usr/bin/env python3
"""calibrate_hybrid.py - both readings behind the limits of the
`lm_train_hybrid` driver's correctness check (PERF.md section 4), at the
cell's own size (2 x 8192 tokens, the published widths): what the system's
first timed step gives, and what the plain reference gives when it is
computed in bfloat16 throughout, the nearest precision below the cell's
(bfloat16 operands, float32 accumulation, norms, softmax and state), each
compared with the float32 reference exactly as the driver compares. The
second has to come out as not correct by at least one limit. Run by hand on
the chip:

    python3 benchmark/tests/calibrate_hybrid.py [seed ...]

One JSON line a seed: under `system` and `bfloat16_reference` the loss
difference beside the driver's band, each compared leaf's relative error
beside its limit, the parameters' change beside its limit (and leaf by
leaf), and `fails`, the limits it breaks.
"""
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [os.path.dirname(BENCH), BENCH]


def readings(driver, loss, grads, change, ref_loss, ref_grads, lr):
    errors = driver.relative_errors(grads, ref_grads)
    ref_change = driver.adam_first_change(ref_grads, lr)
    apart = driver.change_error(change, ref_change)
    fails = [k for k, e in errors.items() if not e <= driver.GRAD_LIMIT[k]]
    if not abs(loss - ref_loss) <= driver.LOSS_BAND:
        fails.append("loss")
    if not apart <= driver.CHANGE_LIMIT:
        fails.append("param_change")
    return {"loss": loss,
            "loss_difference": [abs(loss - ref_loss), driver.LOSS_BAND],
            "grad_rel_error": {k: [errors[k], driver.GRAD_LIMIT[k]]
                               for k in sorted(errors)},
            "param_change_error": [apart, driver.CHANGE_LIMIT],
            "param_change_error_by_leaf": {
                k: driver.change_error({k: change[k]}, {k: ref_change[k]})
                for k in sorted(change)},
            "fails": sorted(fails)}


def main(seeds, config="qwen3-next-80b-a3b",
         traffic="steps-2x8192-zipf-slice"):
    import time

    import jax.numpy as jnp
    from harness import load_json, load_module
    driver = load_module("drivers", "lm_train_hybrid")
    cfg = load_json(os.path.join(BENCH, "configs", config + ".json"))
    mix = load_json(os.path.join(BENCH, "traffic", traffic + ".json"))
    reference = load_module("reference", cfg["reference"])
    lr = cfg["assumed"]["lr"]
    for seed in seeds:
        t0 = time.perf_counter()
        trainer = driver.build_trainer(cfg, seed)
        tokens = driver.zipf_stream(seed, cfg["vocab_size"],
                                    mix["zipf_exponent"], mix["batch"],
                                    mix["seq"])()
        driver.place_experts(reference, trainer, cfg, tokens)
        t1 = time.perf_counter()
        loss32, g32 = driver.reference_readings(reference, trainer, cfg,
                                                tokens)
        t2 = time.perf_counter()
        loss16, g16 = driver.reference_readings(reference, trainer, cfg,
                                                tokens, dtype=jnp.bfloat16)
        t3 = time.perf_counter()
        loss, grads, change = driver.first_step_readings(
            trainer, lambda t: (trainer.step(t),), tokens)
        t4 = time.perf_counter()
        print(json.dumps({
            "seed": seed, "loss_float32": loss32,
            "seconds": {"build": t1 - t0, "reference": t2 - t1,
                        "bfloat16_reference": t3 - t2, "step": t4 - t3},
            "system": readings(driver, loss, grads, change, loss32, g32, lr),
            "bfloat16_reference": readings(
                driver, loss16, g16, driver.adam_first_change(g16, lr),
                loss32, g32, lr)}), flush=True)
        del trainer


if __name__ == "__main__":
    main([int(s) for s in sys.argv[1:]] or [3100000037])
