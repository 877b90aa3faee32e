"""work.py - the operations and bytes each cell's kernels need, from shapes.

The yardstick of the roofline and MFU metrics: kept with the benchmark so
that no PR that claims a gain can change what "the work" is. Every quantity
is what the algorithm needs, not what an implementation happens to do:
recomputed operations (remat, the flash backward's second QK^T) do not count.
"""


def hist_bytes_per_iter(cfg):
    """Bytes one boosting iteration's histograms must read from HBM: every
    level of the level-wise grower passes over all rows once, reading each
    row's F uint8 bins and its f32 gradient, f32 hessian and i32 node id
    (bench.py's `_hist_traffic_bytes`). The histograms written are KB."""
    return float(cfg["max_depth"]) * cfg["n_rows"] * (cfg["n_features"] + 12)


def lm_flops_per_token(cfg, seq):
    """Forward + backward matmul operations of one token in a sequence of
    `seq` tokens (bench.py `BENCH_MODE=lm` arithmetic): 2 per multiply-add,
    the four attention projections and two MLP matmuls of each layer, the
    tied vocabulary head, causal attention (QK^T and PV at half the square),
    backward = 2 x forward. Recompute excluded."""
    d, ff, layers = cfg["n_embd"], cfg["n_inner"], cfg["n_layer"]
    matmul_params = layers * (4 * d * d + 2 * d * ff)
    forward = 2 * matmul_params + 2 * d * cfg["vocab_size"] \
        + layers * 2 * seq * d
    return 3.0 * forward


def flash_flops_per_step(cfg, batch, seq):
    """Operations the attention kernels of one training step need: per
    layer and sequence, causal QK^T and PV forward (2 matmuls of S x S x d
    over the lower triangle, S*S*d operations each) and dV, dP, dQ, dK
    backward (4 more). The backward's recomputed QK^T is not counted."""
    per_matmul = float(seq) * seq * cfg["n_embd"]
    return 6.0 * per_matmul * cfg["n_layer"] * batch


def quantities(cfg, mix):
    """Every work quantity the configuration and mix define, by name, for
    the `derived` reader. A quantity whose sizes the files lack is left
    out."""
    out = {}
    if all(k in cfg for k in ("max_depth", "n_rows", "n_features")):
        out["hist_bytes_per_iter"] = hist_bytes_per_iter(cfg)
    if all(k in cfg for k in ("n_embd", "n_inner", "n_layer", "vocab_size")) \
            and "seq" in mix:
        out["lm_flops_per_token"] = lm_flops_per_token(cfg, mix["seq"])
        if "batch" in mix:
            out["flash_flops_per_step"] = flash_flops_per_step(
                cfg, mix["batch"], mix["seq"])
    return out
