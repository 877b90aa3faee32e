"""work_phi4_flash.py - the operations and bytes the
`phi-4-mini-flash-reasoning` cell needs, from the configuration's widths
and the layer equations of `reference/phi4_flash.py`. `work.py` is for the
accepted cells and is not edited; the driver hands these to the `derived`
reader as facts.

As in `work.py`, every quantity is what the algorithm needs, not what an
implementation does: nothing run again for remat, no masked-out pair, K and
V not repeated for their query heads, the flash backward's second QK^T and
dO V not counted. 2 operations per multiply-add; backward = 2 x forward.
A share computed from these cannot pass 100% unless the time leaves work
out.
"""


def kind_of(index, cfg):
    n, per = cfg["num_hidden_layers"], cfg["mb_per_layer"]
    ssm = index % per == 0
    if index < n // 2:
        return "mamba" if ssm else "window_attention"
    if index < n // 2 + per:
        return "memory_mamba" if ssm else "kv_attention"
    return "gmu" if ssm else "cross_attention"


def layers_held(cfg):
    first, last = cfg.get("held_layers", (0, cfg["num_hidden_layers"] - 1))
    return [kind_of(i, cfg) for i in range(first, last + 1)]


def count(cfg, *kinds):
    return sum(kind in kinds for kind in layers_held(cfg))


def sizes(cfg):
    """(d, ff, heads, kv heads, head size, channels, states, dt rank,
    taps)."""
    def size(key, default):
        return cfg.get(key, cfg.get("assumed", {}).get(key, default))
    d = cfg["hidden_size"]
    return (d, cfg["intermediate_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], d // cfg["num_attention_heads"],
            size("mamba_expand", 2) * d, size("mamba_d_state", 16),
            size("mamba_dt_rank", -(-d // 16)), size("mamba_d_conv", 4))


def visible_pairs(seq, window=None):
    """(query, key) pairs a causal mask leaves, under a window that counts
    the query's own position."""
    if window is None or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def mixer_macs_per_token(cfg, kind, seq):
    """Forward multiply-adds of one token through one layer's mixer."""
    d, _, h, kv, hd, di, n, rank, taps = sizes(cfg)
    if kind in ("mamba", "memory_mamba"):
        # W_in, the taps, W_x, W_dt, the scan (an exp and three
        # multiply-adds a state, counted as 4), the gate, W_out
        return d * 2 * di + taps * di + di * (rank + 2 * n) + rank * di \
            + 4 * di * n + di + di * d
    if kind == "gmu":
        return 2 * d * di + di
    window = cfg["sliding_window"] if kind == "window_attention" else None
    # a pair and query head: q.k at the head size, P.V at twice it
    pairs = visible_pairs(seq, window) / seq * h * 3 * hd
    out = h * hd * d
    if kind == "cross_attention":
        return d * h * hd + out + pairs
    return d * (h + 2 * kv) * hd + out + pairs


def lm_flops_per_token(cfg, seq):
    """Forward + backward operations of one token of a sequence of `seq`
    tokens through the layers and the vocabulary held here."""
    d, ff = cfg["hidden_size"], cfg["intermediate_size"]
    kinds = layers_held(cfg)
    macs = sum(mixer_macs_per_token(cfg, kind, seq) for kind in kinds) \
        + len(kinds) * 3 * d * ff + d * cfg["vocab_size"]
    return 3.0 * 2.0 * macs


def flash_ops_per_pair(cfg):
    """Six matmuls a visible pair and query head: q.k, dS.k, dS.q at the
    head size; P.V, dO.V, P.dO at twice it (the pair's V)."""
    hd = sizes(cfg)[4]
    return 2.0 * (3 * hd + 3 * 2 * hd)


def flash_window_flops_per_step(cfg, batch, seq):
    return visible_pairs(seq, cfg["sliding_window"]) \
        * cfg["num_attention_heads"] * flash_ops_per_pair(cfg) * batch \
        * count(cfg, "window_attention")


def flash_diff_flops_per_step(cfg, batch, seq):
    return visible_pairs(seq) * cfg["num_attention_heads"] \
        * flash_ops_per_pair(cfg) * batch \
        * count(cfg, "kv_attention", "cross_attention")


def ssm_scan_bytes_per_step(cfg, batch, seq):
    """What the scan must move, bfloat16: forward reads v, dt, B, C and
    writes y; backward reads v, dt, B, C, dy and writes dv, ddt, dB, dC."""
    di, n = sizes(cfg)[5], sizes(cfg)[6]
    per_token = 2.0 * ((3 * di + 2 * n) + (5 * di + 4 * n))
    return per_token * batch * seq * count(cfg, "mamba", "memory_mamba")


def parameter_count(cfg):
    """The parameters held here: layers, norms, the embedding slice (tied:
    counted once)."""
    d, ff, h, kv, hd, di, n, rank, taps = sizes(cfg)
    mamba = (d * 2 * di + taps * di + di + di * (rank + 2 * n) + rank * di
             + di + di * n + di + di * d)
    tail = h * hd * d + d + 4 * hd + 2 * hd
    attention = d * (h + 2 * kv) * hd + (h + 2 * kv) * hd + tail
    mixer = {"mamba": mamba, "memory_mamba": mamba,
             "window_attention": attention, "kv_attention": attention,
             "gmu": 2 * d * di, "cross_attention": d * h * hd + h * hd + tail}
    return sum(mixer[kind] + 3 * d * ff + 4 * d
               for kind in layers_held(cfg)) + 2 * d + cfg["vocab_size"] * d
