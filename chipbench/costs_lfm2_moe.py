"""Operations and bytes an `lfm2_moe` train step REQUIRES, from the
configuration's shapes alone (forward and backward once; recomputation is
work the program chose). Used with `costs.roofline_s` and `peaks.json` as
they are.
"""
from __future__ import annotations


def sizes(cfg):
    kinds = cfg["layer_types"][:cfg["num_hidden_layers"]]
    nh = cfg["num_attention_heads"]
    layers = len(kinds)
    dense = min(cfg["num_dense_layers"], layers)
    return {
        "h": cfg["hidden_size"], "nh": nh, "kvh": cfg["num_key_value_heads"],
        "d": cfg["hidden_size"] // nh, "taps": cfg["conv_L_cache"],
        "layers": layers, "conv": kinds.count("conv"),
        "attention": kinds.count("full_attention"), "dense": dense,
        "expert": layers - dense, "m_dense": cfg["intermediate_size"],
        "m": cfg["moe_intermediate_size"], "held": cfg["num_experts"],
        "total": cfg.get("reduced_from", {}).get("num_experts",
                                                 cfg["num_experts"]),
        "k": cfg["num_experts_per_tok"],
        "vocab": cfg.get("vocab_rows", cfg["vocab_size"])}


def matmul_params_per_token(cfg):
    """Matrix parameters one token multiplies in a step: a conv mixer's two
    projections ([H, 3H] and [H, H]), an attention mixer's four, the router
    whole and of the routed experts the share a uniform router sends here
    (top_k * held / total experts a token, each 3 H M; no shared expert),
    the leading dense layers' MLP, and the head (the tied table, once: as
    the embedding it is a lookup)."""
    s = sizes(cfg)
    h = s["h"]
    gqa = h * (s["nh"] + 2 * s["kvh"]) * s["d"] + s["nh"] * s["d"] * h
    moe = h * s["total"] + 3 * h * s["m"] * s["k"] * s["held"] / s["total"]
    return (s["conv"] * 4 * h * h + s["attention"] * gqa + s["expert"] * moe
            + s["dense"] * 3 * h * s["m_dense"] + h * s["vocab"])


def train_flops_per_token(cfg, seq):
    """Forward + backward of one token in a causal sequence of `seq`: 6
    per matrix parameter it multiplies and the attention layers' two
    products over the seq/2 positions it sees on average (6 nh d seq a
    layer, as `costs.train_flops_per_token` counts them). The short
    convolution's few operations a channel are not counted: it is bound by
    its bytes (`short_conv_train`)."""
    s = sizes(cfg)
    return (6 * matmul_params_per_token(cfg)
            + 6 * s["attention"] * s["nh"] * s["d"] * seq)


def moe_experts_train(cfg, pairs):
    """(flops, bytes) of the routed experts' grouped products for `pairs`
    (token, expert) pairs computed here, ONE layer, forward + backward,
    counted as `costs_solar_open2.moe_experts_train` counts them: three
    [pairs, H] x [H, M] products forward, twice that backward -> 18 pairs
    H M. Bytes: the held experts' weights read forward and backward and
    their gradient written, the rows read forward, rows and their
    gradients read and written backward (2 bytes each)."""
    s = sizes(cfg)
    h, m = s["h"], s["m"]
    return 18 * pairs * h * m, (3 * s["held"] * 3 * h * m + 5 * pairs * h) * 2


def short_conv_train(cfg, batch, seq, itemsize=2):
    """(flops, bytes) of ONE layer's gated short convolution, forward +
    backward, over T = batch * seq tokens of H channels. Bytes, what the
    operator HAS to move: forward `bcx` [T, 3H] read and y [T, H] written;
    backward `bcx` and dy read and d`bcx` written: 11 T H elements. The
    forward made again in the backward is time the program chose, not
    work. Operations: forward B * X, a multiply-add a tap, C *: 2 taps + 2
    a channel; backward the taps' sum again, dC, dv, du's taps, dB, dX and
    dw's sums: 6 taps + 4."""
    s = sizes(cfg)
    cells = batch * seq * s["h"]
    return cells * (8 * s["taps"] + 6), 11 * cells * itemsize
