"""Operations and bytes a `solar_open2` train step REQUIRES, from the
configuration's shapes alone (forward and backward once; recomputation
is work the program chose). Used with `costs.roofline_s` and
`peaks.json` as they are.
"""
from __future__ import annotations


def sizes(cfg):
    lin = cfg["linear_attn_config"]
    total = cfg.get("reduced_from", {}).get("n_routed_experts",
                                            cfg["n_routed_experts"])
    n_gqa = sum(1 for i in range(cfg["num_hidden_layers"])
                if i in cfg["gqa_layers"])
    return {
        "h": cfg["hidden_size"], "nh": cfg["num_attention_heads"],
        "kvh": cfg["num_key_value_heads"], "d": cfg["head_dim"],
        "nl": lin["num_heads"], "dl": lin["head_dim"],
        "r": cfg.get("kda_low_rank", lin["head_dim"]),
        "taps": lin["short_conv_kernel_size"],
        "m": cfg["moe_intermediate_size"], "held": cfg["n_routed_experts"],
        "total": total, "k": cfg["num_experts_per_tok"],
        "vocab": cfg.get("vocab_rows", cfg["vocab_size"]),
        "layers": cfg["num_hidden_layers"], "gqa": n_gqa,
        "kda": cfg["num_hidden_layers"] - n_gqa}


def matmul_params_per_token(cfg):
    """Matrix parameters one token multiplies in a step, all layers and
    the head: the mixers whole, router and shared expert whole, and of
    the routed experts the share a uniform router sends here
    (top_k * held / total experts a token, each 3 H M)."""
    s = sizes(cfg)
    h = s["h"]
    gqa = h * (s["nh"] + 2 * s["kvh"]) * s["d"] + 2 * h * s["nh"] * s["d"]
    wide = s["nl"] * s["dl"]
    kda = (h * 3 * wide + 2 * (h * s["r"] + s["r"] * wide) + h * s["nl"]
           + wide * h)
    expert = 3 * h * s["m"]
    moe = h * s["total"] + expert * (1 + s["k"] * s["held"] / s["total"])
    return (s["gqa"] * gqa + s["kda"] * kda + s["layers"] * moe
            + h * s["vocab"])


def kda_core_per_token(cfg, chunk=64):
    """Forward operations of the chunked gated delta rule for one token,
    all heads of ONE layer: the two decayed pair products inside a chunk
    (lower triangles: C dk each), the unit-triangular solve for
    [U~ | W] (C (dk + dv)), P U (C dv), and the three products with the
    state, W S, k^T U and q S (2 dk dv each)."""
    s = sizes(cfg)
    dk = dv = s["dl"]
    per_head = chunk * (3 * dk + 2 * dv) + 6 * dk * dv
    return s["nl"] * per_head


def train_flops_per_token(cfg, seq):
    """Forward + backward of one token in a causal sequence of `seq`: 6
    per matrix parameter it multiplies, the softmax layers' two products
    over the seq/2 positions it sees on average (6 nh d seq a layer, as
    `costs.train_flops_per_token` counts them), and three times the
    linear-attention core's forward."""
    s = sizes(cfg)
    return (6 * matmul_params_per_token(cfg)
            + 6 * s["gqa"] * s["nh"] * s["d"] * seq
            + 3 * s["kda"] * kda_core_per_token(cfg))


def kda_core_train(cfg, batch, seq, chunk=64):
    """(flops, bytes) of ONE layer's gated delta-rule core, forward +
    backward. Bytes: forward reads q, k, v (2 bytes) and the log-decay
    (float32) and writes o; backward reads them and do again and writes
    dq, dk, dv and the decay's gradient; beta is a head's scalar and not
    counted. The state lives on chip between chunks and is not counted."""
    s = sizes(cfg)
    t = batch * seq
    cells = t * s["nl"] * s["dl"]
    return 3 * t * kda_core_per_token(cfg, chunk), (12 + 22) * cells


def moe_experts_train(cfg, pairs):
    """(flops, bytes) of the routed experts' grouped products for `pairs`
    (token, expert) pairs computed here, ONE layer, forward + backward:
    three [pairs, H] x [H, M] products forward, twice that backward ->
    18 pairs H M. Bytes: the held experts' weights read forward and
    backward and their gradient written, the rows read forward, rows and
    their gradients read and written backward."""
    s = sizes(cfg)
    h, m = s["h"], s["m"]
    flops = 18 * pairs * h * m
    byts = (3 * s["held"] * 3 * h * m + 5 * pairs * h) * 2
    return flops, byts
