"""Operations and bytes a `granitemoehybrid` train step REQUIRES, from the
configuration's shapes alone (forward and backward once; recomputation
is work the program chose). Used with `costs.roofline_s` and
`peaks.json` as they are.
"""
from __future__ import annotations


def sizes(cfg):
    kinds = cfg["layer_types"][:cfg["num_hidden_layers"]]
    nh = cfg["num_attention_heads"]
    return {
        "h": cfg["hidden_size"], "nh": nh,
        "kvh": cfg["num_key_value_heads"], "d": cfg["hidden_size"] // nh,
        "heads": cfg["mamba_n_heads"], "p": cfg["mamba_d_head"],
        "n": cfg["mamba_d_state"], "taps": cfg["mamba_d_conv"],
        "chunk": cfg["mamba_chunk_size"],
        "m": cfg["shared_intermediate_size"], "vocab": cfg["vocab_size"],
        "layers": len(kinds), "attention": kinds.count("attention"),
        "mamba": kinds.count("mamba")}


def matmul_params_per_token(cfg):
    """Matrix parameters one token multiplies in a step: the mixers'
    projections, every layer's MLP, and the head (the tied table, once:
    as the embedding it is a lookup)."""
    s = sizes(cfg)
    h, inner = s["h"], s["heads"] * s["p"]
    gqa = h * (s["nh"] + 2 * s["kvh"]) * s["d"] + s["nh"] * s["d"] * h
    mamba = h * (2 * inner + 2 * s["n"] + s["heads"]) + inner * h
    return (s["attention"] * gqa + s["mamba"] * mamba
            + s["layers"] * 3 * h * s["m"] + h * s["vocab"])


def ssd_core_per_token(cfg):
    """Forward operations of the chunked state-space operator for one
    token, all heads of ONE layer: C B^T inside a chunk, once for all
    heads (one group; the lower triangle: Q N), and a head the masked
    product (L o C B^T)(dt x) (lower triangle: Q P), the chunk's state
    B^T (w dt x) and the state's output C S_0 (2 N P each)."""
    s = sizes(cfg)
    q, n, p = s["chunk"], s["n"], s["p"]
    return q * n + s["heads"] * (q * p + 4 * n * p)


def train_flops_per_token(cfg, seq):
    """Forward + backward of one token in a causal sequence of `seq`: 6
    per matrix parameter it multiplies, the softmax layers' two products
    over the seq/2 positions it sees on average (6 nh d seq a layer, as
    `costs.train_flops_per_token` counts them), and three times the
    state-space operator's forward."""
    s = sizes(cfg)
    return (6 * matmul_params_per_token(cfg)
            + 6 * s["attention"] * s["nh"] * s["d"] * seq
            + 3 * s["mamba"] * ssd_core_per_token(cfg))


def ssd_core_train(cfg, batch, seq):
    """(flops, bytes) of ONE layer's state-space operator, forward +
    backward. Bytes: forward reads x (2 bytes), B, C and dt (float32)
    and writes y; backward reads them and dy again and writes dx, dB, dC
    and d dt. The state lives on chip between chunks, dt's running sum
    can be made there, D is a head's scalar: none is counted."""
    s = sizes(cfg)
    t = batch * seq
    wide = t * s["heads"] * s["p"]
    byts = 5 * wide * 2 + 6 * t * s["n"] * 2 + 3 * t * s["heads"] * 4
    return 3 * t * ssd_core_per_token(cfg), byts


def ssm_conv_train(cfg, batch, seq):
    """(flops, bytes) of ONE layer's short convolution + bias + SiLU over
    the x | B | C channels, forward + backward: a multiply-add a tap, the
    bias, and ~4 for the logistic forward; about twice that backward
    (y again, its derivative, dx's taps, dw's sums). Bytes: forward
    reads the projection and writes the activation; backward reads the
    projection and the cotangent and writes its own."""
    s = sizes(cfg)
    cells = batch * seq * (s["heads"] * s["p"] + 2 * s["n"])
    return cells * 3 * (2 * s["taps"] + 5), 5 * cells * 2
