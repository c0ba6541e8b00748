"""Operations and bytes a call needs, from its shapes alone. These are
what the ALGORITHM requires: recomputation counts nothing, a causal
mask halves attention's products. `roofline_s` is the least time a chip
with the given peaks could take.
"""
from __future__ import annotations


def matmul_params(cfg):
    """Parameters in matrices that every token multiplies: all layers
    and the head, without the embedding table (a lookup) and the norms."""
    h, m = cfg["hidden_size"], cfg["intermediate_size"]
    d = h // cfg["num_attention_heads"]
    nh, kvh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    layer = h * (nh + 2 * kvh) * d + nh * d * h + 3 * h * m
    return cfg["num_hidden_layers"] * layer + h * cfg["vocab_size"]


def total_params(cfg):
    h = cfg["hidden_size"]
    return (matmul_params(cfg) + cfg["vocab_size"] * h
            + (2 * cfg["num_hidden_layers"] + 1) * h)


def train_flops_per_token(cfg, seq):
    """Forward + backward of one token in a causal sequence of `seq`:
    6 per matrix parameter, and attention's two products (QK^T, PV)
    forward and four backward over the seq/2 positions a token sees on
    average: 2 * 2 * nh * d * seq/2 forward, three times that in all ->
    6 * L * nh*d * seq. (The PaLM convention, 12 * L * H * seq, counts the
    masked half too; it is not used: work the mask removes is not
    required work.)"""
    nh = cfg["num_attention_heads"]
    d = cfg["hidden_size"] // nh
    return (6 * matmul_params(cfg)
            + 6 * cfg["num_hidden_layers"] * nh * d * seq)


def flash_attention_train(cfg, batch, seq, itemsize=2):
    """(flops, bytes) of causal attention forward + backward for ONE
    layer: forward 2 products, backward 4 (recomputing QK^T in the
    backward is the kernel's choice and is not counted). Bytes: q, k, v,
    o read or written once forward; q, k, v, o, do read and dq, dk, dv
    written backward."""
    nh, kvh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg["hidden_size"] // nh
    product = 2 * batch * nh * seq * seq * d / 2        # one causal matmul
    q = batch * seq * nh * d * itemsize
    kv = batch * seq * kvh * d * itemsize
    fwd_bytes = 2 * q + 2 * kv
    bwd_bytes = 3 * q + 2 * kv + (q + 2 * kv)
    return 6 * product, fwd_bytes + bwd_bytes


def ragged_attention_call(cfg, seqs, itemsize=2):
    """(flops, bytes) of ONE layer's ragged paged attention call over
    `seqs` = [(q_len, kv_len), ...]: sequence i has q_len new rows, the
    last of which sees kv_len cached positions (causal within the
    chunk). Bytes: each sequence's K and V read once, q read and o
    written."""
    nh, kvh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg["hidden_size"] // nh
    flops = byts = 0
    for q_len, kv_len in seqs:
        seen = q_len * kv_len - q_len * (q_len - 1) / 2   # row r sees
        flops += 2 * 2 * nh * d * seen                    # kv_len-q_len+1+r
        byts += (2 * kv_len * kvh * d + 2 * q_len * nh * d) * itemsize
    return flops, byts


def roofline_s(flops, byts, peaks):
    """(least seconds, which bound) on a chip with `peaks`."""
    t_c = flops / peaks["bf16_flops_per_s"]
    t_m = byts / peaks["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
