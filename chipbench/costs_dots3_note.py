"""Operations and bytes a `dots3_note` train step REQUIRES, from the
configuration's shapes alone (forward and backward once; recomputation,
and pairs a kernel visits but the selection or the window leaves out,
are work the program chose). Used with `costs.roofline_s` and
`peaks.json` as they are.
"""
from __future__ import annotations

FULL, SLIDING = "full_attention", "sliding_attention"


def sizes(cfg):
    first = cfg.get("layer_offset", 0)
    kinds = cfg["layer_types"][first:first + cfg["num_hidden_layers"]]
    total = cfg.get("reduced_from", {}).get("n_routed_experts",
                                            cfg["n_routed_experts"])
    dense = min(cfg["first_k_dense_replace"], len(kinds))
    return {
        "h": cfg["hidden_size"], "layers": len(kinds),
        "full": kinds.count(FULL), "sliding": kinds.count(SLIDING),
        "dense": dense, "expert": len(kinds) - dense,
        "m_dense": cfg["intermediate_size"],
        "m": cfg["moe_intermediate_size"], "held": cfg["n_routed_experts"],
        "total": total, "k": cfg["num_experts_per_tok"],
        "vocab": cfg.get("vocab_rows", cfg["vocab_size"]),
        "J": cfg["index_n_heads"], "Di": cfg["index_head_dim"],
        "top": cfg["index_topk"], "window": cfg["sliding_window_size"]}


def heads(cfg, kind):
    """(n, d_n, d_r, d_v, r_q, r_kv) of a layer kind."""
    p = "" if kind == FULL else "swa_"
    return tuple(cfg[p + k] for k in (
        "num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim",
        "v_head_dim", "q_lora_rank", "kv_lora_rank"))


def attention_params(cfg, kind):
    """Matrix parameters of one layer's attention (the indexer's with a
    full layer's), norms apart."""
    h = cfg["hidden_size"]
    n, dn, dr, dv, rq, rkv = heads(cfg, kind)
    mla = (h * rq + rq * n * (dn + dr) + h * (rkv + dr)
           + rkv * n * (dn + dv) + h * n + n * dv * h)
    if kind == SLIDING:
        return mla
    s = sizes(cfg)
    return mla + rq * s["J"] * s["Di"] + h * s["Di"] + h * s["J"]


def matmul_params_per_token(cfg):
    """Matrix parameters one token multiplies in a step, all layers and
    the head: attention and indexer whole, router and shared expert
    whole, of the routed experts the share a uniform router sends here
    (top_k * held / total experts a token, each 3 H M), a leading dense
    layer's MLP whole."""
    s = sizes(cfg)
    h = s["h"]
    moe = h * s["total"] + 3 * h * s["m"] * (
        1 + s["k"] * s["held"] / s["total"])
    return (s["full"] * attention_params(cfg, FULL)
            + s["sliding"] * attention_params(cfg, SLIDING)
            + s["expert"] * moe + s["dense"] * 3 * h * s["m_dense"]
            + h * s["vocab"])


def selected_pairs(cfg, seq):
    """(query, key) pairs ONE sequence attends in a full layer: row t
    keeps min(t + 1, index_topk) keys."""
    top = min(cfg["index_topk"], seq)
    return top * (top + 1) // 2 + (seq - top) * top


def band_pairs(cfg, seq):
    """Pairs inside the window of a sliding layer, one sequence."""
    w = min(cfg["sliding_window_size"], seq)
    return w * (w + 1) // 2 + (seq - w) * w


def causal_pairs(seq):
    return seq * (seq + 1) // 2


def _core(cfg, kind, pairs, tokens):
    """(flops, bytes) of one layer's attention core over `pairs`, forward
    + backward: per pair and head the two products forward (q.k over
    d_n + d_r, p.v over d_v) and twice that backward. Bytes: forward
    reads q, k (k^R once for all heads), v and writes o; backward reads
    them, o and do and writes dq, dk, dv (2 bytes each)."""
    n, dn, dr, dv, _, _ = heads(cfg, kind)
    flops = 3 * pairs * n * 2 * (dn + dr + dv)
    q_w, k_w, v_w = n * (dn + dr), n * dn + dr, n * dv
    return flops, tokens * 2 * (3 * q_w + 3 * k_w + 6 * v_w)


def dsa_core_train(cfg, batch, seq):
    """ONE full layer's selected core; the selection itself read as
    index_topk int32 positions a token, forward and backward."""
    flops, byts = _core(cfg, FULL, batch * selected_pairs(cfg, seq),
                        batch * seq)
    return flops, byts + 2 * 4 * batch * selected_pairs(cfg, seq)


def window_attn_train(cfg, batch, seq):
    """ONE sliding layer's core over the band of `sliding_window_size`."""
    return _core(cfg, SLIDING, batch * band_pairs(cfg, seq), batch * seq)


def dsa_index_train(cfg, batch, seq):
    """ONE full layer's index scores over the causal pairs, forward +
    backward: a pair costs a product over index_head_dim an index head
    forward, and two more backward (to q^I and to k^I). Bytes: q^I, k^I
    and the heads' weights (float32) read forward and backward, their
    gradients written; the scores are not an output (the selection is:
    index_topk int32 positions a token)."""
    s = sizes(cfg)
    flops = 3 * batch * causal_pairs(seq) * s["J"] * 2 * s["Di"]
    per_token = s["J"] * s["Di"] + s["Di"] + s["J"]
    return flops, batch * seq * (3 * per_token + s["top"]) * 4


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


def train_flops_per_token(cfg, seq):
    """Forward + backward of one token in a causal sequence of `seq`: 6
    per matrix parameter it multiplies, the full layers' cores over the
    selected pairs and their index scores over the causal pairs, the
    sliding layers' cores over their band."""
    s = sizes(cfg)
    per_seq = (s["full"] * (dsa_core_train(cfg, 1, seq)[0]
                            + dsa_index_train(cfg, 1, seq)[0])
               + s["sliding"] * window_attn_train(cfg, 1, seq)[0])
    return 6 * matmul_params_per_token(cfg) + per_seq / seq
