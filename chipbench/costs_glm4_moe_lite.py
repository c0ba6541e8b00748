"""Operations and bytes a `glm4_moe_lite` train step REQUIRES, from the
configuration's shapes alone (forward and backward once; recomputation is
work the program chose). The multi-token-prediction module counts as what
it is: one more attention layer, one more expert layer, a [2H, H] product
and a second pass over the head. Used with `costs.roofline_s` and
`peaks.json` as they are.
"""
from __future__ import annotations


def sizes(cfg):
    layers = cfg["num_hidden_layers"]
    total = cfg.get("reduced_from", {}).get("n_routed_experts",
                                            cfg["n_routed_experts"])
    dense = min(cfg["first_k_dense_replace"], layers)
    return {
        "h": cfg["hidden_size"], "layers": layers, "dense": dense,
        "expert": layers - dense,
        "mtp": int(bool(cfg.get("num_nextn_predict_layers", 0))),
        "m_dense": cfg["intermediate_size"],
        "m": cfg["moe_intermediate_size"], "held": cfg["n_routed_experts"],
        "total": total, "k": cfg["num_experts_per_tok"],
        "vocab": cfg.get("vocab_rows", cfg["vocab_size"])}


def heads(cfg):
    """(n, d_n, d_r, d_v, r_q, r_kv)."""
    return tuple(cfg[k] for k in (
        "num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim",
        "v_head_dim", "q_lora_rank", "kv_lora_rank"))


def attention_params(cfg):
    """Matrix parameters of one layer's attention, norms apart."""
    h = cfg["hidden_size"]
    n, dn, dr, dv, rq, rkv = heads(cfg)
    return (h * rq + rq * n * (dn + dr) + h * (rkv + dr)
            + rkv * n * (dn + dv) + n * dv * h)


def matmul_params_per_token(cfg):
    """Matrix parameters one token multiplies in a step: attention whole
    in every layer and in the module, router and shared expert whole, of
    the routed experts the share a uniform router sends here (top_k * held
    / total experts a token, each 3 H M), the leading dense layer's MLP,
    the head once for the trunk and once for the module, and the module's
    [2H, H] product."""
    s = sizes(cfg)
    h = s["h"]
    moe = h * s["total"] + 3 * h * s["m"] * (
        1 + s["k"] * s["held"] / s["total"])
    return ((s["layers"] + s["mtp"]) * attention_params(cfg)
            + (s["expert"] + s["mtp"]) * moe
            + s["dense"] * 3 * h * s["m_dense"]
            + (1 + s["mtp"]) * h * s["vocab"] + s["mtp"] * 2 * h * h)


def causal_pairs(seq):
    """(query, key) pairs of ONE causal sequence: the triangle."""
    return seq * (seq + 1) // 2


def mla_core_train(cfg, batch, seq):
    """(flops, bytes) of ONE call of the dense-causal latent core, forward
    + backward: per causal pair and head the two products forward (q.k
    over d_n + d_r, p.v over d_v) and twice that backward. Bytes: forward
    reads q, k (k^R once for all heads), v and writes o; backward reads
    them, o and do and writes dq, dk, dv (2 bytes each)."""
    n, dn, dr, dv, _, _ = heads(cfg)
    flops = 3 * batch * causal_pairs(seq) * n * 2 * (dn + dr + dv)
    q_w, k_w, v_w = n * (dn + dr), n * dn + dr, n * dv
    return flops, batch * seq * 2 * (3 * q_w + 3 * k_w + 6 * v_w)


def mla_core_calls(cfg):
    """Calls of the core a step: every layer's and the module's."""
    s = sizes(cfg)
    return s["layers"] + s["mtp"]


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
    per matrix parameter it multiplies (the held experts' pairs, both
    heads, the module) and the causal triangle of every core."""
    return (6 * matmul_params_per_token(cfg)
            + mla_core_calls(cfg) * mla_core_train(cfg, 1, seq)[0] / seq)
