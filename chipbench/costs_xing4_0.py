"""Operations and bytes a `xing4_0` train step REQUIRES, from the
configuration's shapes alone (forward and backward once; recomputation is
work the program chose). Attention, experts, heads and the prediction
module count as `costs_glm4_moe_lite` counts them (the same layers at other
widths; `mla_core_train` and `moe_experts_train` are the shared readers');
on top, every half-layer's hyper-connection: the product with Phi in the
step's operations, and `hc_mix_train` for the mixing's own roofline. Used
with `costs.roofline_s` and `peaks.json` as they are.
"""
from __future__ import annotations

from chipbench import costs_glm4_moe_lite as glm
from chipbench.costs_glm4_moe_lite import (  # noqa: F401
    attention_params, causal_pairs, heads, mla_core_calls, mla_core_train,
    moe_experts_train, sizes)


def hc_halves(cfg):
    """Half-layers on a hyper-connection a step: two a layer, the
    prediction module's layer among them."""
    s = sizes(cfg)
    return 2 * (s["layers"] + s["mtp"])


def hc_map_width(cfg):
    n = cfg["hc_mult"]
    return 2 * n + n * n


def hc_mix_train(cfg, batch, seq, itemsize=2):
    """(flops, bytes) of ONE half-layer's map + pre + post, forward +
    backward, over T = batch * seq tokens; n streams of C columns, K = 2n
    + n^2 map columns, X the [n, T, C] stream array.
    Forward: the norm's sum of squares (2 n C a token) and x~ Phi (2 n C
    K), u = H_pre X (2 n C), X' = H_res X + H_post y (2 (n^2 + n) C);
    Sinkhorn's 20 x 2 normalisations of a 4 x 4 are not counted. Backward:
    dX and dy of post (2 (n^2 + n) C), dH_res and dH_post (2 (n^2 + n) C),
    dX and dH_pre of pre (4 n C), d x~ and dPhi of the product (4 n C K),
    the norm's (4 n C).
    Bytes, what a fused forward and a fused backward cannot avoid: the map
    needs a token's whole X before u exists and post needs y = F(u), so
    the forward reads X twice, writes u, reads y and writes X' ((3 n + 2)
    C); the backward is cut in two by F's own backward: first dX', X and y
    in, dy and H_res^T dX' out ((3 n + 2) C), then X, du and that partial
    in, dX out ((3 n + 1) C): (9 n + 5) C elements a token in all. Phi and the maps (K and 2K numbers a token)
    are not counted. Recomputation (a feed-forward half runs map and pre
    again) is in the time, not here."""
    n, c, k = cfg["hc_mult"], cfg["hidden_size"], hc_map_width(cfg)
    t = batch * seq
    forward = 2 * n * c + 2 * n * c * k + 2 * n * c + 2 * (n * n + n) * c
    backward = (4 * (n * n + n) * c + 4 * n * c + 4 * n * c * k + 4 * n * c)
    return t * (forward + backward), t * (9 * n + 5) * c * itemsize


def matmul_params_per_token(cfg):
    """`costs_glm4_moe_lite.matmul_params_per_token` and every half-layer's
    Phi [n C, K]."""
    n, c = cfg["hc_mult"], cfg["hidden_size"]
    return (glm.matmul_params_per_token(cfg)
            + hc_halves(cfg) * n * c * hc_map_width(cfg))


def train_flops_per_token(cfg, seq):
    """Forward + backward of one token in a causal sequence of `seq`: 6
    per matrix parameter it multiplies (Phi among them) and the causal
    triangle of every core; the mixes' elementwise work (under 1 % of it)
    is left out."""
    return (6 * matmul_params_per_token(cfg)
            + mla_core_calls(cfg) * mla_core_train(cfg, 1, seq)[0] / seq)
