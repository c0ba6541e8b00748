"""Operations and bytes the MLP and the head + cross-entropy of a train
step REQUIRE, from the configuration's shapes alone (forward and
backward once; recomputation is work the program chose, not required
work). Used with `costs.roofline_s` and `peaks.json` as they are.
"""
from __future__ import annotations


def mlp_train(cfg, batch, seq, itemsize=2):
    """(flops, bytes) of ONE layer's gated MLP, forward + backward, over
    T = batch * seq tokens. Three matmuls [T,H]x[H,M] (gate, up) and
    [T,M]x[M,H] (down): 2 T H M each forward, twice that backward
    (input and weight gradients) -> 18 T H M; the gated activation
    silu(g) * u and its derivative ~ 14 per element of [T, M]. Bytes:
    forward reads x and the three matrices and writes y; backward reads
    x, dy and the matrices and writes dx and three gradients. The
    [T, M] intermediates are not counted: a fused kernel need not keep
    them."""
    h, m = cfg["hidden_size"], cfg["intermediate_size"]
    t = batch * seq
    flops = 18 * t * h * m + 14 * t * m
    byts = (5 * t * h + 9 * h * m) * itemsize
    return flops, byts


def head_loss_train(cfg, batch, seq, itemsize=2):
    """(flops, bytes) of the output head and the cross-entropy, forward +
    backward: logits = h W (2 T H V), dh and dW (4 T H V), and the
    softmax with its gradient ~ 8 per logit. Bytes: W read twice and its
    gradient written, h read twice and dh written; the [T, V] logits are
    not counted: a fused head + loss need not write them."""
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    t = batch * seq
    flops = 6 * t * h * v + 8 * t * v
    byts = (3 * h * v + 3 * t * h) * itemsize
    return flops, byts
