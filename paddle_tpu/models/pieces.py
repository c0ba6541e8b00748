"""What `solar_open2`, `granite_hybrid`, `dots3_note`, `glm4_moe_lite`,
`xing4_0` and `lfm2_moe` are built from, each piece written once: the norm
(and a head's own norm in front of rotary), the embedding and the head
under their scopes, the shifted labels and the blocked head +
loss, the residual path in its two forms (`Residual`: x + F(x);
`HyperConnection`: n streams mixed around F), the two kinds of feed-forward
half-layer (experts, dense SwiGLU) written against it, the scan that sums a
mixer's groups of heads, and the stack embed -> layers -> norm.

The arrows point one way: a model module imports from here (and
`glm4_moe_lite` from `dots3_note` its layer classes), this file from
`llama.py`, `kernels/` and `nn/`. What `llama.py` keeps under underscore
names has its public name here, so that no model module reaches for
another's private one.

Memory at long sequences decides the structure, the same in all of them: a
half of a layer is ONE taped operation that keeps its input alone and is
recomputed in the backward (`jax.checkpoint`), head and loss go over blocks
of rows, and only the last norm in front of them runs again.
"""
from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..autograd.tape import apply_op
from ..framework import core
from ..nn import initializer as I
from ..nn.layer.container import LayerList
from ..nn.layer.layers import Layer
from ..nn.layer.moe import DroplessMoE
from ..observability.scopes import scope
from ..ops._helpers import to_tensor_like
from ..tensor import Tensor
from .llama import LlamaRMSNorm as RMSNorm  # noqa: F401
from .llama import _param as param
from .llama import _sdpa as sdpa  # noqa: F401
from .llama import _swiglu as swiglu

__all__ = ["RMSNorm", "param", "sdpa", "swiglu", "rms", "qk_norm_rope",
           "branch", "embed",
           "head", "shifted", "head_loss", "blocked_loss", "Residual",
           "PLAIN", "HyperConnection", "over_sequences", "group_of",
           "sum_of_groups",
           "dropless_moe_of", "expert_half", "moe_half", "SwiGLUHalf",
           "moe_counters", "DecoderStack", "CausalLM"]


def rms(a, w, eps):
    from ..kernels import rms_norm as krn
    with scope("norm"):
        return krn.rms_norm(a, w, eps)


def qk_norm_rope(q, k, wq, wk, eps, theta):
    """(q, k) [B, S, heads, d] after an RMSNorm over each head's d channels
    (one [d] weight for q, one for k) and rotary over all d at positions
    0..S-1: float32 from the projections' outputs to the one rounding
    (`kernels/rope.py`: `head_norm`, `rotate`)."""
    from ..kernels import rope
    with scope("attn/qk_norm"):
        qf, kf = rope.head_norm(q, wq, eps), rope.head_norm(k, wk, eps)
    with scope("attn/rope"):
        return rope.rotate(qf, theta, q.dtype), rope.rotate(kf, theta,
                                                            k.dtype)


def branch(x, out, r):
    """x + r * out, out the float32 accumulator of a branch's last
    product: one rounding, to x's dtype."""
    return (x.astype(jnp.float32) + r * out).astype(x.dtype)


def embed(ids, w, multiplier=None, under="embed"):
    """The table's rows of ids under scope `under`, times `multiplier`
    (in float32, one rounding) where a model scales its embeddings."""
    with scope(under):
        rows = jnp.take(w, ids.astype(jnp.int32), axis=0)
        if multiplier is None:
            return rows
        return (rows.astype(jnp.float32) * multiplier).astype(w.dtype)


def head(a, w):
    with scope("head"):
        return a @ w


def shifted(labels, by=1):
    """[B * S] labels of [B, S]: row i's is the token `by` positions on;
    the last `by` rows of a sequence have none (-100)."""
    lb = to_tensor_like(labels).data
    return jnp.concatenate(
        [lb[:, by:], jnp.full((lb.shape[0], by), -100, lb.dtype)],
        axis=1).reshape(-1)


def head_loss(x, norm_w, w, labels, *, eps, block_rows, tied=False,
              logit_scale=None, scopes=("head", "loss")):
    """Mean cross-entropy of RMSNorm(x) W against `labels` [B * S], head
    and loss a block of rows at a time (`_linear_cross_entropy`, whose
    `tied`, `logit_scale` and `scopes` these are). The last norm's output
    is not kept: the norm alone runs again in the backward (a
    `jax.checkpoint` around the blocked rule would run it twice)."""
    from ..nn.functional.loss import _linear_cross_entropy
    xn = jax.checkpoint(rms, static_argnums=2)(x, norm_w, eps)
    return _linear_cross_entropy(
        xn.reshape(-1, xn.shape[-1]), w, labels, block_rows, -100,
        tied=tied, logit_scale=logit_scale, scopes=scopes)


# -- the residual path, in two forms --------------------------------------------

class Residual:
    """The plain residual path: ONE stream, a half-layer is x + F(x). It
    learns nothing, keeps nothing and counts nothing (`leaves`, `extra`).

    A half-layer is written against `half`: it hands its branch F (the
    norm inside, no add) and the path joins F's output to what it carries.
    `half` runs on raw arrays, inside the half-layer's one taped operation;
    `hw` are the path's own leaves as that operation received them."""

    extra = 0          # outputs `half` appends to F's: none

    def leaves(self):
        return []

    def half(self, F, x, hw=(), join=None, under=None):
        """x + F(x): F returns the branch's output, or a tuple that starts
        with it (the rest is handed through). `join(x, y)` where a model's
        add is not the plain one (a multiplier, one rounding), `under` the
        scope the add belongs to."""
        y, *more = _tuple(F(x))
        with scope(under) if under else contextlib.nullcontext():
            out = x + y if join is None else join(x, y)
        return (out, *more) if more else out

    def by_sequence(self, seq, x, hw=(), gather=None, under=None):
        """`half` around a branch `seq` that takes ONE sequence [S, H] (a
        mixer whose core is a sequence's), on x [B, S, H]: the whole half a
        sequence at a time, the add inside the loop (the accepted models'
        programs); `gather` makes one value of each of the branch's other
        outputs over the sequences."""
        return over_sequences(
            lambda xs: self.half(seq, xs, hw, under=under), gather)(x)

    def record(self, *extra):
        pass


PLAIN = Residual()


def _tuple(out):
    return out if isinstance(out, tuple) else (out,)


def over_sequences(one, gather=None):
    """f on [B, S, ...] from `one` on a sequence [S, ...] -> (y, *rest):
    the one sequence of a batch of one directly, more through
    `jax.lax.map`, their `rest` (stacked over the sequences) through
    `gather`."""
    def f(x):
        if x.shape[0] == 1:
            y, *rest = _tuple(one(x[0]))
            return (y[None], *rest)
        y, *rest = jax.lax.map(lambda xs: _tuple(one(xs)), x)
        return (y, *(gather(*rest) if rest else ()))
    return f


class HyperConnection(Layer):
    """The four-stream path of ONE half-layer (manifold-constrained
    hyper-connections, arXiv:2512.24880; `kernels/hyper_connection.py` has
    the equations): X [n, B, S, H] -> H_res X + H_post^T F(H_pre X), the
    three maps made from X itself through the leaves held here: `phi`
    [n H, 2n + n^2], three scalars `scale` and 2n + n^2 biases `bias`
    (float32). `cfg` gives `hc_mult`, `hc_sinkhorn_iters`, `hc_eps`,
    `mhc_h_res_clamp_min` / `_max`, `rms_norm_eps`, and how the leaves
    start (`hc_init`: scale, b_pre, b_post, the diagonal and the rest of
    b_res).

    `half` appends one output to F's: the largest |row sum - 1| and
    |column sum - 1| of H_res over the tokens, [2]; `record` keeps it in
    the buffer `res_sum_err`. Of the maps' making only X and the leaves are
    kept for the backward (the 40 normalisations run again there); a
    half-layer under a whole `jax.checkpoint` keeps X alone."""

    extra = 1          # one output appended to F's: the two errors, [2]

    def __init__(self, cfg):
        super().__init__()
        from ..kernels import hyper_connection as hc
        self.cfg = cfg
        n, h = cfg.hc_mult, cfg.hidden_size
        self.phi = param(self, (n * h, hc.map_width(n)), P(None, None),
                         dtype=cfg.dtype)
        self.scale = param(self, (3,), P(None),
                           init=I.Constant(cfg.hc_init[0]), dtype="float32")
        self.bias = param(self, (hc.map_width(n),), P(None), init=I.Assign(
            self.bias_start(n, *cfg.hc_init[1:])), dtype="float32")
        self.register_buffer("res_sum_err", Tensor(jnp.zeros((2,),
                                                             jnp.float32)))

    @staticmethod
    def bias_start(n, b_pre, b_post, b_diag, b_off):
        """The 2n + n^2 biases at the start: b_pre and b_post n times each,
        b_res `b_diag` on the diagonal and `b_off` off it (float32)."""
        b_res = np.full((n, n), b_off, np.float32)
        np.fill_diagonal(b_res, b_diag)
        return np.concatenate([np.full(n, b_pre, np.float32),
                               np.full(n, b_post, np.float32),
                               b_res.reshape(-1)])

    def leaves(self):
        return [self.phi, self.scale, self.bias]

    def half(self, F, X, hw, join=None, under=None):
        from ..kernels import hyper_connection as hc
        cfg = self.cfg
        with scope("hc/map"):
            h_pre, h_post, h_res = jax.checkpoint(functools.partial(
                hc.maps, eps=cfg.rms_norm_eps, iters=cfg.hc_sinkhorn_iters,
                hc_eps=cfg.hc_eps, clamp=(cfg.mhc_h_res_clamp_min,
                                          cfg.mhc_h_res_clamp_max)))(X, *hw)
            errs = jnp.stack(hc.sum_errors(jax.lax.stop_gradient(h_res)))
        with scope("hc/pre"):
            u = hc.pre(X, h_pre)
        y, *more = _tuple(F(u))
        with scope("hc/post"):
            out = hc.post(X, y, h_res, h_post)
        return (out, *more, errs)

    def by_sequence(self, seq, X, hw, gather=None, under=None):
        """`half` around a branch `seq` that takes ONE sequence [S, H]: the
        mixing over the whole batch (it is a token's own), the branch a
        sequence at a time."""
        return self.half(over_sequences(seq, gather), X, hw)

    def record(self, errs):
        self.res_sum_err.data = errs


def expand_streams(x, n):
    """X_0[j] = x for every stream j: [B, S, H] -> [n, B, S, H]."""
    from ..kernels import hyper_connection as hc
    with scope("hc/expand"):
        return hc.expand(x, n)


def reduce_streams(X):
    """sum_j X[j]: [n, B, S, H] -> [B, S, H]."""
    from ..kernels import hyper_connection as hc
    with scope("hc/reduce"):
        return hc.reduce(X)


# -- a mixer's heads, a group at a time ----------------------------------------

def group_of(w, parts, groups, g):
    """Columns of group g: w [rows, parts * groups * n] viewed as
    [rows, parts, groups, n] -> [rows, parts * n]."""
    rows = w.shape[0]
    w4 = w.reshape(rows, parts, groups, -1)
    return jax.lax.dynamic_index_in_dim(w4, g, 2, keepdims=False).reshape(
        rows, -1)


def sum_of_groups(group, n, x, ws):
    """sum over g < n of group(g, x, *ws), in x's dtype: one group of heads
    at a time, summed in float32; the backward recomputes a group
    (`jax.checkpoint`) and keeps of it what the armed remat policy names
    (the attention kernel's out and logsumexp, stacked over the groups by
    the scan; a delta-rule group stamps nothing)."""
    run = jax.checkpoint(group, policy=core.current_remat_policy())

    def body(acc, g):
        return acc + run(g, x, *ws), None

    acc, _ = jax.lax.scan(body, jnp.zeros(x.shape, jnp.float32),
                          jnp.arange(n))
    return acc.astype(x.dtype)


# -- the feed-forward half of a layer ------------------------------------------

def dropless_moe_of(cfg, **extra):
    """`nn.DroplessMoE` as a configuration with the expert fields spells
    it: told which experts it holds and how many rows its buffer has."""
    return DroplessMoE(
        cfg.hidden_size, cfg.moe_intermediate_size, cfg.n_routed_experts,
        cfg.num_experts_per_tok, experts_held=cfg.experts_held,
        first_expert=cfg.expert_offset, shared_experts=cfg.n_shared_experts,
        norm_topk_prob=cfg.norm_topk_prob,
        routed_scaling_factor=cfg.routed_scaling_factor, rows=cfg.moe_rows,
        dtype=cfg.dtype, **extra)


def expert_half(mlp, eps, h, ln_w, *ws, path=PLAIN):
    """(`path`'s half around experts(RMSNorm(.)), rows per held expert,
    dropped pairs, what the path appends) on raw arrays, `ws` the path's
    leaves then `mlp.weights()`."""
    k = len(path.leaves())
    return path.half(lambda u: mlp.compute(rms(u, ln_w, eps), *ws[k:]), h,
                     ws[:k])


def moe_half(mlp, h, ln_w, eps, path=PLAIN):
    """`expert_half` as ONE taped operation that keeps the mixer's output
    h and recomputes itself whole; the layer's counters (and the path's)
    are written from its other outputs."""
    run = jax.checkpoint(functools.partial(expert_half, mlp, eps, path=path),
                         policy=core.current_remat_policy())
    y, counts, dropped, *extra = apply_op(
        run, h, ln_w, *path.leaves(), *mlp.weights(),
        n_outputs=3 + path.extra, name="moe_block")
    mlp.record(counts.data, dropped.data)
    path.record(*(e.data for e in extra))
    return y


class SwiGLUHalf(Layer):
    """h + (silu(a Wg) * (a Wu)) Wd, a = RMSNorm(h): a dense half-layer,
    gate | up stored as one [h, 2m] projection (`kernels/swiglu.py`), ONE
    taped operation named `op_name` (the tape's residuals are keyed by it)
    and recomputed in the backward, written against the residual path its
    caller hands it. On the plain path the branch is added in the
    activations' dtype; a model whose arithmetic differs there states its
    own `down` (the last product) and `join` (the add)."""

    join = None        # the plain path's own add

    def __init__(self, cfg, op_name):
        super().__init__()
        self.cfg, self.op_name = cfg, op_name
        h, m = cfg.hidden_size, cfg.intermediate_size
        self.gate_up_proj = param(self, (h, 2 * m), P(None, "mp"),
                                  dtype=cfg.dtype)
        self.down_proj = param(self, (m, h), P("mp", None), dtype=cfg.dtype)

    def down(self, o, wd):
        return o @ wd

    def branch(self, u, ln_w, wgu, wd):
        """F of this half: SwiGLU(RMSNorm(u)) Wd, no add."""
        a = rms(u, ln_w, self.cfg.rms_norm_eps)
        with scope("mlp"):
            return self.down(swiglu(a, wgu), wd)

    def block(self, h, ln_w, *ws, path=PLAIN):
        k = len(path.leaves())
        return path.half(lambda u: self.branch(u, ln_w, *ws[k:]), h, ws[:k],
                         join=self.join, under="mlp")

    def forward(self, h, ln_w, path=PLAIN):
        out = apply_op(
            jax.checkpoint(functools.partial(self.block, path=path),
                           policy=core.current_remat_policy()),
            to_tensor_like(h), ln_w, *path.leaves(), self.gate_up_proj,
            self.down_proj, n_outputs=1 + path.extra, name=self.op_name)
        if path.extra:
            out, errs = out
            path.record(errs.data)
        return out


def moe_counters(blocks, **extra):
    """{"expert_tokens": [expert layers, experts held], "dropped_pairs":
    [expert layers]} of the blocks whose `mlp` is a `DroplessMoE`, as the
    last step left them, and {name: [buffers]} of `extra` alike (host
    arrays; not for a timed region: reading waits for the device)."""
    mlps = [b.mlp for b in blocks if isinstance(b.mlp, DroplessMoE)]
    host = lambda tensors: [np.asarray(t.data) for t in tensors]
    out = {"expert_tokens": np.stack(host(m.expert_tokens for m in mlps)),
           "dropped_pairs": np.asarray(host(m.dropped_pairs for m in mlps))}
    out.update({k: np.asarray(host(v)) for k, v in extra.items()})
    return out


# -- the stack and the model ---------------------------------------------------

class DecoderStack(Layer):
    """embed_tokens -> `layer_of(cfg, i)` for every layer -> norm; with
    `streams` > 1 the embedding is expanded to that many residual streams
    in front of the layers and they are summed behind them."""

    def __init__(self, cfg, layer_of, embedding_multiplier=None, streams=1):
        super().__init__()
        self.cfg = cfg
        self.embedding_multiplier = embedding_multiplier
        self.streams = streams
        self.embed_tokens = param(self, (cfg.vocab_size, cfg.hidden_size),
                                  P("mp", None), dtype=cfg.dtype)
        self.layers = LayerList([layer_of(cfg, i)
                                 for i in range(cfg.num_hidden_layers)])
        self.norm = RMSNorm(cfg.hidden_size, cfg.rms_norm_eps)

    def forward(self, input_ids, final_norm=True, aux=None):
        """A layer that has a loss of its own returns (x, that loss or
        None); the losses are appended to `aux` where the caller hands
        one."""
        x = apply_op(embed, to_tensor_like(input_ids), self.embed_tokens,
                     name="embed", multiplier=self.embedding_multiplier)
        if self.streams > 1:
            x = apply_op(expand_streams, x, name="hc_expand",
                         n=self.streams)
        for lyr in self.layers:
            with scope("layers"):
                x = lyr(x)
            if isinstance(x, tuple):
                x, own = x
                if own is not None and aux is not None:
                    aux.append(own)
        if self.streams > 1:
            x = apply_op(reduce_streams, x, name="hc_reduce")
        return self.norm(x) if final_norm else x


def blocked_loss(cfg, x, norm_w, w, labels, name="head_loss", **how):
    """The taped `head_loss` of hidden states x through the head w against
    `labels` [B * S] (`shifted`), its norm's eps and its block of rows the
    configuration's."""
    return apply_op(head_loss, x, norm_w, w, labels, name=name,
                    eps=cfg.rms_norm_eps, block_rows=cfg.loss_block_rows,
                    **how)


class CausalLM(Layer):
    """A stack `model` and an untied head `lm_head`."""

    def __init__(self, cfg, stack):
        super().__init__()
        self.cfg = cfg
        self.model = stack(cfg)
        self.lm_head = param(self, (cfg.hidden_size, cfg.vocab_size),
                             P(None, "mp"), dtype=cfg.dtype)

    def forward(self, input_ids):
        return apply_op(head, self.model(input_ids), self.lm_head,
                        name="lm_head")
