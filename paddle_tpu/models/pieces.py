"""What `solar_open2`, `granite_hybrid`, `dots3_note` and `glm4_moe_lite`
are built from, each piece written once: the norm, the embedding and the
head under their scopes, the shifted labels and the blocked head + loss,
the two kinds of feed-forward half-layer (experts, dense SwiGLU), the scan
that sums a mixer's groups of heads, and the stack embed -> layers -> norm.

The arrows point one way: a model module imports from here (and
`glm4_moe_lite` from `dots3_note` its layer classes), this file from
`llama.py`, `kernels/` and `nn/`. What `llama.py` keeps under underscore
names has its public name here, so that no model module reaches for
another's private one.

Memory at long sequences decides the structure, the same in all four: a
half of a layer is ONE taped operation that keeps its input alone and is
recomputed in the backward (`jax.checkpoint`), head and loss go over blocks
of rows, and only the last norm in front of them runs again.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..autograd.tape import apply_op
from ..framework import core
from ..nn.layer.container import LayerList
from ..nn.layer.layers import Layer
from ..nn.layer.moe import DroplessMoE
from ..observability.scopes import scope
from ..ops._helpers import to_tensor_like
from .llama import LlamaRMSNorm as RMSNorm  # noqa: F401
from .llama import _param as param
from .llama import _sdpa as sdpa  # noqa: F401
from .llama import _swiglu as swiglu

__all__ = ["RMSNorm", "param", "sdpa", "swiglu", "rms", "branch", "embed",
           "head", "shifted", "head_loss", "blocked_loss", "group_of",
           "sum_of_groups", "dropless_moe_of", "expert_half", "moe_half",
           "SwiGLUHalf", "moe_counters", "DecoderStack", "CausalLM"]


def rms(a, w, eps):
    from ..kernels import rms_norm as krn
    with scope("norm"):
        return krn.rms_norm(a, w, eps)


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


def expert_half(mlp, eps, h, ln_w, *ws):
    """(h + experts(RMSNorm(h)), rows per held expert, dropped pairs) on
    raw arrays, `ws` as `mlp.weights()` lists them."""
    y, counts, dropped = mlp.compute(rms(h, ln_w, eps), *ws)
    return h + y, counts, dropped


def moe_half(mlp, h, ln_w, eps):
    """`expert_half` as ONE taped operation that keeps the mixer's output
    h and recomputes itself whole; the layer's counters are written from
    its two other outputs."""
    run = jax.checkpoint(functools.partial(expert_half, mlp, eps),
                         policy=core.current_remat_policy())
    y, counts, dropped = apply_op(run, h, ln_w, *mlp.weights(), n_outputs=3,
                                  name="moe_block")
    mlp.record(counts.data, dropped.data)
    return y


class SwiGLUHalf(Layer):
    """h + (silu(a Wg) * (a Wu)) Wd, a = RMSNorm(h): a dense half-layer,
    gate | up stored as one [h, 2m] projection (`kernels/swiglu.py`), ONE
    taped operation named `op_name` (the tape's residuals are keyed by it)
    and recomputed in the backward. The branch is added in the activations'
    dtype; a model whose arithmetic differs there states its own `add`."""

    def __init__(self, cfg, op_name):
        super().__init__()
        self.cfg, self.op_name = cfg, op_name
        h, m = cfg.hidden_size, cfg.intermediate_size
        self.gate_up_proj = param(self, (h, 2 * m), P(None, "mp"),
                                  dtype=cfg.dtype)
        self.down_proj = param(self, (m, h), P("mp", None), dtype=cfg.dtype)

    def add(self, h, o, wd):
        return h + (o @ wd)

    def block(self, h, ln_w, wgu, wd):
        a = rms(h, ln_w, self.cfg.rms_norm_eps)
        with scope("mlp"):
            return self.add(h, swiglu(a, wgu), wd)

    def forward(self, h, ln_w):
        return apply_op(
            jax.checkpoint(self.block, policy=core.current_remat_policy()),
            to_tensor_like(h), ln_w, self.gate_up_proj, self.down_proj,
            name=self.op_name)


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
    """embed_tokens -> `layer_of(cfg, i)` for every layer -> norm."""

    def __init__(self, cfg, layer_of, embedding_multiplier=None):
        super().__init__()
        self.cfg = cfg
        self.embedding_multiplier = embedding_multiplier
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
        for lyr in self.layers:
            with scope("layers"):
                x = lyr(x)
            if isinstance(x, tuple):
                x, own = x
                if own is not None and aux is not None:
                    aux.append(own)
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
