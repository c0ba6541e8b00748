"""A dropless mixture-of-experts layer that is told which experts it
holds.

The router scores every token against ALL `num_experts` experts (a
sigmoid each), keeps the `top_k` largest and normalises their scores
into weights (with a selection bias, the largest of score + bias are
kept and the weights are still the scores' own). This layer holds the
experts [first_expert, first_expert + experts_held): it sorts the (token,
expert) pairs routed to them by expert, gathers those tokens' rows into
one row buffer, runs two grouped matrix products over it (SwiGLU experts,
`kernels/grouped_matmul.py`), and adds each row back to its token with
its weight. Pairs routed to experts held elsewhere add nothing here:
under expert parallelism their chips add them, and on one chip the
partial sum is the result. A shared expert, if any, is added once.

How rows move (`kernels/row_moves.py`): dispatch is `gather_rows`, combine
`scatter_add_rows`, and each is the other's backward, so a layer's four
row moves are two routines over the LIVE rows (`live` = the pairs that got
a row; what the buffer holds past them is never used). A row is weighted
in float32 and rounded to x's dtype; a token's rows are then summed in
float32 and rounded once. The gathers are `jnp.take` on every route. The
scatter-adds are a Pallas kernel on a TPU for bf16 rows a multiple of 128
wide and the compiler's scatter elsewhere: at 10 KB rows that scatter
took 32.8 ms a call of 16,384 buffer rows where the gather of the same
rows took 1.4 (the dots3 cell, PERF.md section 6, PR 45). One route for
every width: no rule of route beyond platform, dtype and tiling was
needed. The set-up event `moe.rows` says which route a traced layer took.

Dropless: the row buffer has `rows` rows for the whole layer, not a
capacity an expert. Every pair of a held expert gets a row while
sum(pairs here) <= rows; what does not fit is COUNTED (`dropped_pairs`,
a running sum the compiled step writes), never silently lost.
`rows=None` sizes the buffer for the worst case (every pair here).

Counters are non-trainable buffers, written by the step that runs the
layer and read by the host between steps: `expert_tokens` [experts_held]
(the last call's rows per held expert) and `dropped_pairs` [].
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ...autograd.tape import apply_op
from ...observability.scopes import scope
from ...ops._helpers import to_tensor_like
from ...tensor import Tensor
from .. import initializer as I
from .layers import Layer

__all__ = ["DroplessMoE", "dropless_moe", "route_top_k"]


def route_top_k(x, w_router, top_k, norm_topk=True, scaling=1.0,
                bias=None, norm_eps=0.0):
    """(expert ids [T, k] int32, weights [T, k] f32): sigmoid scores over
    all the router's outputs, accumulated in float32 at full precision (a
    score's rounding picks another expert; bf16 operands multiply
    exactly, so no float32 copy of x is made), the top-k, normalised.
    With `bias` [experts] the top-k are those of score + bias and the
    weights still the scores' own (selection bias: it balances the load
    and carries no gradient). `norm_eps` is added to the sum the chosen
    scores are divided by (a published block that divides by sum + 1e-6
    says so; 0 divides by the sum itself)."""
    s = jax.nn.sigmoid(jnp.matmul(
        x, w_router, precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32))
    if bias is None:
        top_s, top_i = jax.lax.top_k(s, top_k)
    else:
        _, top_i = jax.lax.top_k(
            s + jax.lax.stop_gradient(bias.astype(jnp.float32)), top_k)
        top_s = jnp.take_along_axis(s, top_i, axis=-1)
    if norm_topk:
        total = jnp.sum(top_s, -1, keepdims=True)
        top_s = top_s / (total + norm_eps if norm_eps else total)
    return top_i.astype(jnp.int32), top_s * scaling


def dropless_moe(x, w_router, w_gate_up, w_down, *, first_expert, top_k,
                 norm_topk=True, scaling=1.0, rows=None, bias=None,
                 norm_eps=0.0):
    """The held experts' part of the routed sum for x [T, H]. w_gate_up
    [E_held, H, 2M] (gate | up), w_down [E_held, M, H]. Returns
    (y [T, H] in x's dtype, rows per held expert [E_held] int32,
    pairs that found no row [] int32)."""
    from ...kernels.grouped_matmul import ROW_TILE, grouped_matmul
    from ...kernels import row_moves
    from ...observability import spans
    T, H = x.shape
    E, M = w_gate_up.shape[0], w_down.shape[1]
    pairs = T * top_k
    rows = -(-pairs // ROW_TILE) * ROW_TILE if rows is None \
        else min(rows, -(-pairs // ROW_TILE) * ROW_TILE)
    spans.setup_event(
        "moe.rows", route=row_moves.route(rows, T, H, x.dtype), rows=rows,
        hidden=H, row_bytes=H * x.dtype.itemsize, tokens=T,
        tile=row_moves.TILE, chunk=row_moves.CHUNK)
    with scope("moe/router"):
        top_i, top_w = route_top_k(x, w_router, top_k, norm_topk, scaling,
                                   bias, norm_eps)
    with scope("moe/dispatch"):
        local = top_i - first_expert
        key = jnp.where((local >= 0) & (local < E), local, E).reshape(-1)
        order = jnp.argsort(key, stable=True)
        if rows > pairs:           # a buffer larger than the pairs: pad
            order = jnp.pad(order, (0, rows - pairs))
            key = jnp.pad(key, (0, rows - pairs), constant_values=E)
        order = order[:rows]
        counts = jnp.sum(key[:, None] == jnp.arange(E, dtype=jnp.int32),
                         axis=0, dtype=jnp.int32)
        ends = jnp.minimum(jnp.cumsum(counts), rows)
        sizes = jnp.diff(ends, prepend=0)
        live = ends[-1]
        dropped = jnp.sum(counts) - live
        valid = jnp.arange(rows) < live
        token = jnp.where(valid, order // top_k, 0)
        w_row = jnp.where(valid, jnp.take(top_w.reshape(-1), order), 0.0)
        xs = row_moves.gather_rows(x, token, live)
    with scope("moe/experts"):
        gu = grouped_matmul(xs, w_gate_up, sizes)
        act = (jax.nn.silu(gu[:, :M].astype(jnp.float32))
               * gu[:, M:].astype(jnp.float32)).astype(x.dtype)
        out = grouped_matmul(act, w_down, sizes)
    with scope("moe/combine"):
        # weighted in float32 and rounded to x's dtype a row; a token's
        # rows (at most top_k) are summed in float32 and rounded once
        out = jnp.where(valid[:, None],
                        out.astype(jnp.float32) * w_row[:, None], 0.0)
        y = row_moves.scatter_add_rows(out.astype(x.dtype), token, live, T)
    return y, counts, dropped


class DroplessMoE(Layer):
    """Sigmoid-routed top-k mixture of SwiGLU experts over the experts
    held here, plus `shared_experts` shared SwiGLU experts of the same
    width (0 or 1). See the module docstring."""

    def __init__(self, hidden_size, expert_size, num_experts, top_k,
                 experts_held=None, first_expert=0, shared_experts=1,
                 norm_topk_prob=True, routed_scaling_factor=1.0, rows=None,
                 dtype=None, std=0.02, selection_bias=False,
                 norm_topk_eps=0.0):
        super().__init__()
        held = num_experts if experts_held is None else experts_held
        if not 0 <= first_expert <= first_expert + held <= num_experts:
            raise ValueError(
                f"experts [{first_expert}, {first_expert + held}) are not "
                f"among the router's {num_experts}")
        if shared_experts not in (0, 1):
            raise ValueError("shared_experts is 0 or 1")
        self.num_experts, self.top_k = num_experts, top_k
        self.first_expert, self.experts_held = first_expert, held
        self.norm_topk_prob = norm_topk_prob
        self.norm_topk_eps = norm_topk_eps
        self.routed_scaling_factor = routed_scaling_factor
        self.rows = rows
        h, m = hidden_size, expert_size

        def param(shape, pspec):
            p = self.create_parameter(shape, dtype=dtype,
                                      default_initializer=I.Normal(0.0, std))
            p.pspec = pspec
            return p

        self.router = param((h, num_experts), P(None, None))
        self.experts_gate_up = param((held, h, 2 * m), P("ep", None, None))
        self.experts_down = param((held, m, h), P("ep", None, None))
        if shared_experts:
            self.shared_gate_up = param((h, 2 * m), P(None, None))
            self.shared_down = param((m, h), P(None, None))
        else:
            self.shared_gate_up = self.shared_down = None
        # a buffer, not a parameter: no gradient reaches it (a trainer
        # moves it by the experts' load, outside the step)
        if selection_bias:
            self.register_buffer("e_score_correction_bias", Tensor(
                jnp.zeros((num_experts,), jnp.float32)))
        else:
            self.e_score_correction_bias = None
        self.register_buffer("expert_tokens",
                             Tensor(jnp.zeros((held,), jnp.int32)))
        self.register_buffer("dropped_pairs", Tensor(jnp.zeros((), jnp.int32)))

    def compute(self, x, w_router, w_gate_up, w_down, w_shared_gate_up=None,
                w_shared_down=None):
        """Raw arrays in, (y, rows per held expert, dropped pairs) out:
        what `forward` records, for a caller that runs the layer inside a
        `jax.checkpoint` and records the counters outside it. A layer
        built with `selection_bias` reads its buffer as it stands."""
        lead = x.shape[:-1]
        x2 = x.reshape(-1, x.shape[-1])
        bias = None if self.e_score_correction_bias is None \
            else self.e_score_correction_bias.data
        y, counts, dropped = dropless_moe(
            x2, w_router, w_gate_up, w_down, first_expert=self.first_expert,
            top_k=self.top_k, norm_topk=self.norm_topk_prob,
            scaling=self.routed_scaling_factor, rows=self.rows, bias=bias,
            norm_eps=self.norm_topk_eps)
        if w_shared_gate_up is not None:
            from ...kernels.swiglu import swiglu
            with scope("moe/shared"):
                y = y + swiglu(x2, w_shared_gate_up) @ w_shared_down
        return y.reshape(lead + (y.shape[-1],)), counts, dropped

    def weights(self):
        ws = [self.router, self.experts_gate_up, self.experts_down]
        if self.shared_gate_up is not None:
            ws += [self.shared_gate_up, self.shared_down]
        return ws

    def record(self, counts, dropped):
        """Write the counters (arrays or tracers of the running step)."""
        self.expert_tokens.data = counts
        self.dropped_pairs.data = self.dropped_pairs.data + dropped

    def forward(self, x):
        y, counts, dropped = apply_op(
            self.compute, to_tensor_like(x), *self.weights(), n_outputs=3,
            name="dropless_moe")
        self.record(counts.data, dropped.data)
        return y
