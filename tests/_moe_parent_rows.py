"""How `nn/layer/moe.py::dropless_moe` moved its rows before ISSUE 45, kept
as the tests' reference: dispatch a `jnp.take` whose dead rows point at
token 0 and are masked after, combine `.at[token].add` in x's dtype over
the whole buffer, the dead rows colliding on token 0, and jax's own
transposes of both. Router, sort, sizes and the grouped products are the
layer's own. Not a test file."""
import jax
import jax.numpy as jnp

from paddle_tpu.kernels.grouped_matmul import ROW_TILE, grouped_matmul
from paddle_tpu.nn.layer.moe import route_top_k


def dropless_moe(x, w_router, w_gate_up, w_down, *, first_expert, top_k,
                 norm_topk=True, scaling=1.0, rows=None, bias=None):
    T, H = x.shape
    E, M = w_gate_up.shape[0], w_down.shape[1]
    pairs = T * top_k
    rows = -(-pairs // ROW_TILE) * ROW_TILE if rows is None \
        else min(rows, -(-pairs // ROW_TILE) * ROW_TILE)
    top_i, top_w = route_top_k(x, w_router, top_k, norm_topk, scaling, bias)
    local = top_i - first_expert
    key = jnp.where((local >= 0) & (local < E), local, E).reshape(-1)
    order = jnp.argsort(key, stable=True)
    if rows > pairs:
        order = jnp.pad(order, (0, rows - pairs))
        key = jnp.pad(key, (0, rows - pairs), constant_values=E)
    order = order[:rows]
    counts = jnp.sum(key[:, None] == jnp.arange(E, dtype=jnp.int32),
                     axis=0, dtype=jnp.int32)
    ends = jnp.minimum(jnp.cumsum(counts), rows)
    sizes = jnp.diff(ends, prepend=0)
    dropped = jnp.sum(counts) - ends[-1]
    valid = jnp.arange(rows) < ends[-1]
    token = jnp.where(valid, order // top_k, 0)
    w_row = jnp.where(valid, jnp.take(top_w.reshape(-1), order), 0.0)
    xs = jnp.where(valid[:, None], jnp.take(x, token, axis=0), 0)
    gu = grouped_matmul(xs, w_gate_up, sizes)
    act = (jax.nn.silu(gu[:, :M].astype(jnp.float32))
           * gu[:, M:].astype(jnp.float32)).astype(x.dtype)
    out = grouped_matmul(act, w_down, sizes)
    out = jnp.where(valid[:, None],
                    out.astype(jnp.float32) * w_row[:, None], 0.0)
    y = jnp.zeros((T, H), x.dtype).at[token].add(out.astype(x.dtype))
    return y, counts, dropped
