"""The mixing of a manifold-constrained hyper-connection (arXiv:2512.24880,
after arXiv:2409.19606): a residual path n streams wide, X [n, B, S, C] (stream
major: a stream is one contiguous [B, S, C] slab, so nothing tiles a 4-row
dimension; it is also the layout the TPU compiler gives the backward's
sums whatever the program asks for: with the streams second it copies X
there and back), around a branch F that reads and writes ONE stream's
width.
Per token, x~ = vec(X) in float32 (stream after stream):

  map   r = rsqrt(mean(x~^2) + eps_norm);  m = r (x~ Phi), Phi [nC, 2n + n^2]
        H~_pre = a_pre m[0:n] + b_pre;  H~_post = a_post m[n:2n] + b_post;
        H~_res = a_res reshape(m[2n:], n, n) + b_res
        H_pre = sigmoid(H~_pre);  H_post = 2 sigmoid(H~_post)
        M_0 = exp(clamp(H~_res, lo, hi));  `iters` times:
          M <- M / (row sums + eps_hc), then M <- M / (column sums + eps_hc)
        H_res = M_iters             (doubly stochastic to the iteration's end)
  pre   u = sum_j H_pre[j] X[j]
  post  X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y,   y = F(u)

The maps are float32; a mix accumulates in float32 and rounds once, to the
stream's dtype. What reads the wide stream is written so that it is read
as seldom as the arithmetic allows and nothing float32 of its size is kept:

  * `stream_stats` (the sum of squares and x~ Phi): a `jax.custom_vjp` that
    keeps X and Phi; forward one product a stream, summed;
  * `pre`, `post`: `jax.custom_vjp`s that keep their own arguments (X in
    its dtype, the maps); forward ONE pass over X each: on a TPU the Pallas
    kernels `hc_pre_fwd` / `hc_post_fwd` below (a sequence's block of rows x
    a block of columns of all n streams at a time), elsewhere and for a width that is
    not a multiple of 128 their `jax.numpy` form, which is also what the
    tests hold the kernels to (`_pre_fused(..., interpret=True)`);
  * the backward of all three is `jax.numpy` (the compiler's fusions): a
    fused backward is a later PR's (ROADMAP.md, Speed queue).
`maps` itself (24 numbers a token) is plain `jax.numpy` under autodiff.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ._tpu import LANES, on_tpu as _on_tpu

__all__ = ["stream_stats", "maps", "maps_of", "pre", "post", "expand",
           "reduce", "sum_errors", "supported", "map_width"]

_F32 = jnp.float32
ROWS = 256          # tokens a grid step of the two kernels holds
COLS = 512          # columns of every stream a grid step holds


def map_width(n):
    """Columns of Phi: n for H_pre, n for H_post, n * n for H_res."""
    return 2 * n + n * n


def supported(shape) -> bool:
    """X [n, B, S, C]: what the compiled kernels take (lane alignment)."""
    return int(shape[-1]) % LANES == 0


# -- the norm's statistic and the product with Phi ------------------------------

@jax.custom_vjp
def stream_stats(X, phi):
    """(sum over streams and columns of X^2 [B, S], x~ Phi [B, S, K]), both
    float32, of X [n, B, S, C] and Phi [n * C, K]."""
    return _stats(X, phi)


def _mm(a, b):
    """a @ b accumulated in float32: the operands as they are on a TPU (its
    matrix unit takes bfloat16 pairs), in float32 elsewhere (XLA's CPU
    backend has no such product for every shape)."""
    if not _on_tpu():
        a, b = a.astype(_F32), b.astype(_F32)
    return jnp.matmul(a, b, preferred_element_type=_F32)


def _stats(X, phi):
    n, C = X.shape[0], X.shape[-1]
    w = phi.reshape(n, C, -1).astype(X.dtype)
    ss = jnp.sum(jnp.square(X.astype(_F32)), axis=(0, 3))
    p = sum(_mm(X[j], w[j]) for j in range(n))
    return ss, p


def _stats_fwd(X, phi):
    return _stats(X, phi), (X, phi)


def _stats_bwd(res, cts):
    X, phi = res
    dss, dp = cts
    n, C = X.shape[0], X.shape[-1]
    w = phi.reshape(n, C, -1).astype(X.dtype)
    dpc = dp.astype(X.dtype)
    dX = jnp.stack([
        (_mm(dpc, w[j].T)
         + 2.0 * dss[..., None] * X[j].astype(_F32)).astype(X.dtype)
        for j in range(n)])
    rows = dpc.reshape(-1, dpc.shape[-1])
    dphi = jnp.stack([_mm(X[j].reshape(-1, C).T, rows)
                      for j in range(n)]).reshape(phi.shape).astype(phi.dtype)
    return dX, dphi


stream_stats.defvjp(_stats_fwd, _stats_bwd)


# -- the three maps, from 2n + n^2 numbers a token -----------------------------

def sinkhorn(M, iters, eps):
    """`iters` times rows then columns of M [n, n, T] (positive): the
    TOKENS are the minor dimension, so that a sum over a row or a column is
    a few adds of whole [T] vectors and the 2 x `iters` normalisations are
    one elementwise chain (a 4 x 4 minor tile would be mostly padding)."""
    for _ in range(iters):
        M = M / (jnp.sum(M, 1, keepdims=True) + eps)
        M = M / (jnp.sum(M, 0, keepdims=True) + eps)
    return M


def maps_of(ss, p, scale, bias, *, n, width, eps, iters, hc_eps, clamp):
    """(H_pre [B, S, n], H_post [B, S, n], H_res [B, S, n, n]) float32 from
    `stream_stats`' two outputs, the three scalars `scale` (pre, post,
    res) and the 2n + n^2 biases `bias`; `width` = n * C. Made with the
    tokens minor ([2n + n^2, B S]) and turned at the end (24 numbers a
    token)."""
    lead = ss.shape
    r = jax.lax.rsqrt(ss.reshape(-1) / width + eps)
    m = r[None, :] * p.reshape(-1, p.shape[-1]).T
    b = bias.astype(_F32)[:, None]
    a = scale.astype(_F32)
    h_pre = jax.nn.sigmoid(a[0] * m[:n] + b[:n])
    h_post = 2.0 * jax.nn.sigmoid(a[1] * m[n:2 * n] + b[n:2 * n])
    raw = (a[2] * m[2 * n:] + b[2 * n:]).reshape(n, n, -1)
    h_res = sinkhorn(jnp.exp(jnp.clip(raw, clamp[0], clamp[1])), iters,
                     hc_eps)
    return (h_pre.T.reshape(lead + (n,)), h_post.T.reshape(lead + (n,)),
            jnp.moveaxis(h_res, -1, 0).reshape(lead + (n, n)))


def maps(X, phi, scale, bias, *, eps, iters, hc_eps, clamp):
    """The three maps of X [n, B, S, C]."""
    n, C = X.shape[0], X.shape[-1]
    ss, p = stream_stats(X, phi)
    return maps_of(ss, p, scale, bias, n=n, width=n * C, eps=eps,
                   iters=iters, hc_eps=hc_eps, clamp=clamp)


def sum_errors(h_res):
    """(largest |row sum - 1|, largest |column sum - 1|) of H_res
    [..., n, n]: the columns were normalised last."""
    return (jnp.max(jnp.abs(jnp.sum(h_res, -1) - 1.0)),
            jnp.max(jnp.abs(jnp.sum(h_res, -2) - 1.0)))


# -- expand and reduce ---------------------------------------------------------

def expand(x, n):
    """X_0[j] = x for every j: [B, S, C] -> [n, B, S, C]."""
    return jnp.broadcast_to(x[None], (n,) + x.shape)


def reduce(X):
    """sum_j X[j], summed in float32 and rounded once."""
    return jnp.sum(X.astype(_F32), axis=0).astype(X.dtype)


# -- pre: n streams -> one -----------------------------------------------------

def _pre_jnp(X, h_pre):
    acc = sum(h_pre[..., j, None] * X[j].astype(_F32)
              for j in range(X.shape[0]))
    return acc.astype(X.dtype)


def _blocks(S, C):
    rows = S if S <= ROWS else ROWS
    cols = COLS if C % COLS == 0 else (C if C <= COLS else LANES)
    return rows, cols


def _pre_kernel(x_ref, h_ref, u_ref):
    h = h_ref[...]
    acc = h[:, 0:1] * x_ref[0].astype(_F32)
    for j in range(1, x_ref.shape[0]):
        acc = acc + h[:, j:j + 1] * x_ref[j].astype(_F32)
    u_ref[...] = acc.astype(u_ref.dtype)


def _pre_fused(X, h_pre, interpret=False):
    n, B, S, C = X.shape
    rows, cols = _blocks(S, C)
    return pl.pallas_call(
        _pre_kernel, name="hc_pre_fwd",
        grid=(B, pl.cdiv(S, rows), C // cols),
        in_specs=[pl.BlockSpec((n, None, rows, cols),
                               lambda b, t, c: (0, b, t, c)),
                  pl.BlockSpec((None, rows, n), lambda b, t, c: (b, t, 0))],
        out_specs=pl.BlockSpec((None, rows, cols), lambda b, t, c: (b, t, c)),
        out_shape=jax.ShapeDtypeStruct((B, S, C), X.dtype),
        interpret=interpret)(X, h_pre)


@jax.custom_vjp
def pre(X, h_pre):
    """u [B, S, C] = sum_j H_pre[..., j] X[j]."""
    if _on_tpu() and supported(X.shape):
        return _pre_fused(X, h_pre)
    return _pre_jnp(X, h_pre)


def _pre_fwd(X, h_pre):
    return pre(X, h_pre), (X, h_pre)


def _pre_bwd(res, du):
    X, h_pre = res
    n = X.shape[0]
    duf = du.astype(_F32)
    dX = jnp.stack([(h_pre[..., j, None] * duf).astype(X.dtype)
                    for j in range(n)])
    dh = jnp.stack([jnp.sum(duf * X[j].astype(_F32), -1) for j in range(n)],
                   axis=-1)
    return dX, dh


pre.defvjp(_pre_fwd, _pre_bwd)


# -- post: n streams and the branch -> n streams ------------------------------

def _post_jnp(X, y, h_res, h_post):
    n = X.shape[0]
    Xf, yf = [X[j].astype(_F32) for j in range(n)], y.astype(_F32)
    return jnp.stack([
        (sum(h_res[..., i, j, None] * Xf[j] for j in range(n))
         + h_post[..., i, None] * yf).astype(X.dtype) for i in range(n)])


def _post_kernel(x_ref, y_ref, hr_ref, hp_ref, o_ref):
    n = x_ref.shape[0]
    hr, hp = hr_ref[...], hp_ref[...]
    xs = [x_ref[j].astype(_F32) for j in range(n)]
    y = y_ref[...].astype(_F32)
    for i in range(n):
        acc = hp[:, i:i + 1] * y
        for j in range(n):
            k = i * n + j
            acc = acc + hr[:, k:k + 1] * xs[j]
        o_ref[i] = acc.astype(o_ref.dtype)


def _post_fused(X, y, h_res, h_post, interpret=False):
    n, B, S, C = X.shape
    rows, cols = _blocks(S, C)
    wide = pl.BlockSpec((n, None, rows, cols), lambda b, t, c: (0, b, t, c))
    small = lambda k: pl.BlockSpec((None, rows, k), lambda b, t, c: (b, t, 0))
    return pl.pallas_call(
        _post_kernel, name="hc_post_fwd",
        grid=(B, pl.cdiv(S, rows), C // cols),
        in_specs=[wide,
                  pl.BlockSpec((None, rows, cols),
                               lambda b, t, c: (b, t, c)),
                  small(n * n), small(n)],
        out_specs=wide,
        out_shape=jax.ShapeDtypeStruct(X.shape, X.dtype),
        interpret=interpret)(X, y, h_res.reshape(B, S, n * n), h_post)


@jax.custom_vjp
def post(X, y, h_res, h_post):
    """X' [n, B, S, C]: X'[i] = sum_j H_res[..., i, j] X[j] + H_post[..., i]
    y."""
    if _on_tpu() and supported(X.shape):
        return _post_fused(X, y, h_res, h_post)
    return _post_jnp(X, y, h_res, h_post)


def _post_fwd(X, y, h_res, h_post):
    return post(X, y, h_res, h_post), (X, y, h_res, h_post)


def _post_bwd(res, dXo):
    X, y, h_res, h_post = res
    n = X.shape[0]
    d = [dXo[i].astype(_F32) for i in range(n)]
    dX = jnp.stack([
        sum(h_res[..., i, j, None] * d[i] for i in range(n)).astype(X.dtype)
        for j in range(n)])
    dy = sum(h_post[..., i, None] * d[i] for i in range(n)).astype(y.dtype)
    Xf, yf = [X[j].astype(_F32) for j in range(n)], y.astype(_F32)
    dh_res = jnp.stack([jnp.stack([jnp.sum(d[i] * Xf[j], -1)
                                   for j in range(n)], -1)
                        for i in range(n)], -2)
    dh_post = jnp.stack([jnp.sum(d[i] * yf, -1) for i in range(n)], -1)
    return dX, dy, dh_res, dh_post


post.defvjp(_post_fwd, _post_bwd)
