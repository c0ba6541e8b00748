"""Chunked gated delta rule with a per-channel decay ("KDA"), forward and
backward.

Per head, with a state S in R^{dk x dv}, a log-decay g_t in R^{dk}
(alpha_t = exp(g_t)) and a step size beta_t:

    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t * scale

The recurrence is sequential in t. This operator runs it a CHUNK of C
tokens at a time (the WY form): with G_t the running sum of g inside the
chunk and S_0 the state the chunk starts from,

    A_ij = beta_i sum_c k_ic k_jc exp(G_ic - G_jc)          (j < i)
    (I + A) [U~ | W] = [beta v | beta k exp(G)]   (unit lower triangular)
    U    = U~ - W S_0
    o    = scale [(q exp(G)) S_0 + P U],  P_ij = sum_c q_ic k_jc
                                          exp(G_ic - G_jc)  (j <= i)
    S_C  = Diag(exp(G_C)) S_0 + (k exp(G_C - G))^T U

and only U, o and S_C depend on the chunk before, through S_0.
exp(G_i - G_j) is never split into exp(G_i) exp(-G_j), which overflows
under a strong decay: between 16-token sub-blocks it is split at the
later sub-block's first token (both factors <= 1), and inside a
sub-block it is computed pairwise.

What runs where. On a TPU, at head widths that tile (dk, dv multiples of
128), each pass is ONE Pallas kernel a call, and the operator a
`jax.custom_vjp` over the two:
  * `kda_chunk_states`, grid (batch, heads four at a time, chunks), the
    chunks in order and the transposed state [dv, dk] in VMEM across
    them. It reads a chunk's q, k, v (the model's dtype), g (float32)
    and beta as the model has them ([B, T, H * d]: a head is a lane
    slice, nothing is transposed or copied to float32 in HBM) and does
    everything above in VMEM: G by a product with a 0/1 triangle; A and
    P (a diagonal sub-block a token j at a time against its tokens
    i >= j, on the vector units; the sub-blocks left of it as one
    product a row of sub-blocks); (I + A beta)^-1 (the diagonal
    sub-blocks by substitution, every sub-block of the four heads at
    once, then three float32 products over the sub-blocks); U~ and W;
    U, o and S_C. Differentiated, it also writes what the backward is
    cheaper with than without: each chunk's S_0, A, P, the inverse, U~
    and W (2.4 KB a token a head);
  * `kda_chunk_states_bwd`, the same grid from the last chunk, the
    state's cotangent in VMEM: dq, dk, dv in the model's dtype, dg
    (float32, its reversed running sum taken in the kernel) and dbeta.
    The solve's cotangent is the transposed solve (dR = M^T dX,
    d(A beta) = -tril(dR X^T, -1)), a pairwise sub-block's is the same
    [16, 16, dk] products once more, with no sum over the channels.
What bounds these kernels is the vector units and the chains of small
products, not memory and not the count of operations: a kernel's stages
run over the four heads of a grid step one after the other, so that one
head's products run in the shadow of another's. Everywhere else (the
CPU, head widths that do not tile) the same equations are plain
`jax.numpy`: all chunks at once, a `lax.scan` for the three products that
depend on the chunk before, a triangular solve, and jax's transpose of
all that as the backward. The route is decided from the platform and the
shapes alone. Callers bound the memory of either by the heads they pass
at once: `models/solar_open2.py` passes a group of heads under
`jax.checkpoint`, which runs the forward kernel a second time, and hands
it q, k, v as `kernels/short_conv.py`'s `kda_conv_fwd` writes them
([B, T, heads * d] in the model's dtype, so the reshapes between the two
kernels move nothing); dq, dk, dv go back to `kda_conv_bwd` the same way.

What is rounded where, on both routes: matmul operands take the dtype of
`q` (bf16 in a bf16 model, as the MXU would round them anyway; float32
stays float32), accumulation, G, the decays, A, P, the solve and the
state are float32 (in the kernels a product of two float32 operands runs
at full precision).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._tpu import LANES
from ._tpu import on_tpu as _on_tpu

__all__ = ["chunk_gated_delta_rule"]

_SUB = 16          # tokens of a sub-block; the chunk is a multiple
_PAIR_BYTES = 128 * 1024 * 1024


def _mm(a, b, dtype):
    """a @ b over the last two dims, operands in `dtype`, f32 out."""
    return jnp.matmul(a.astype(dtype), b.astype(dtype),
                      preferred_element_type=jnp.float32)


def _t(x):
    return jnp.swapaxes(x, -1, -2)


def _pair_blocks(q, k, G, dtype):
    """(A, P) [N, ..., C, C] of decayed pair products inside each chunk:
    A_ij = sum_c k_ic k_jc exp(G_ic - G_jc) for j < i, P_ij the same with
    q_i for j <= i; zero elsewhere. q, k, G [N, ..., C, dk] float32."""
    C = q.shape[-2]
    nb = C // _SUB
    low = jnp.tril(jnp.ones((_SUB, _SUB), bool))

    def diagonal(args):
        """One sub-block of one chunk, pairwise: [..., s, dk] -> [..., s, s]."""
        qb, kb, Gb = args
        e = jnp.exp(jnp.where(low[..., None],
                              Gb[..., :, None, :] - Gb[..., None, :, :],
                              -jnp.inf))
        ke = kb[..., None, :, :] * e
        return (jnp.sum(kb[..., :, None, :] * ke, -1)
                * jnp.tril(jnp.ones((_SUB, _SUB), jnp.float32), -1),
                jnp.sum(qb[..., :, None, :] * ke, -1))

    def sub(x):
        """[N, ..., C, dk] -> [N * nb, ..., s, dk]: the pairwise products
        a few sub-blocks at a time, so [s, s, dk] stays small."""
        x = x.reshape(x.shape[:-2] + (nb, _SUB, x.shape[-1]))
        x = jnp.moveaxis(x, -3, 1)                  # [N, nb, ..., s, dk]
        return x.reshape((-1,) + x.shape[2:])

    # checkpointed: the backward makes a batch's [s, s, dk] products again
    # instead of keeping every batch's. A batch is as many sub-blocks as
    # keep one [s, s, dk] product under _PAIR_BYTES: few, large turns
    qs, ks, Gs = sub(q), sub(k), sub(G)
    one = 4 * _SUB * math.prod(qs.shape[1:])
    dA, dP = jax.lax.map(jax.checkpoint(diagonal), (qs, ks, Gs),
                         batch_size=max(1, min(qs.shape[0],
                                               _PAIR_BYTES // one)))

    def unsub(d):
        d = d.reshape((-1, nb) + d.shape[1:])       # [N, nb, ..., s, s]
        return jnp.moveaxis(d, 1, -3)               # [N, ..., nb, s, s]

    dA, dP = unsub(dA), unsub(dP)
    rows_A, rows_P = [], []
    for a in range(nb):
        lo = a * _SUB
        right = jnp.zeros(q.shape[:-2] + (_SUB, C - lo - _SUB), jnp.float32)
        bA, bP = [dA[..., a, :, :], right], [dP[..., a, :, :], right]
        if lo:
            ref = G[..., lo:lo + 1, :]
            row = jnp.exp(G[..., lo:lo + _SUB, :] - ref)
            col = _t(k[..., :lo, :] * jnp.exp(ref - G[..., :lo, :]))
            bA.insert(0, _mm(k[..., lo:lo + _SUB, :] * row, col, dtype))
            bP.insert(0, _mm(q[..., lo:lo + _SUB, :] * row, col, dtype))
        rows_A.append(jnp.concatenate(bA, -1))
        rows_P.append(jnp.concatenate(bP, -1))
    return jnp.concatenate(rows_A, -2), jnp.concatenate(rows_P, -2)


# -- the sequential part, everywhere but on the chip ---------------------------
#
# The state is kept TRANSPOSED, St = S^T [dv, dk]: the decay then scales
# lanes, and every product is one the MXU takes as it stands.

def _states_scan(Ut, W, k_end, decay):
    dtype = W.dtype

    def step(S, xs):
        Ut_n, W_n, k_end_n, decay_n = xs
        U = (Ut_n - _mm(W_n, S, dtype)).astype(dtype)
        S_next = decay_n[..., None] * S + _mm(_t(k_end_n), U, dtype)
        return S_next, (_t(S), U)

    S = jnp.zeros(W.shape[1:-2] + (W.shape[-1], Ut.shape[-1]), jnp.float32)
    return jax.lax.scan(step, S, (Ut, W, k_end, decay))[1]


# -- the chip's route: one Mosaic kernel a pass --------------------------------
#
# Grid (batch, blocks of heads, chunks), the chunk axis walked in order.
# q, k, g, v, o stay [B, T, H * d]: a head is a lane slice of a chunk's
# [C, heads * d] block, so nothing is transposed or copied to float32 in
# HBM. beta goes as [B, H / heads, T, heads] (a column a head).

_NN = (((1,), (0,)), ((), ()))     # a @ b
_NT = (((1,), (1,)), ((), ()))     # a @ b^T
_TN = (((0,), (0,)), ((), ()))     # a^T @ b
_F32 = jnp.float32
_HEADS = 4         # heads a grid step, where they divide


def _dot(a, b, dims=_NN):
    """Operands as they come, float32 out. Two float32 operands (the
    running sums, the solve; everything in a float32 model) multiply at
    full precision, bf16 operands in the MXU's one pass."""
    full = a.dtype == _F32 and b.dtype == _F32
    return jax.lax.dot_general(
        a, b, dims, preferred_element_type=_F32,
        precision=jax.lax.Precision.HIGHEST if full else None)


def _iota(shape, dim):
    return jax.lax.broadcasted_iota(jnp.int32, shape, dim)


def _running_sum(g, reverse=False):
    """Sum over the chunk's tokens up to each (from each, reversed), in
    float32: the 0/1 triangle is exact in bf16, g goes as three bf16
    pieces that add up to it."""
    C = g.shape[0]
    i, j = _iota((C, C), 0), _iota((C, C), 1)
    tri = ((i <= j) if reverse else (i >= j)).astype(jnp.bfloat16)
    hi = g.astype(jnp.bfloat16)
    rest = g - hi.astype(_F32)
    mid = rest.astype(jnp.bfloat16)
    low = (rest - mid.astype(_F32)).astype(jnp.bfloat16)
    return _dot(tri, hi) + (_dot(tri, mid) + _dot(tri, low))


def _pairs(q, k, G, dtype):
    """(A, P) [C, C] float32 of one chunk, as `_pair_blocks` states them:
    a diagonal sub-block pairwise, a column at a time (a token j against
    the sub-block's tokens i >= j), the blocks left of it as one product
    a row of sub-blocks, split at that row's first token."""
    C = k.shape[0]
    rows, lane, tok = _iota((_SUB, 1), 0), _iota((_SUB, C), 1), _iota((C, 1), 0)
    rows_A, rows_P = [], []
    for lo in range(0, C, _SUB):
        qb, kb, Gb = (x[lo:lo + _SUB] for x in (q, k, G))
        if lo:
            ref = Gb[:1]
            row = jnp.exp(Gb - ref)
            col = k * jnp.exp(jnp.where(tok < lo, ref - G, -jnp.inf))
            left = _dot(jnp.concatenate([kb * row, qb * row], 0).astype(dtype),
                        col.astype(dtype), _NT)                 # [2 s, C]
            bA, bP = left[:_SUB], left[_SUB:]
        else:
            bA = bP = jnp.zeros((_SUB, C), _F32)
        for j in range(_SUB):
            e = jnp.exp(jnp.where(rows >= j, Gb - Gb[j:j + 1], -jnp.inf))
            ke = kb[j:j + 1] * e
            here = lane == lo + j
            bA = jnp.where(here, jnp.sum(kb * ke, 1, keepdims=True), bA)
            bP = jnp.where(here, jnp.sum(qb * ke, 1, keepdims=True), bP)
        rows_A.append(bA)
        rows_P.append(bP)
    i, j = _iota((C, C), 0), _iota((C, C), 1)
    return (jnp.where(i > j, jnp.concatenate(rows_A, 0), 0.0),
            jnp.where(i >= j, jnp.concatenate(rows_P, 0), 0.0))


def _pairs_bwd(q, k, G, dA, dP, dtype):
    """The cotangents (dq, dk, dG) [C, dk] of `_pairs`' operands from
    those of A (strictly lower) and P (lower)."""
    C = k.shape[0]
    rows, tok = _iota((_SUB, 1), 0), _iota((C, 1), 0)
    dk_all = dG_all = jnp.zeros_like(k)
    dq_rows, dk_rows, dG_rows = [], [], []
    for lo in range(0, C, _SUB):
        qb, kb, Gb = (x[lo:lo + _SUB] for x in (q, k, G))
        dAb, dPb = dA[lo:lo + _SUB], dP[lo:lo + _SUB]
        dqb = dkb = dGb = jnp.zeros_like(kb)
        if lo:
            ref = Gb[:1]
            row = jnp.exp(Gb - ref)
            reach = jnp.exp(jnp.where(tok < lo, ref - G, -jnp.inf))
            col = k * reach
            lhs = jnp.concatenate([kb * row, qb * row], 0).astype(dtype)
            d_left = jnp.concatenate([dAb, dPb], 0).astype(dtype)
            d_lhs = _dot(d_left, col.astype(dtype))             # [2 s, dk]
            d_col = _dot(d_left, lhs, _TN)                      # [C, dk]
            dkb, dqb = d_lhs[:_SUB] * row, d_lhs[_SUB:] * row
            z = dkb * kb + dqb * qb
            y = d_col * col
            dk_all = dk_all + d_col * reach
            dG_all = dG_all - y
            dGb = z + jnp.where(
                rows == 0, jnp.sum(y, 0, keepdims=True)
                - jnp.sum(z, 0, keepdims=True), 0.0)
        # token j of the sub-block against its tokens i >= j; what comes
        # back to token j itself (a sum over i) is set aside a row a j
        back = jnp.zeros_like(kb)
        for j in range(_SUB):
            e = jnp.exp(jnp.where(rows >= j, Gb - Gb[j:j + 1], -jnp.inf))
            kj = kb[j:j + 1]
            ke = kj * e
            ca, cp = dAb[:, lo + j:lo + j + 1], dPb[:, lo + j:lo + j + 1]
            te = (ca * kb + cp * qb) * e
            dqb = dqb + cp * ke
            dkb = dkb + ca * ke
            dGb = dGb + te * kj
            back = jnp.where(rows == j, jnp.sum(te, 0, keepdims=True), back)
        dkb = dkb + back
        dGb = dGb - back * kb
        dq_rows.append(dqb)
        dk_rows.append(dkb)
        dG_rows.append(dGb)
    return (jnp.concatenate(dq_rows, 0),
            dk_all + jnp.concatenate(dk_rows, 0),
            dG_all + jnp.concatenate(dG_rows, 0))


def _unit_lower_inverses(Ls):
    """(I + L)^-1 for each strictly lower triangular L [C, C] of the
    list, float32. The diagonal sub-blocks by substitution, every
    sub-block of every L at once (the matrices stacked by rows: column r
    of a sub-block, times its row r, comes off the rows below, which
    leaves no sum in the chain of fifteen steps); then E = D^-1 (L - its
    diagonal sub-blocks) is nilpotent over the sub-blocks, and
    (I + E)^-1 = (I - E)(I + E^2)(I + E^4).. exactly."""
    n, C = len(Ls), Ls[0].shape[0]
    i = jnp.concatenate([_iota((C, C), 0)] * n, 0)
    j = _iota((n * C, C), 1)
    bits = _SUB.bit_length() - 1
    L = jnp.concatenate(Ls, 0)
    N = jnp.where((i >> bits) == (j >> bits), L, 0.0)
    eye = (i == j).astype(_F32)
    D = eye
    for r in range(_SUB - 1):
        col = jnp.sum(jnp.where((j & (_SUB - 1)) == r, N, 0.0), 1,
                      keepdims=True)
        row = jnp.concatenate(
            [jnp.broadcast_to(D[lo + r:lo + r + 1], (_SUB, C))
             for lo in range(0, n * C, _SUB)], 0)
        D = D - col * row
    Ds = [D[h * C:(h + 1) * C] for h in range(n)]
    if C == _SUB:
        return Ds
    eye = eye[:C]
    Es = [_dot(Ds[h], (L - N)[h * C:(h + 1) * C]) for h in range(n)]
    Fs, Eps, m = [eye - E for E in Es], [_dot(E, E) for E in Es], 2
    while m < C // _SUB:                   # F = sum of (-E)^p, p < m
        Fs = [F + _dot(F, Ep) for F, Ep in zip(Fs, Eps)]
        m *= 2
        if m < C // _SUB:
            Eps = [_dot(Ep, Ep) for Ep in Eps]
    return [_dot(F, D_) for F, D_ in zip(Fs, Ds)]


def _heads(ref, heads):
    """Each head's lane slice of a [C, heads * d] block, float32."""
    d = ref.shape[-1] // heads
    return [ref[:, h * d:(h + 1) * d].astype(_F32) for h in range(heads)]


def _each(fn, *lists):
    """`fn` a head. Every stage of the kernels runs over the heads of
    the grid step before the next begins: the heads are independent, so
    one's products run in the shadow of another's."""
    return [fn(*xs) for xs in zip(*lists)]


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, o_ref, *rest, heads,
                scale):
    """One chunk of `heads` heads. `rest`: the backward's residuals (each
    chunk's starting state, A, P, (I + A beta)^-1, U~, W) where they are
    asked for, then the scratch that holds the state."""
    st_ref = rest[-1]

    @pl.when(pl.program_id(2) == 0)
    def _():
        st_ref[...] = jnp.zeros_like(st_ref)

    dtype = q_ref.dtype
    dk, dv = q_ref.shape[-1] // heads, v_ref.shape[-1] // heads
    q, k, v, g = (_heads(r, heads) for r in (q_ref, k_ref, v_ref, g_ref))
    b = [b_ref[:, h:h + 1] for h in range(heads)]
    G = _each(_running_sum, g)
    eG = _each(jnp.exp, G)
    A, P = zip(*_each(lambda q_, k_, G_: _pairs(q_, k_, G_, dtype), q, k, G))
    M = _unit_lower_inverses(_each(jnp.multiply, A, b))
    X = _each(lambda M_, v_, k_, e_, b_: _dot(M_, jnp.concatenate(
        [v_ * b_, k_ * e_ * b_], 1)), M, v, k, eG, b)
    Ut = [x[:, :dv] for x in X]
    W = [x[:, dv:].astype(dtype) for x in X]
    st = [st_ref[h] for h in range(heads)]
    sc = [s.astype(dtype) for s in st]
    U = _each(lambda Ut_, W_, s_: (Ut_ - _dot(W_, s_, _NT)).astype(dtype),
              Ut, W, sc)
    o = _each(lambda q_, e_, s_, P_, U_: _dot((q_ * e_).astype(dtype), s_, _NT)
              + _dot(P_.astype(dtype), U_), q, eG, sc, P, U)
    new = _each(lambda s_, G_, e_, U_, k_: s_ * e_[-1:] + _dot(
        U_, (k_ * jnp.exp(G_[-1:] - G_)).astype(dtype), _TN), st, G, eG, U, k)
    for h in range(heads):
        o_ref[:, h * dv:(h + 1) * dv] = (o[h] * scale).astype(o_ref.dtype)
        st_ref[h] = new[h]
        if len(rest) > 1:
            s0_ref, a_ref, p_ref, m_ref, ut_ref, w_ref = rest[:-1]
            s0_ref[h], a_ref[h], m_ref[h] = st[h], A[h], M[h]
            p_ref[h] = P[h].astype(dtype)
            ut_ref[:, h * dv:(h + 1) * dv] = Ut[h]
            w_ref[:, h * dk:(h + 1) * dk] = W[h]


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, do_ref, s0_ref, a_ref,
                p_ref, m_ref, ut_ref, w_ref, dq_ref, dk_ref, dv_ref, dg_ref,
                db_ref, ds_ref, *, heads, scale):
    """One chunk of the walk back: ds_ref holds the cotangent of the
    state the chunk ENDS with."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        ds_ref[...] = jnp.zeros_like(ds_ref)

    dtype = q_ref.dtype
    dk, dv = q_ref.shape[-1] // heads, v_ref.shape[-1] // heads
    C = q_ref.shape[0]
    i, j = _iota((C, C), 0), _iota((C, C), 1)
    last = _iota((C, 1), 0) == C - 1
    hs = range(heads)
    q, k, v, g, do = (_heads(r, heads)
                      for r in (q_ref, k_ref, v_ref, g_ref, do_ref))
    b = [b_ref[:, h:h + 1] for h in hs]
    G = _each(_running_sum, g)
    eG = _each(jnp.exp, G)
    to_end = _each(lambda G_: jnp.exp(G_[-1:] - G_), G)
    keG, k_end, qg = (_each(jnp.multiply, x, y)
                      for x, y in ((k, eG), (k, to_end), (q, eG)))
    st, ds = [s0_ref[h] for h in hs], [ds_ref[h] for h in hs]
    sc, dsc = ([x.astype(dtype) for x in xs] for xs in (st, ds))
    Ut = [ut_ref[:, h * dv:(h + 1) * dv] for h in hs]
    W = [w_ref[:, h * dk:(h + 1) * dk] for h in hs]
    U = _each(lambda Ut_, W_, s_: (Ut_ - _dot(W_, s_, _NT)).astype(dtype),
              Ut, W, sc)
    dos = [(x * scale).astype(dtype) for x in do]

    dU = _each(lambda h, dos_, ke_, ds_: _dot(p_ref[h], dos_, _TN)
               + _dot(ke_.astype(dtype), ds_, _NT), hs, dos, k_end, dsc)
    dUc = [x.astype(dtype) for x in dU]
    dqg = _each(_dot, dos, sc)
    dk_end = _each(_dot, U, dsc)
    new = _each(lambda ds_, e_, dos_, qg_, dUc_, W_: ds_ * e_[-1:]
                + _dot(dos_, qg_.astype(dtype), _TN) - _dot(dUc_, W_, _TN),
                ds, eG, dos, qg, dUc, W)
    # the solve's cotangent is the transposed solve
    dR = _each(lambda h, dU_, dUc_, s_: _dot(m_ref[h], jnp.concatenate(
        [dU_, -_dot(dUc_, s_)], 1), _TN), hs, dU, dUc, sc)
    dRv, dRw = [x[:, :dv] for x in dR], [x[:, dv:] for x in dR]
    dAb = _each(lambda dRv_, dRw_, Ut_, W_: jnp.where(i > j, -(
        _dot(dRv_.astype(dtype), Ut_.astype(dtype), _NT)
        + _dot(dRw_.astype(dtype), W_, _NT)), 0.0), dRv, dRw, Ut, W)
    dP = _each(lambda dos_, U_: jnp.where(i >= j, _dot(dos_, U_, _NT), 0.0),
               dos, U)
    pairs = _each(lambda q_, k_, G_, dAb_, b_, dP_: _pairs_bwd(
        q_, k_, G_, dAb_ * b_, dP_, dtype), q, k, G, dAb, b, dP)
    for h in hs:
        dq, dk_, dG = pairs[h]
        ks, vs = slice(h * dk, (h + 1) * dk), slice(h * dv, (h + 1) * dv)
        w = dk_end[h] * k_end[h]
        d_end = (jnp.sum(w, 0, keepdims=True)
                 + jnp.sum(ds[h] * st[h], 0, keepdims=True) * eG[h][-1:])
        dG = (dG + dqg[h] * qg[h] + b[h] * keG[h] * dRw[h] - w
              + jnp.where(last, d_end, 0.0))
        ds_ref[h] = new[h]
        dq_ref[:, ks] = (dq + dqg[h] * eG[h]).astype(dq_ref.dtype)
        dk_ref[:, ks] = (dk_ + b[h] * eG[h] * dRw[h] + dk_end[h] * to_end[h]
                         ).astype(dk_ref.dtype)
        dv_ref[:, vs] = (b[h] * dRv[h]).astype(dv_ref.dtype)
        dg_ref[:, ks] = _running_sum(dG, reverse=True)
        db_ref[:, h:h + 1] = (jnp.sum(a_ref[h] * dAb[h], 1, keepdims=True)
                              + jnp.sum(v[h] * dRv[h], 1, keepdims=True)
                              + jnp.sum(keG[h] * dRw[h], 1, keepdims=True))


def _call(kernel, name, backwards, ins, outs, heads, H, N, dv, dk, interpret):
    """`kernel` over the grid (B, H / heads, N). An operand [B, T, H * d]
    goes a chunk's [C, heads * d] block at a time, one by columns
    [B, H / heads, T, heads] a chunk's [C, heads], a residual
    [B, H, N, r, c] a chunk's [heads, r, c]; first chunk first, or last."""
    B = ins[0].shape[0]
    C = ins[0].shape[1] // N

    def at(n):
        return N - 1 - n if backwards else n

    def spec(x):
        if x.ndim == 3:
            return pl.BlockSpec((None, C, x.shape[-1] // H * heads),
                                lambda b, h, n: (b, at(n), h))
        if x.ndim == 4:
            return pl.BlockSpec((None, None, C, heads),
                                lambda b, h, n: (b, h, at(n), 0))
        return pl.BlockSpec((None, heads, None) + x.shape[3:],
                            lambda b, h, n: (b, h, at(n), 0, 0))

    return pl.pallas_call(
        kernel, grid=(B, H // heads, N), in_specs=[spec(x) for x in ins],
        out_specs=[spec(x) for x in outs], out_shape=outs,
        scratch_shapes=[pltpu.VMEM((heads, dv, dk), _F32)],
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret, name=name)(*ins)


# jitted, so that a model's layers share one trace and one lowering of a
# kernel (its body is unrolled over heads, sub-blocks and tokens)
@functools.partial(jax.jit, static_argnames=("chunk", "scale", "interpret",
                                             "residuals"))
def _fused_fwd(q, k, v, g, beta, chunk, scale, interpret, residuals=True):
    """o, and what the backward keeps. q, k, g [B, T, H, dk], v
    [B, T, H, dv], beta [B, T, H]."""
    B, T, H, dk = q.shape
    dv = v.shape[-1]
    pad = -T % chunk
    Tp = T + pad
    N = Tp // chunk
    heads = max(n for n in range(1, _HEADS + 1) if H % n == 0)

    def wide(x):
        """[B, T, H, d] -> [B, Tp, H * d]; padded tokens are zeros:
        beta = 0 and g = 0 leave the state as it is."""
        x = x.reshape(B, T, -1)
        return jnp.pad(x, ((0, 0), (0, pad), (0, 0))) if pad else x

    def cols(x):
        """[B, T, H] -> [B, H / heads, Tp, heads]."""
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0))) if pad else x
        return jnp.moveaxis(x.reshape(B, Tp, H // heads, heads), 2, 1)

    ins = (wide(q), wide(k), wide(v), wide(g.astype(_F32)),
           cols(beta.astype(_F32)))
    sds = jax.ShapeDtypeStruct
    outs = [sds((B, Tp, H * dv), v.dtype)]
    if residuals:
        outs += [sds((B, H, N, dv, dk), _F32), sds((B, H, N, chunk, chunk), _F32),
                 sds((B, H, N, chunk, chunk), q.dtype),
                 sds((B, H, N, chunk, chunk), _F32),
                 sds((B, Tp, H * dv), _F32), sds((B, Tp, H * dk), q.dtype)]
    o, *res = _call(
        functools.partial(_fwd_kernel, heads=heads, scale=scale),
        "kda_chunk_states", False, ins, outs, heads, H, N, dv, dk, interpret)
    return o[:, :T].reshape(B, T, H, dv), (ins, tuple(res))


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _fused_bwd(chunk, scale, interpret, saved, do):
    ins, res = saved
    (B, T, H, dv), Tp = do.shape, ins[0].shape[1]
    dk = ins[0].shape[-1] // H
    N, heads = Tp // chunk, ins[4].shape[-1]
    do = do.reshape(B, T, H * dv)
    if Tp > T:
        do = jnp.pad(do, ((0, 0), (0, Tp - T), (0, 0)))
    q, k, v, g, b = ins
    sds = jax.ShapeDtypeStruct
    outs = [sds(q.shape, q.dtype), sds(k.shape, k.dtype), sds(v.shape, v.dtype),
            sds(g.shape, _F32), sds(b.shape, _F32)]
    dq, dk_, dv_, dg, db = _call(
        functools.partial(_bwd_kernel, heads=heads, scale=scale),
        "kda_chunk_states_bwd", True, ins + (do,) + res, outs, heads, H, N,
        dv, dk, interpret)
    db = jnp.moveaxis(db, 1, 2).reshape(B, Tp, H)
    return tuple(x[:, :T].reshape(B, T, H, -1) for x in (dq, dk_, dv_, dg)
                 ) + (db[:, :T],)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _fused(q, k, v, g, beta, chunk, scale, interpret=False):
    """The operator through the two kernels; `interpret` runs them in the
    Pallas interpreter (the tests' way, on the CPU)."""
    return _fused_fwd(q, k, v, g, beta, chunk, scale, interpret,
                      residuals=False)[0]


_fused.defvjp(_fused_fwd, _fused_bwd)


def chunk_gated_delta_rule(q, k, v, g, beta, *, chunk=64, scale=None):
    """q, k, g [B, T, H, dk], v [B, T, H, dv], beta [B, T, H]; g is the
    LOG of the per-channel decay (<= 0). Returns o [B, T, H, dv] in v's
    dtype, from a zero initial state. T need not divide by `chunk`."""
    if chunk % _SUB:
        raise ValueError(f"chunk {chunk} is not a multiple of {_SUB}")
    B, T, H, dk = q.shape
    dv = v.shape[-1]
    scale = 1.0 / math.sqrt(dk) if scale is None else scale
    if _on_tpu() and dk % LANES == 0 and dv % LANES == 0:
        return _fused(q, k, v, g, beta, chunk, scale, False)
    dtype, out_dtype, f32 = q.dtype, v.dtype, jnp.float32
    pad = -T % chunk
    N = (T + pad) // chunk

    def chunks(x):
        """[B, T, H, ...] -> [N, B, H, C, ...] float32; padded tokens are
        zeros: beta = 0 and g = 0 leave the state as it is."""
        x = x.astype(f32)
        if pad:
            x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        x = x.reshape((B, N, chunk) + x.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(x, 3, 1), 2, 0)

    q, k, v, g = chunks(q), chunks(k), chunks(v), chunks(g)
    b = chunks(beta)[..., None]                          # [N, B, H, C, 1]
    G = jnp.cumsum(g, axis=-2)
    eG = jnp.exp(G)
    A, P = _pair_blocks(q, k, G, dtype)
    X = jax.scipy.linalg.solve_triangular(
        A * b + jnp.eye(chunk, dtype=f32),
        jnp.concatenate([v * b, k * eG * b], -1),
        lower=True, unit_diagonal=True)
    Ut, W = X[..., :dv], X[..., dv:]
    G_end = G[..., -1:, :]
    k_end = k * jnp.exp(G_end - G)
    decay = jnp.exp(G_end[..., 0, :])                    # [N, B, H, dk]

    S0t, U = _states_scan(Ut, W.astype(dtype), k_end.astype(dtype), decay)
    o = (_mm(q * eG, _t(S0t), dtype) + _mm(P, U, dtype)) * scale
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 2), 1, 3)        # [B, N, C, H, dv]
    return o.reshape(B, N * chunk, H, dv)[:, :T].astype(out_dtype)
