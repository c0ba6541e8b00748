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

so everything but U, o and S_C's dependence on S_0 is computed for all
chunks at once, and only three small products a chunk run in sequence:
on a TPU in ONE Pallas kernel a pass (`kda_chunk_states`, the state held
in VMEM across a head's chunks, and `kda_chunk_states_bwd`, the same walk
backwards), elsewhere a `lax.scan` that jax transposes. A scan costs the
device six to thirteen tiny operations a chunk: at 32768 tokens that was
nine tenths of the operations of a whole training step. exp(G_i - G_j) is never split into exp(G_i) exp(-G_j),
which overflows under a strong decay: between 16-token sub-blocks it is
split at the later sub-block's first token (both factors <= 1), and
inside a sub-block it is computed pairwise.

The backward is jax's transpose of this chunked forward (the kernel's is
written out beside it): each chunk's starting state is kept, nothing is
approximated. Callers bound its
memory by the heads they pass at once (`models/solar_open2.py` passes a
group of heads under `jax.checkpoint`).

Matmul operands take the dtype of `q` (bf16 in a bf16 model, as the MXU
would round them anyway; float32 stays float32), accumulation, the
decays and the state are float32. All but the kernel is plain
`jax.numpy`, which XLA compiles for the chip and for the CPU alike.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._tpu import LANES, SUBLANES
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


# -- the sequential part: U and each chunk's starting state --------------------
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


_NT = (((1,), (1,)), ((), ()))     # a @ b^T
_TN = (((0,), (0,)), ((), ()))     # a^T @ b


def _dot(a, b, dims=(((1,), (0,)), ((), ()))):
    return jax.lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)


def _states_kernel(ut_ref, w_ref, k_ref, d_ref, s0_ref, u_ref, st_ref):
    @pl.when(pl.program_id(1) == 0)
    def _():
        st_ref[...] = jnp.zeros_like(st_ref)

    st = st_ref[...]
    s0_ref[...] = st
    u = (ut_ref[...] - _dot(w_ref[...], st.astype(w_ref.dtype), _NT)
         ).astype(u_ref.dtype)
    u_ref[...] = u
    st_ref[...] = st * d_ref[...] + _dot(u, k_ref[...], _TN)


def _states_bwd_kernel(w_ref, k_ref, d_ref, s0_ref, u_ref, gs_ref, gu_ref,
                       dut_ref, dw_ref, dk_ref, dd_ref, g_ref):
    """One chunk of the walk back: g_ref holds the cotangent of the state
    the chunk ENDS with."""
    @pl.when(pl.program_id(1) == 0)
    def _():
        g_ref[...] = jnp.zeros_like(g_ref)

    dtype = w_ref.dtype
    g, st = g_ref[...], s0_ref[...]
    gb = g.astype(dtype)
    du = (gu_ref[...].astype(jnp.float32) + _dot(k_ref[...], gb, _NT)
          ).astype(dtype)
    dut_ref[...] = du.astype(jnp.float32)
    dk_ref[...] = _dot(u_ref[...], gb).astype(dtype)
    dw_ref[...] = (-_dot(du, st.astype(dtype))).astype(dtype)
    dd_ref[...] = jnp.sum(g * st, axis=0, keepdims=True)
    g_ref[...] = gs_ref[...] + g * d_ref[...] - _dot(du, w_ref[...], _TN)


def _states_call(kernel, name, backwards, ins, outs, dv, dk):
    """`kernel` over the grid (heads, chunks), each operand [N, heads, r, c]
    a chunk's [r, c] block at a time, first chunk first or last."""
    N, heads = ins[0].shape[:2]
    interpret = not _on_tpu()        # the tests' route, through the CPU

    def spec(x):
        return pl.BlockSpec(
            (None, None) + x.shape[2:],
            (lambda h, n: (N - 1 - n, h, 0, 0)) if backwards
            else (lambda h, n: (n, h, 0, 0)))

    return pl.pallas_call(
        kernel, grid=(heads, N), in_specs=[spec(x) for x in ins],
        out_specs=[spec(x) for x in outs], out_shape=outs,
        scratch_shapes=[pltpu.VMEM((dv, dk), jnp.float32)],
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret, name=name)(*ins)


def _flat(x):
    """[N, B, H, r, c] -> [N, B * H, r, c]."""
    return x.reshape((x.shape[0], -1) + x.shape[3:])


def _states_fwd(Ut, W, k_end, decay):
    N, B, H, C, dv = Ut.shape
    dk = W.shape[-1]
    f32 = jnp.float32
    S0t, U = _states_call(
        _states_kernel, "kda_chunk_states", False,
        (_flat(Ut), _flat(W), _flat(k_end), _flat(decay[..., None, :])),
        (jax.ShapeDtypeStruct((N, B * H, dv, dk), f32),
         jax.ShapeDtypeStruct((N, B * H, C, dv), W.dtype)), dv, dk)
    return (S0t.reshape(N, B, H, dv, dk), U.reshape(N, B, H, C, dv)), (
        W, k_end, decay, S0t, U)


def _states_bwd(res, cts):
    W, k_end, decay, S0t, U = res
    N, B, H, C, dk = W.shape
    dv = U.shape[-1]
    f32 = jnp.float32
    gS, gU = (_flat(c) for c in cts)
    dUt, dW, dK, dD = _states_call(
        _states_bwd_kernel, "kda_chunk_states_bwd", True,
        (_flat(W), _flat(k_end), _flat(decay[..., None, :]), S0t, U,
         gS.astype(f32), gU.astype(W.dtype)),
        (jax.ShapeDtypeStruct((N, B * H, C, dv), f32),
         jax.ShapeDtypeStruct((N, B * H, C, dk), W.dtype),
         jax.ShapeDtypeStruct((N, B * H, C, dk), W.dtype),
         jax.ShapeDtypeStruct((N, B * H, 1, dk), f32)), dv, dk)
    return (dUt.reshape(N, B, H, C, dv), dW.reshape(W.shape),
            dK.reshape(W.shape), dD.reshape(decay.shape))


@jax.custom_vjp
def _states_pallas(Ut, W, k_end, decay):
    return _states_fwd(Ut, W, k_end, decay)[0]


_states_pallas.defvjp(_states_fwd, _states_bwd)


def _chunk_states(Ut, W, k_end, decay):
    """Ut [N, B, H, C, dv] float32, W and k_end [N, B, H, C, dk] in the
    matmul dtype, decay [N, B, H, dk] float32 -> (S0t [N, B, H, dv, dk]
    float32: the TRANSPOSED state each chunk starts from, U [N, B, H, C,
    dv] in the matmul dtype), from a zero state."""
    C, dv = Ut.shape[-2:]
    dk = W.shape[-1]
    if (_on_tpu() and C % SUBLANES == 0 and dk % LANES == 0
            and dv % LANES == 0):
        return _states_pallas(Ut, W, k_end, decay)
    return _states_scan(Ut, W, k_end, decay)


def chunk_gated_delta_rule(q, k, v, g, beta, *, chunk=64, scale=None):
    """q, k, g [B, T, H, dk], v [B, T, H, dv], beta [B, T, H]; g is the
    LOG of the per-channel decay (<= 0). Returns o [B, T, H, dv] in v's
    dtype, from a zero initial state. T need not divide by `chunk`."""
    if chunk % _SUB:
        raise ValueError(f"chunk {chunk} is not a multiple of {_SUB}")
    B, T, H, dk = q.shape
    dv = v.shape[-1]
    scale = 1.0 / math.sqrt(dk) if scale is None else scale
    dtype, f32 = q.dtype, jnp.float32
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

    S0t, U = _chunk_states(Ut, W.astype(dtype), k_end.astype(dtype), decay)
    o = (_mm(q * eG, _t(S0t), dtype) + _mm(P, U, dtype)) * scale
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 2), 1, 3)        # [B, N, C, H, dv]
    return o.reshape(B, N * chunk, H, dv)[:, :T].astype(v.dtype)
