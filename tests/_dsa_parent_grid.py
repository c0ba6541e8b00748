"""The grid `kernels/sparse_select_attention.py` gave its four tile-walking
kernels before ISSUE 35, kept as the tests' reference: one head a grid step
over EVERY (row block, key block) pair of the sequence, 256 rows by 512
keys, the body of a tile above the diagonal skipped. The bodies, the blocks
and the order a row's key blocks are visited in are PR 33's, so the live
tile walk has to give the same bits (`tests/test_sparse_select_attention.py`).
Not a test file."""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.kernels.sparse_select_attention import (
    LANES, NEG, _F32, _NT, _blocks, _diag, _params, _scores_of)


def _core_fwd_kernel(qn_ref, qr_ref, kn_ref, kr_ref, v_ref, mask_ref, o_ref,
                     lse_ref, m_scr, l_scr, acc_scr, *, scale, bq, bk, nk):
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def init():
        m_scr[...] = jnp.full(m_scr.shape, NEG, _F32)
        l_scr[...] = jnp.zeros(l_scr.shape, _F32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, _F32)

    @pl.when(j <= _diag(i, bq, bk))
    def run():
        keep = mask_ref[...].astype(jnp.int32) != 0
        s = _scores_of(qn_ref[...], qr_ref[...], kn_ref[...], kr_ref[...],
                       keep, scale)
        m_prev = m_scr[...]
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_next[:, :1])
        alpha = jnp.exp(m_prev - m_next)
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
        m_scr[...] = m_next
        acc_scr[...] = alpha[:, :1] * acc_scr[...] + jnp.dot(
            p.astype(v_ref.dtype), v_ref[...], preferred_element_type=_F32)

    @pl.when(j == nk - 1)
    def end():
        l = l_scr[...]
        o_ref[...] = (acc_scr[...] / l[:, :1]).astype(o_ref.dtype)
        lse_ref[...] = m_scr[...] + jnp.log(l)


def _kv_maps(bq, bk):
    """Index maps of the key-side blocks for grid (head, rows, keys):
    a block above the diagonal is not fetched (the map repeats the last
    block the rows see)."""
    def last(i, j):
        return jnp.minimum(j, _diag(i, bq, bk))
    return (lambda h, i, j: (h, last(i, j), 0),
            lambda h, i, j: (last(i, j), 0),
            lambda h, i, j: (i, last(i, j)))


def _core_fwd_fused(qn, qr, kn, kr, v, mask, scale, interpret=False):
    H, S, dn = qn.shape
    dr, dv = qr.shape[-1], v.shape[-1]
    bq, bk = _blocks(S, 256, 512)
    per_head, shared, mask_map = _kv_maps(bq, bk)
    o, lse = pl.pallas_call(
        functools.partial(_core_fwd_kernel, scale=scale, bq=bq, bk=bk,
                          nk=S // bk),
        grid=(H, S // bq, S // bk),
        in_specs=[pl.BlockSpec((None, bq, dn), lambda h, i, j: (h, i, 0)),
                  pl.BlockSpec((None, bq, dr), lambda h, i, j: (h, i, 0)),
                  pl.BlockSpec((None, bk, dn), per_head),
                  pl.BlockSpec((bk, dr), shared),
                  pl.BlockSpec((None, bk, dv), per_head),
                  pl.BlockSpec((bq, bk), mask_map)],
        out_specs=[pl.BlockSpec((None, bq, dv), lambda h, i, j: (h, i, 0)),
                   pl.BlockSpec((None, bq, LANES),
                                lambda h, i, j: (h, i, 0))],
        out_shape=[jax.ShapeDtypeStruct((H, S, dv), v.dtype),
                   jax.ShapeDtypeStruct((H, S, LANES), _F32)],
        scratch_shapes=[pltpu.VMEM((bq, LANES), _F32),
                        pltpu.VMEM((bq, LANES), _F32),
                        pltpu.VMEM((bq, dv), _F32)],
        compiler_params=_params("parallel", "parallel", "arbitrary"),
        name="dsa_core_fwd", interpret=interpret,
    )(qn, qr, kn, kr, v, mask)
    return o, lse[..., 0]


def _p_and_ds(qn_ref, qr_ref, kn_ref, kr_ref, v_ref, mask_ref, lse_ref,
              do_ref, di_ref, scale):
    keep = mask_ref[...].astype(jnp.int32) != 0
    s = _scores_of(qn_ref[...], qr_ref[...], kn_ref[...], kr_ref[...], keep,
                   scale)
    p = jnp.exp(s - jnp.expand_dims(lse_ref[0], -1))
    dp = jax.lax.dot_general(do_ref[...], v_ref[...], _NT,
                             preferred_element_type=_F32)
    ds = p * (dp - jnp.expand_dims(di_ref[0], -1)) * scale
    return p, ds


def _core_dq_kernel(qn_ref, qr_ref, kn_ref, kr_ref, v_ref, mask_ref, lse_ref,
                    do_ref, di_ref, dqn_ref, dqr_ref, dqn_scr, dqr_scr, *,
                    scale, bq, bk, nk):
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def init():
        dqn_scr[...] = jnp.zeros(dqn_scr.shape, _F32)
        dqr_scr[...] = jnp.zeros(dqr_scr.shape, _F32)

    @pl.when(j <= _diag(i, bq, bk))
    def run():
        _, ds = _p_and_ds(qn_ref, qr_ref, kn_ref, kr_ref, v_ref, mask_ref,
                          lse_ref, do_ref, di_ref, scale)
        ds = ds.astype(kn_ref.dtype)
        dqn_scr[...] += jnp.dot(ds, kn_ref[...], preferred_element_type=_F32)
        dqr_scr[...] += jnp.dot(ds, kr_ref[...], preferred_element_type=_F32)

    @pl.when(j == nk - 1)
    def end():
        dqn_ref[...] = dqn_scr[...].astype(dqn_ref.dtype)
        dqr_ref[...] = dqr_scr[...].astype(dqr_ref.dtype)


def _core_dkv_kernel(qn_ref, qr_ref, kn_ref, kr_ref, v_ref, mask_ref,
                     lse_ref, do_ref, di_ref, dkn_ref, dv_ref, dkr_ref,
                     dkn_scr, dv_scr, dkr_scr, *, scale, bq, bk, nq, H):
    j, h, i = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when((h == 0) & (i == 0))
    def init_shared():
        dkr_scr[...] = jnp.zeros(dkr_scr.shape, _F32)

    @pl.when(i == 0)
    def init():
        dkn_scr[...] = jnp.zeros(dkn_scr.shape, _F32)
        dv_scr[...] = jnp.zeros(dv_scr.shape, _F32)

    @pl.when(j <= _diag(i, bq, bk))
    def run():
        p, ds = _p_and_ds(qn_ref, qr_ref, kn_ref, kr_ref, v_ref, mask_ref,
                          lse_ref, do_ref, di_ref, scale)
        dv_scr[...] += jnp.dot(p.T.astype(do_ref.dtype), do_ref[...],
                               preferred_element_type=_F32)
        dst = ds.T.astype(qn_ref.dtype)
        dkn_scr[...] += jnp.dot(dst, qn_ref[...], preferred_element_type=_F32)
        dkr_scr[...] += jnp.dot(dst, qr_ref[...], preferred_element_type=_F32)

    @pl.when(i == nq - 1)
    def end():
        dkn_ref[...] = dkn_scr[...].astype(dkn_ref.dtype)
        dv_ref[...] = dv_scr[...].astype(dv_ref.dtype)

    @pl.when((h == H - 1) & (i == nq - 1))
    def end_shared():
        dkr_ref[...] = dkr_scr[...]


def _core_bwd_fused(qn, qr, kn, kr, v, mask, o, lse, do, scale,
                    interpret=False):
    H, S, dn = qn.shape
    dr, dv = qr.shape[-1], v.shape[-1]
    bq, bk = _blocks(S, 256, 512)
    nq, nk = S // bq, S // bk
    di = jnp.sum(o.astype(_F32) * do.astype(_F32), axis=-1)[:, None, :]
    lse3 = lse[:, None, :]
    per_head, shared, mask_map = _kv_maps(bq, bk)
    row = lambda h, i, j: (h, i, 0)
    vec = lambda h, i, j: (h, 0, i)
    dqn, dqr = pl.pallas_call(
        functools.partial(_core_dq_kernel, scale=scale, bq=bq, bk=bk, nk=nk),
        grid=(H, nq, nk),
        in_specs=[pl.BlockSpec((None, bq, dn), row),
                  pl.BlockSpec((None, bq, dr), row),
                  pl.BlockSpec((None, bk, dn), per_head),
                  pl.BlockSpec((bk, dr), shared),
                  pl.BlockSpec((None, bk, dv), per_head),
                  pl.BlockSpec((bq, bk), mask_map),
                  pl.BlockSpec((None, 1, bq), vec),
                  pl.BlockSpec((None, bq, dv), row),
                  pl.BlockSpec((None, 1, bq), vec)],
        out_specs=[pl.BlockSpec((None, bq, dn), row),
                   pl.BlockSpec((None, bq, dr), row)],
        out_shape=[jax.ShapeDtypeStruct(qn.shape, qn.dtype),
                   jax.ShapeDtypeStruct(qr.shape, qr.dtype)],
        scratch_shapes=[pltpu.VMEM((bq, dn), _F32),
                        pltpu.VMEM((bq, dr), _F32)],
        compiler_params=_params("parallel", "parallel", "arbitrary"),
        name="dsa_core_bwd_dq", interpret=interpret,
    )(qn, qr, kn, kr, v, mask, lse3, do, di)

    # grid (keys, head, rows): the rope key's gradient sums over the heads
    # too, so the heads turn inside a block of keys; rows before the
    # block's first are not fetched
    def first(j, i):
        return jnp.maximum(i, (j * bk) // bq)

    qrow = lambda j, h, i: (h, first(j, i), 0)
    qvec = lambda j, h, i: (h, 0, first(j, i))
    krow = lambda j, h, i: (h, j, 0)
    dkn, dvv, dkr = pl.pallas_call(
        functools.partial(_core_dkv_kernel, scale=scale, bq=bq, bk=bk,
                          nq=nq, H=H),
        grid=(nk, H, nq),
        in_specs=[pl.BlockSpec((None, bq, dn), qrow),
                  pl.BlockSpec((None, bq, dr), qrow),
                  pl.BlockSpec((None, bk, dn), krow),
                  pl.BlockSpec((bk, dr), lambda j, h, i: (j, 0)),
                  pl.BlockSpec((None, bk, dv), krow),
                  pl.BlockSpec((bq, bk), lambda j, h, i: (first(j, i), j)),
                  pl.BlockSpec((None, 1, bq), qvec),
                  pl.BlockSpec((None, bq, dv), qrow),
                  pl.BlockSpec((None, 1, bq), qvec)],
        out_specs=[pl.BlockSpec((None, bk, dn), krow),
                   pl.BlockSpec((None, bk, dv), krow),
                   pl.BlockSpec((bk, dr), lambda j, h, i: (j, 0))],
        out_shape=[jax.ShapeDtypeStruct(kn.shape, kn.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct(kr.shape, _F32)],
        scratch_shapes=[pltpu.VMEM((bk, dn), _F32),
                        pltpu.VMEM((bk, dv), _F32),
                        pltpu.VMEM((bk, dr), _F32)],
        compiler_params=_params("parallel", "arbitrary", "arbitrary"),
        name="dsa_core_bwd_dkv", interpret=interpret,
    )(qn, qr, kn, kr, v, mask, lse3, do, di)
    return dqn, dqr, dkn, dkr.astype(kr.dtype), dvv


def _head_probs_kernel(qn_ref, qr_ref, kn_ref, kr_ref, lse_ref, mask_ref,
                       *rest, scale, bq, bk, H):
    acc_ref = rest[0] if len(rest) == 3 else None
    o_ref, scr = rest[-2:]
    i, j, h = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    live = j <= _diag(i, bq, bk)

    @pl.when(h == 0)
    def init():
        scr[...] = (jnp.zeros(scr.shape, _F32) if acc_ref is None
                    else acc_ref[...])

    @pl.when(live)
    def run():
        keep = mask_ref[...].astype(jnp.int32) != 0
        s = _scores_of(qn_ref[...], qr_ref[...], kn_ref[...], kr_ref[...],
                       keep, scale)
        scr[...] += jnp.exp(s - jnp.expand_dims(lse_ref[0], -1))

    @pl.when(h == H - 1)
    def end():
        o_ref[...] = scr[...]


def _head_probs_fused(qn, qr, kn, kr, lse, mask, scale, acc,
                      interpret=False):
    H, S, dn = qn.shape
    dr = qr.shape[-1]
    bq, bk = _blocks(S, 256, 512)

    def last(i, j):
        return jnp.minimum(j, _diag(i, bq, bk))

    tile = pl.BlockSpec((bq, bk), lambda i, j, h: (i, j))
    more = () if acc is None else (acc,)        # None: start from nothing
    return pl.pallas_call(
        functools.partial(_head_probs_kernel, scale=scale, bq=bq, bk=bk,
                          H=H),
        grid=(S // bq, S // bk, H),
        in_specs=[pl.BlockSpec((None, bq, dn), lambda i, j, h: (h, i, 0)),
                  pl.BlockSpec((None, bq, dr), lambda i, j, h: (h, i, 0)),
                  pl.BlockSpec((None, bk, dn),
                               lambda i, j, h: (h, last(i, j), 0)),
                  pl.BlockSpec((bk, dr), lambda i, j, h: (last(i, j), 0)),
                  pl.BlockSpec((None, 1, bq), lambda i, j, h: (h, 0, i)),
                  tile] + [tile] * len(more),
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((S, S), _F32),
        scratch_shapes=[pltpu.VMEM((bq, bk), _F32)],
        input_output_aliases={6: 0} if more else {},
        compiler_params=_params("parallel", "parallel", "arbitrary"),
        name="dsa_head_probs", interpret=interpret,
    )(qn, qr, kn, kr, lse[:, None, :], mask, *more)
