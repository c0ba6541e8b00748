"""Loss functionals (ref: python/paddle/nn/functional/loss.py 4.3k LoC)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ...autograd.tape import apply_op
from ...framework import core
from ...tensor import Tensor
from ...ops._helpers import to_tensor_like, unwrap

__all__ = [
    "margin_cross_entropy",
    "cross_entropy", "softmax_with_cross_entropy", "binary_cross_entropy",
    "binary_cross_entropy_with_logits", "mse_loss", "l1_loss", "nll_loss",
    "smooth_l1_loss", "kl_div", "margin_ranking_loss", "hinge_embedding_loss",
    "cosine_embedding_loss", "triplet_margin_loss",
    "triplet_margin_with_distance_loss", "multi_label_soft_margin_loss",
    "soft_margin_loss", "sigmoid_focal_loss", "dice_loss", "log_loss",
    "square_error_cost", "ctc_loss", "poisson_nll_loss", "gaussian_nll_loss",
    "multi_margin_loss", "hsigmoid_loss", "npair_loss", "rnnt_loss",
    "linear_cross_entropy",
]


def _reduce(val, reduction):
    if reduction == "mean":
        return jnp.mean(val)
    if reduction == "sum":
        return jnp.sum(val)
    return val


def cross_entropy(input, label, weight=None, ignore_index=-100,
                  reduction="mean", soft_label=False, axis=-1,
                  use_softmax=True, label_smoothing=0.0, name=None):
    """ref: python/paddle/nn/functional/loss.py::cross_entropy +
    phi softmax_with_cross_entropy kernel. One fused logsumexp path on TPU."""
    args = [to_tensor_like(input), to_tensor_like(label)]
    if weight is not None:
        args.append(to_tensor_like(weight))

    def f(logits, label, *rest):
        ax = axis % logits.ndim
        n_class = logits.shape[ax]
        is_soft = soft_label or (label.ndim == logits.ndim
                                 and label.shape[ax] == n_class
                                 and jnp.issubdtype(label.dtype,
                                                    jnp.floating))
        if (not is_soft and use_softmax and not rest
                and label_smoothing == 0 and ax == logits.ndim - 1):
            # big-vocab hard-label fast path: blockwise Pallas kernel, no
            # [N, V] f32 log-softmax materialization (kernels/cross_entropy)
            from ...kernels import cross_entropy as _fck
            if _fck.supported(n_class):
                lbl = label
                if lbl.ndim == logits.ndim and lbl.shape[ax] == 1:
                    lbl = jnp.squeeze(lbl, ax)
                lbl = lbl.astype(jnp.int32)
                from ...distributed.sharding import shard_kernel
                from jax.sharding import PartitionSpec as _P
                flat = logits.reshape(-1, n_class)
                loss = shard_kernel(
                    lambda x, y: _fck.fused_cross_entropy(
                        x, y, ignore_index),
                    (_P("data", None), _P("data")), _P("data"),
                    batch=flat.shape[0])(flat, lbl.reshape(-1))
                loss = loss.reshape(lbl.shape)
                if reduction == "mean":
                    nvalid = jnp.sum((lbl != ignore_index).astype(
                        jnp.float32))
                    return jnp.sum(loss) / jnp.maximum(nvalid, 1.0)
                return _reduce(loss, reduction)
        if use_softmax:
            logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=ax)
        else:
            logp = jnp.log(jnp.maximum(logits.astype(jnp.float32), 1e-30))
        if soft_label or (label.ndim == logits.ndim
                          and label.shape[ax] == n_class
                          and jnp.issubdtype(label.dtype, jnp.floating)):
            soft = label.astype(jnp.float32)
            if label_smoothing > 0:
                soft = soft * (1 - label_smoothing) + label_smoothing / n_class
            loss = -jnp.sum(soft * logp, axis=ax)
            if rest:
                w = jnp.sum(soft * rest[0].astype(jnp.float32), axis=ax)
                loss = loss * w
                if reduction == "mean":
                    return jnp.sum(loss) / jnp.maximum(jnp.sum(w), 1e-12)
            return _reduce(loss, reduction)
        lbl = label
        if lbl.ndim == logits.ndim and lbl.shape[ax] == 1:
            lbl = jnp.squeeze(lbl, ax)
        lbl = lbl.astype(jnp.int32)
        valid = lbl != ignore_index
        safe = jnp.where(valid, lbl, 0)
        picked = jnp.take_along_axis(logp, safe[..., None] if ax == logits.ndim - 1
                                     else jnp.expand_dims(safe, ax), axis=ax)
        picked = jnp.squeeze(picked, ax)
        if label_smoothing > 0:
            smooth = jnp.mean(logp, axis=ax)
            picked = (1 - label_smoothing) * picked + label_smoothing * smooth
        loss = jnp.where(valid, -picked, 0.0)
        if rest:
            w = rest[0].astype(jnp.float32)[safe] * valid.astype(jnp.float32)
            loss = loss * w
            if reduction == "mean":
                return jnp.sum(loss) / jnp.maximum(jnp.sum(w), 1e-12)
        if reduction == "mean":
            return jnp.sum(loss) / jnp.maximum(jnp.sum(valid.astype(jnp.float32)),
                                               1.0)
        return _reduce(loss, reduction)

    return apply_op(f, *args, name="cross_entropy")


def softmax_with_cross_entropy(logits, label, soft_label=False,
                               ignore_index=-100, numeric_stable_mode=True,
                               return_softmax=False, axis=-1):
    loss = cross_entropy(logits, label, soft_label=soft_label,
                         ignore_index=ignore_index, reduction="none", axis=axis)
    loss = apply_op(_expand_dims_k, loss, ax=int(axis))
    if return_softmax:
        from .activation import softmax as _softmax
        return loss, _softmax(logits, axis=axis)
    return loss


def binary_cross_entropy(input, label, weight=None, reduction="mean", name=None):
    args = [to_tensor_like(input), to_tensor_like(label)]
    if weight is not None:
        args.append(to_tensor_like(weight))

    def f(p, y, *rest):
        p = jnp.clip(p.astype(jnp.float32), 1e-12, 1.0 - 1e-7)
        loss = -(y * jnp.log(p) + (1 - y) * jnp.log1p(-p))
        if rest:
            loss = loss * rest[0]
        return _reduce(loss, reduction)
    return apply_op(f, *args, name="bce")


def binary_cross_entropy_with_logits(logit, label, weight=None,
                                     reduction="mean", pos_weight=None,
                                     name=None):
    args = [to_tensor_like(logit), to_tensor_like(label)]
    has_w = weight is not None
    has_pw = pos_weight is not None
    if has_w:
        args.append(to_tensor_like(weight))
    if has_pw:
        args.append(to_tensor_like(pos_weight))

    def f(x, y, *rest):
        x = x.astype(jnp.float32)
        y = y.astype(jnp.float32)
        i = 0
        w = None
        pw = None
        if has_w:
            w = rest[i]; i += 1
        if has_pw:
            pw = rest[i]
        # log(1+e^-|x|) stable form with optional pos_weight
        if pw is not None:
            log_w = (pw - 1) * y + 1
            loss = (1 - y) * x + log_w * (jnp.logaddexp(0.0, -jnp.abs(x))
                                          + jnp.maximum(-x, 0.0))
        else:
            loss = jnp.maximum(x, 0.0) - x * y + jnp.logaddexp(0.0, -jnp.abs(x))
        if w is not None:
            loss = loss * w
        return _reduce(loss, reduction)
    return apply_op(f, *args, name="bce_logits")


def _expand_dims_k(a, *, ax):
    return jnp.expand_dims(a, ax)


def _mse_k(a, b, *, reduction):
    return _reduce((a - b) ** 2, reduction)


def mse_loss(input, label, reduction="mean", name=None):
    return apply_op(_mse_k, to_tensor_like(input), to_tensor_like(label),
                    name="mse", reduction=reduction)


def _sq_err_k(a, b):
    return (a - b) ** 2


def square_error_cost(input, label):
    return apply_op(_sq_err_k, to_tensor_like(input), to_tensor_like(label))


def _l1_k(a, b, *, reduction):
    return _reduce(jnp.abs(a - b), reduction)


def l1_loss(input, label, reduction="mean", name=None):
    return apply_op(_l1_k, to_tensor_like(input), to_tensor_like(label),
                    name="l1", reduction=reduction)


def nll_loss(input, label, weight=None, ignore_index=-100, reduction="mean",
             name=None):
    args = [to_tensor_like(input), to_tensor_like(label)]
    if weight is not None:
        args.append(to_tensor_like(weight))

    def f(logp, y, *rest):
        y = y.astype(jnp.int32)
        valid = y != ignore_index
        safe = jnp.where(valid, y, 0)
        picked = jnp.take_along_axis(logp, jnp.expand_dims(safe, 1), axis=1)
        picked = jnp.squeeze(picked, 1)
        w = (rest[0].astype(jnp.float32)[safe] if rest
             else jnp.ones_like(picked))
        w = w * valid.astype(jnp.float32)
        loss = -picked * w
        if reduction == "mean":
            return jnp.sum(loss) / jnp.maximum(jnp.sum(w), 1e-12)
        return _reduce(loss, reduction)
    return apply_op(f, *args, name="nll")


def smooth_l1_loss(input, label, reduction="mean", delta=1.0, name=None):
    def f(a, b):
        d = jnp.abs(a - b)
        loss = jnp.where(d < delta, 0.5 * d * d / delta, d - 0.5 * delta)
        # paddle multiplies by delta to get huber
        return _reduce(loss * delta, reduction)
    return apply_op(f, to_tensor_like(input), to_tensor_like(label),
                    name="smooth_l1")


def kl_div(input, label, reduction="mean", log_target=False, name=None):
    def f(logp, t):
        if log_target:
            loss = jnp.exp(t) * (t - logp)
        else:
            loss = jnp.where(t > 0, t * (jnp.log(jnp.maximum(t, 1e-30)) - logp), 0.0)
        if reduction == "batchmean":
            return jnp.sum(loss) / logp.shape[0]
        return _reduce(loss, reduction)
    return apply_op(f, to_tensor_like(input), to_tensor_like(label), name="kl_div")


def margin_ranking_loss(input, other, label, margin=0.0, reduction="mean",
                        name=None):
    return apply_op(
        lambda a, b, y: _reduce(jnp.maximum(-y * (a - b) + margin, 0.0), reduction),
        to_tensor_like(input), to_tensor_like(other), to_tensor_like(label))


def hinge_embedding_loss(input, label, margin=1.0, reduction="mean", name=None):
    return apply_op(
        lambda a, y: _reduce(jnp.where(y == 1, a, jnp.maximum(margin - a, 0.0)),
                             reduction),
        to_tensor_like(input), to_tensor_like(label))


def cosine_embedding_loss(input1, input2, label, margin=0.0, reduction="mean",
                          name=None):
    def f(a, b, y):
        cos = jnp.sum(a * b, -1) / jnp.maximum(
            jnp.linalg.norm(a, axis=-1) * jnp.linalg.norm(b, axis=-1), 1e-12)
        loss = jnp.where(y == 1, 1 - cos, jnp.maximum(cos - margin, 0.0))
        return _reduce(loss, reduction)
    return apply_op(f, to_tensor_like(input1), to_tensor_like(input2),
                    to_tensor_like(label))


def triplet_margin_loss(input, positive, negative, margin=1.0, p=2.0,
                        epsilon=1e-6, swap=False, reduction="mean", name=None):
    def f(a, pos, neg):
        dp = jnp.linalg.norm(a - pos + epsilon, ord=p, axis=-1)
        dn = jnp.linalg.norm(a - neg + epsilon, ord=p, axis=-1)
        if swap:
            dn2 = jnp.linalg.norm(pos - neg + epsilon, ord=p, axis=-1)
            dn = jnp.minimum(dn, dn2)
        return _reduce(jnp.maximum(dp - dn + margin, 0.0), reduction)
    return apply_op(f, to_tensor_like(input), to_tensor_like(positive),
                    to_tensor_like(negative))


def triplet_margin_with_distance_loss(input, positive, negative,
                                      distance_function=None, margin=1.0,
                                      swap=False, reduction="mean", name=None):
    if distance_function is None:
        return triplet_margin_loss(input, positive, negative, margin=margin,
                                   swap=swap, reduction=reduction)
    dp = distance_function(input, positive)
    dn = distance_function(input, negative)
    if swap:
        dn2 = distance_function(positive, negative)
        from ...ops.math import minimum
        dn = minimum(dn, dn2)
    return apply_op(
        lambda a, b: _reduce(jnp.maximum(a - b + margin, 0.0), reduction),
        dp, dn)


def multi_label_soft_margin_loss(input, label, weight=None, reduction="mean",
                                 name=None):
    args = [to_tensor_like(input), to_tensor_like(label)]
    if weight is not None:
        args.append(to_tensor_like(weight))

    def f(x, y, *rest):
        loss = -(y * jax.nn.log_sigmoid(x) + (1 - y) * jax.nn.log_sigmoid(-x))
        loss = jnp.mean(loss, axis=-1)
        if rest:
            loss = loss * rest[0]
        return _reduce(loss, reduction)
    return apply_op(f, *args)


def soft_margin_loss(input, label, reduction="mean", name=None):
    return apply_op(
        lambda x, y: _reduce(jnp.log1p(jnp.exp(-y * x)), reduction),
        to_tensor_like(input), to_tensor_like(label))


def sigmoid_focal_loss(logit, label, normalizer=None, alpha=0.25, gamma=2.0,
                       reduction="sum", name=None):
    args = [to_tensor_like(logit), to_tensor_like(label)]
    if normalizer is not None:
        args.append(to_tensor_like(normalizer))

    def f(x, y, *rest):
        p = jax.nn.sigmoid(x)
        ce = jnp.maximum(x, 0.0) - x * y + jnp.logaddexp(0.0, -jnp.abs(x))
        p_t = p * y + (1 - p) * (1 - y)
        a_t = alpha * y + (1 - alpha) * (1 - y)
        loss = a_t * ((1 - p_t) ** gamma) * ce
        if rest:
            loss = loss / rest[0]
        return _reduce(loss, reduction)
    return apply_op(f, *args, name="focal")


def dice_loss(input, label, epsilon=1e-5, name=None):
    def f(p, y):
        y1 = jax.nn.one_hot(y.squeeze(-1).astype(jnp.int32), p.shape[-1],
                            dtype=p.dtype)
        red = tuple(range(1, p.ndim))
        inter = jnp.sum(p * y1, axis=red)
        union = jnp.sum(p, axis=red) + jnp.sum(y1, axis=red)
        return jnp.mean(1 - (2 * inter + epsilon) / (union + epsilon))
    return apply_op(f, to_tensor_like(input), to_tensor_like(label))


def log_loss(input, label, epsilon=1e-4, name=None):
    return apply_op(
        lambda p, y: -y * jnp.log(p + epsilon) - (1 - y) * jnp.log(1 - p + epsilon),
        to_tensor_like(input), to_tensor_like(label))


def poisson_nll_loss(input, label, log_input=True, full=False, epsilon=1e-8,
                     reduction="mean", name=None):
    def f(x, y):
        if log_input:
            loss = jnp.exp(x) - y * x
        else:
            loss = x - y * jnp.log(x + epsilon)
        if full:
            stirling = y * jnp.log(y + epsilon) - y + 0.5 * jnp.log(
                2 * jnp.pi * (y + epsilon))
            loss = loss + jnp.where(y > 1, stirling, 0.0)
        return _reduce(loss, reduction)
    return apply_op(f, to_tensor_like(input), to_tensor_like(label))


def gaussian_nll_loss(input, label, variance, full=False, epsilon=1e-6,
                      reduction="mean", name=None):
    def f(mu, y, var):
        var = jnp.maximum(var, epsilon)
        loss = 0.5 * (jnp.log(var) + (y - mu) ** 2 / var)
        if full:
            loss = loss + 0.5 * jnp.log(jnp.asarray(2 * jnp.pi, mu.dtype))
        return _reduce(loss, reduction)
    return apply_op(f, to_tensor_like(input), to_tensor_like(label),
                    to_tensor_like(variance))


def multi_margin_loss(input, label, p=1, margin=1.0, weight=None,
                      reduction="mean", name=None):
    args = [to_tensor_like(input), to_tensor_like(label)]
    if weight is not None:
        args.append(to_tensor_like(weight))

    def f(x, y, *rest):
        y = y.astype(jnp.int32)
        xy = jnp.take_along_axis(x, y[:, None], axis=1)
        m = jnp.maximum(margin - xy + x, 0.0) ** p
        if rest:
            m = m * rest[0][y][:, None]
        mask = jax.nn.one_hot(y, x.shape[1], dtype=x.dtype)
        loss = jnp.sum(m * (1 - mask), axis=1) / x.shape[1]
        return _reduce(loss, reduction)
    return apply_op(f, *args)


def npair_loss(anchor, positive, labels, l2_reg=0.002):
    def f(a, p, y):
        sim = a @ p.T
        eq = (y[:, None] == y[None, :]).astype(a.dtype)
        tgt = eq / jnp.sum(eq, axis=1, keepdims=True)
        logp = jax.nn.log_softmax(sim, axis=1)
        ce = -jnp.sum(tgt * logp, axis=1)
        reg = l2_reg * (jnp.mean(jnp.sum(a * a, 1)) + jnp.mean(jnp.sum(p * p, 1))) / 2
        return jnp.mean(ce) + reg
    return apply_op(f, to_tensor_like(anchor), to_tensor_like(positive),
                    to_tensor_like(labels))


def hsigmoid_loss(input, label, num_classes, weight, bias=None,
                  path_table=None, path_code=None, is_sparse=False,
                  name=None):
    """ref: loss.py::hsigmoid_loss — hierarchical sigmoid over the default
    complete binary tree; weight: [num_classes-1, feature], bias:
    [num_classes-1] (custom path_table/path_code not supported — the
    reference's custom-tree mode serves its sparse PS path)."""
    if path_table is not None or path_code is not None:
        raise NotImplementedError(
            "custom path_table/path_code trees are not supported; the "
            "default complete-binary-tree mode covers the dense API")
    if is_sparse:
        raise NotImplementedError(
            "is_sparse=True (sparse row-wise weight updates) is the "
            "reference's PS path; gradients here are dense")
    nodes, codes, mask = _hsig_paths(int(num_classes))
    args = [to_tensor_like(input), to_tensor_like(label),
            to_tensor_like(weight)]
    if bias is not None:
        args.append(to_tensor_like(bias))

    def f(x, lbl, w, *b):
        lbl = lbl.reshape(-1).astype(jnp.int32)
        nsel = nodes[lbl]
        csel = codes[lbl].astype(jnp.float32)
        msel = mask[lbl]
        wsel = w[nsel]                    # [B, depth, F]
        logits = jnp.einsum("bf,bdf->bd", x.astype(jnp.float32),
                            wsel.astype(jnp.float32))
        if b:
            logits = logits + b[0][nsel]
        sign = 1.0 - 2.0 * csel
        logp = jax.nn.log_sigmoid(sign * logits) * msel
        return -jnp.sum(logp, axis=1, keepdims=True)

    return apply_op(f, *args, name="hsigmoid_loss")


import functools as _functools


@_functools.lru_cache(maxsize=64)
def _hsig_paths(num_classes):
    """Per-class (internal-node index, left/right bit, valid mask) paths
    of the complete binary tree (heap numbering), as DEVICE arrays.
    Cached — rebuilding/re-uploading a 100k-class table per step would
    dominate the loss itself."""
    import math as _m
    depth = int(_m.ceil(_m.log2(max(num_classes, 2))))
    codes = np.zeros((num_classes, depth), np.int32)
    nodes = np.zeros((num_classes, depth), np.int32)
    mask = np.zeros((num_classes, depth), np.float32)
    for c in range(num_classes):
        node = c + num_classes
        path = []
        while node > 1:
            path.append((node // 2, node % 2))
            node //= 2
        path.reverse()
        for d, (n, bit) in enumerate(path[:depth]):
            nodes[c, d] = n - 1
            codes[c, d] = bit
            mask[c, d] = 1.0
    return jnp.asarray(nodes), jnp.asarray(codes), jnp.asarray(mask)


def ctc_loss(log_probs, labels, input_lengths, label_lengths, blank=0,
             reduction="mean", norm_by_times=False):
    """CTC via optax.ctc_loss (ref: warpctc third_party dependency)."""
    import optax
    lp = to_tensor_like(log_probs)   # [T, B, C] paddle layout
    lbl = to_tensor_like(labels)     # [B, L]
    il = unwrap(input_lengths)
    ll = unwrap(label_lengths)

    def f(logits, labs):
        logits_btc = jnp.transpose(logits, (1, 0, 2)).astype(jnp.float32)
        B, T, C = logits_btc.shape
        t_idx = jnp.arange(T)[None, :]
        logitpaddings = (t_idx >= il[:, None]).astype(jnp.float32)
        l_idx = jnp.arange(labs.shape[1])[None, :]
        labelpaddings = (l_idx >= ll[:, None]).astype(jnp.float32)
        per_seq = optax.ctc_loss(logits_btc, logitpaddings,
                                 labs.astype(jnp.int32), labelpaddings,
                                 blank_id=blank)
        if norm_by_times:
            # the reference (warpctc) normalizes the GRADIENTS by each
            # sample's time steps, leaving the loss value unchanged:
            # value == per_seq, d/dx == (1/T) * d(per_seq)/dx
            t = jnp.maximum(il.astype(jnp.float32), 1.0)
            scaled = per_seq / t
            per_seq = scaled + jax.lax.stop_gradient(per_seq - scaled)
        if reduction == "mean":
            return jnp.mean(per_seq / jnp.maximum(ll.astype(jnp.float32), 1.0))
        return _reduce(per_seq, reduction)
    return apply_op(f, lp, lbl, name="ctc_loss")


def rnnt_loss(input, label, input_lengths, label_lengths, blank=0,
              fastemit_lambda=0.001, reduction="mean", name=None):
    """ref: loss.py::rnnt_loss (warprnnt there; a lax.scan forward-variable
    DP here — nn/layer/extras.py). input: [B, T, U+1, V] logits; label:
    [B, U]; lengths select each sample's (T_i, U_i) readout."""
    if blank != 0:
        raise NotImplementedError("this implementation fixes blank=0")
    if fastemit_lambda not in (0, 0.0, 0.001):
        # FastEmit is NOT implemented; warn only for explicitly tuned
        # values (the API-parity default would spam every call)
        import warnings
        warnings.warn(
            "rnnt_loss: fastemit_lambda is accepted for API parity but "
            "the FastEmit regularization term is not implemented — the "
            "returned value is the plain RNNT NLL", UserWarning)
    from ..layer.extras import _rnnt_alpha

    args = [to_tensor_like(input), to_tensor_like(label),
            to_tensor_like(input_lengths), to_tensor_like(label_lengths)]

    def f(x, lbl, il, ll):
        logp = jax.nn.log_softmax(x.astype(jnp.float32), axis=-1)
        B, T, U1, V = logp.shape
        il = il.reshape(-1)
        if U1 == 1:      # U=0: the only path emits t_len blanks
            t_mask = jnp.arange(T)[None, :] < il[:, None]
            losses = -jnp.sum(logp[:, :, 0, 0] * t_mask, axis=1)
        else:
            losses = jax.vmap(
                lambda lp, lb, ti, ui: _rnnt_alpha(
                    lp, lb.astype(jnp.int32), T, U1 - 1,
                    t_len=ti.astype(jnp.int32), u_len=ui.astype(jnp.int32))
            )(logp, lbl, il, ll.reshape(-1))
        if reduction == "mean":
            return jnp.mean(losses)
        if reduction == "sum":
            return jnp.sum(losses)
        return losses

    return apply_op(f, *args, name="rnnt_loss")


def margin_cross_entropy(logits, label, margin1=1.0, margin2=0.5,
                         margin3=0.0, scale=64.0, group=None,
                         return_softmax=False, reduction="mean", name=None):
    """ref: phi margin_cross_entropy (ArcFace/CosFace-style margins over
    possibly class-sharded logits; under GSPMD class sharding is an
    annotation, the math is identical):
    cos(m1*theta + m2) - m3 applied to the target class, then scaled CE."""
    lb = unwrap(to_tensor_like(label)).reshape(-1).astype(jnp.int32)

    def f(lg):
        lg = lg.astype(jnp.float32)   # arccos near ±1 needs f32
        onehot = jax.nn.one_hot(lb, lg.shape[-1], dtype=lg.dtype)
        theta = jnp.arccos(jnp.clip(lg, -1.0, 1.0))
        target = jnp.cos(margin1 * theta + margin2) - margin3
        adj = jnp.where(onehot > 0, target, lg) * scale
        logp = jax.nn.log_softmax(adj, axis=-1)
        loss = -jnp.sum(onehot * logp, axis=-1, keepdims=True)
        return loss, jax.nn.softmax(adj, axis=-1)

    loss, sm = apply_op(f, to_tensor_like(logits), n_outputs=2,
                        name="margin_cross_entropy")
    if reduction == "mean":
        loss = loss.mean()
    elif reduction == "sum":
        loss = loss.sum()
    if return_softmax:
        return loss, sm
    return loss


def _linear_cross_entropy(h, w, labels, block_rows, ignore_index,
                          tied=False, logit_scale=None,
                          scopes=("head", "loss")):
    """Mean cross-entropy of (h @ w) against labels, a block of rows at a
    time: h [N, H], w [H, V], labels [N]. The [N, V] logits never exist,
    and a block's are made ONCE: under differentiation (a
    `jax.custom_vjp`) the block's share of dh and of dw is made beside
    its loss, from the same float32 logits, and the backward only
    multiplies the two by the loss's cotangent; an evaluation that is not
    differentiated makes the one product a block and no gradient, and an
    argument that is not differentiated (a frozen head) gets none. dw is
    summed over the blocks in w's dtype, each block's product made in
    float32. Forward-mode differentiation does not pass a custom_vjp: no
    caller uses it. `tied`: w is the embedding table [V, H], multiplied as
    it is stored (no transposed copy is made); `logit_scale` multiplies
    the float32 logits (a model that divides them by a constant);
    `scopes` names the products and the reduction (a second pass over the
    head in one step names its own)."""
    from ...observability.scopes import scope
    head, loss = scopes
    f32 = jnp.float32
    dims = (((1,), (1 if tied else 0,)), ((), ()))
    n, hidden = h.shape
    block = min(block_rows, n)
    pad = -n % block
    if pad:
        h = jnp.pad(h, ((0, pad), (0, 0)))
        labels = jnp.pad(labels, (0, pad), constant_values=ignore_index)
    labels = labels.astype(jnp.int32).reshape(-1, block)
    with scope(loss):
        count = jnp.maximum(jnp.sum(labels != ignore_index), 1).astype(f32)

    def rows(hb, w_, lb):
        """A block's (loss sum, float32 logits, their logsumexp)."""
        with scope(head):
            lg = jax.lax.dot_general(hb, w_, dims, preferred_element_type=f32)
            if logit_scale is not None:
                lg = lg * logit_scale
        with scope(loss):
            valid = lb != ignore_index
            lse = jax.nn.logsumexp(lg, axis=-1)
            tgt = jnp.take_along_axis(
                lg, jnp.where(valid, lb, 0)[:, None], axis=-1)[:, 0]
            return jnp.sum(jnp.where(valid, lse - tgt, 0.0)), lg, lse

    def mean(sums):
        with scope(loss):
            return jnp.sum(sums) / count

    @jax.custom_vjp
    def blocks(h3, w_, lb2):
        return mean(jax.lax.map(lambda a: rows(a[0], w_, a[1])[0],
                                (h3, lb2)))

    def fwd(h3, w_, lb2):
        want_dh, want_dw = h3.perturbed, w_.perturbed
        h3, w_, lb2 = h3.value, w_.value, lb2.value

        def step(dw, args):
            hb, lb = args
            s, lg, lse = rows(hb, w_, lb)
            with scope(loss):
                # d mean / d logits: (softmax - onehot) on the rows that
                # count, through the scale
                each = (lb != ignore_index).astype(f32) / count
                if logit_scale is not None:
                    each = each * logit_scale
                hit = jnp.arange(lg.shape[-1])[None, :] == lb[:, None]
                dlg = (jnp.exp(lg - lse[:, None]) - hit) * each[:, None]
            dhb = None
            with scope(head):
                if want_dh:
                    dhb = jax.lax.dot_general(
                        dlg, w_, (((1,), (0 if tied else 1,)), ((), ())),
                        preferred_element_type=f32).astype(hb.dtype)
                if want_dw:
                    pair = (dlg, hb) if tied else (hb, dlg)
                    dw = dw + jax.lax.dot_general(
                        *pair, (((0,), (0,)), ((), ())),
                        preferred_element_type=f32).astype(dw.dtype)
            return dw, (s, dhb)

        dw, (sums, dh3) = jax.lax.scan(
            step, jnp.zeros_like(w_) if want_dw else None, (h3, lb2))
        return mean(sums), (dh3, dw)

    def bwd(kept, g):
        with scope(head):
            return tuple(None if d is None else (g * d).astype(d.dtype)
                         for d in kept) + (None,)

    blocks.defvjp(fwd, bwd, symbolic_zeros=True)
    return blocks(h.reshape(-1, block, hidden), w, labels)


def linear_cross_entropy(hidden, weight, labels, block_rows=2048,
                         ignore_index=-100, tied=False, logit_scale=None):
    """The output head and its cross-entropy in one: mean over the rows
    whose label is not `ignore_index` of CE(hidden @ weight, labels),
    computed `block_rows` rows at a time so that the logits of a long
    sequence over a large vocabulary are never held whole (at 32768 rows
    x 24576 columns they are 3.2 GB in float32, and as much again for
    their gradient), and a block's are made once: where the loss is
    differentiated, the block's gradients are made beside its loss and
    the backward only scales them by the loss's cotangent. hidden
    [..., H], weight [H, V] (or, `tied`, the embedding table [V, H]),
    labels [...]; `logit_scale` multiplies the logits."""
    hidden, weight = to_tensor_like(hidden), to_tensor_like(weight)
    lb = unwrap(labels).reshape(-1)
    H = hidden.shape[-1]

    def fn(h, w):
        return _linear_cross_entropy(h.reshape(-1, H), w, lb, block_rows,
                                     ignore_index, tied, logit_scale)

    return apply_op(fn, hidden, weight, name="linear_cross_entropy")
