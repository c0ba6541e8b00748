"""AMP (ref: python/paddle/amp/: auto_cast.py, grad_scaler.py:578).

TPU-native AMP, bf16-first.

O1 (`auto_cast`): a thread-local policy CONSUMED BY THE TAPE — every op
routed through `autograd.tape.apply_op` asks `compute_dtype(op_name)` and
casts its floating inputs to the policy dtype (white list), to float32
(black list), or leaves them alone (promote). This mirrors the reference's
generated ad_funcs, where the AMP cast is inlined before every kernel call
(ref: fluid/eager/amp_utils.h, eager_gen.py:455).

O2 (`decorate`): params cast to the low dtype with fp32 master weights kept
in the optimizer (ref: fleet/utils/mix_precision_utils.py).

`GradScaler` keeps the Paddle API (ref grad_scaler.py:578: dynamic loss
scaling via check_finite_and_unscale + update_loss_scaling) but is
implemented with traced jnp state — scale/good/bad counters are jax scalars
and the skip-on-inf decision is a `jnp.where` blend, so the whole scaler
works INSIDE a compiled TrainStep (fp16 path) instead of only in eager.
"""
from __future__ import annotations

import contextlib
import threading

import jax.numpy as jnp
import numpy as np

from ..framework import core
from ..tensor import Tensor

__all__ = ["auto_cast", "amp_guard", "decorate", "GradScaler",
           "is_bfloat16_supported", "is_float16_supported",
           "is_float8_supported", "white_list", "black_list",
           "compute_dtype"]

# ref: fluid/imperative/amp_auto_cast.cc O1 lists, trimmed + extended with
# this framework's fused-op tape names (llama_attn, flash_attention, ...)
white_list = {"matmul", "linear", "conv1d", "conv2d", "conv3d",
              "conv1d_transpose", "conv2d_transpose", "conv3d_transpose",
              "einsum", "bmm", "mm", "attention", "attn", "flash_attention",
              "sdpa", "llama_attn", "llama_mlp", "bert_attn", "ernie_attn",
              "lm_head", "lm_head_tied", "addmm", "matmul_v2"}
black_list = {"exp", "log", "log2", "log10", "log1p", "softmax",
              "log_softmax", "cross_entropy", "softmax_with_cross_entropy",
              "layer_norm", "rms_norm", "norm", "mean", "sum", "cumsum",
              "logsumexp", "erf", "erfinv", "pow", "square", "reciprocal",
              "rsqrt", "acos", "asin", "cosh", "sinh", "tan", "atan2",
              "softplus", "cdist", "dist", "renorm", "group_norm",
              "instance_norm", "batch_norm", "sigmoid_cross_entropy",
              "nll_loss", "kl_div", "smooth_l1_loss", "mse_loss"}


class _AmpState(threading.local):
    def __init__(self):
        self.enabled = False
        self.dtype = jnp.bfloat16
        self.level = "O1"
        self.custom_white = set()
        self.custom_black = set()


_amp = _AmpState()


def amp_state():
    return _amp


def compute_dtype(op_name: str):
    """The dtype apply_op should cast this op's float inputs to, or None.

    White-listed ops run in the autocast dtype, black-listed ops in float32,
    everything else is left to jnp promotion semantics ("promote" mode).
    Matching is exact first, then on '_'-separated tokens of the tape name
    (so "bert_attn" hits via "attn", "decoder_scan" hits nothing).
    """
    if not _amp.enabled or _amp.level != "O1":
        return None
    name = op_name or ""
    white = white_list | _amp.custom_white
    black = black_list | _amp.custom_black
    if name in black:
        return jnp.float32
    if name in white:
        return _amp.dtype
    toks = set(name.split("_"))
    if toks & black:
        return jnp.float32
    if toks & white:
        return _amp.dtype
    return None


def is_bfloat16_supported(device=None):
    return True


def is_float16_supported(device=None):
    return True


def is_float8_supported(device=None):
    """fp8-e4m3 availability on this jax/backend — the same probe that
    gates the quantized collectives' fp8 wire mode (ISSUE 8; the
    scale/cast plumbing is shared in paddle_tpu/quantization/comm.py)."""
    from ..quantization import comm as _qcomm
    return _qcomm.supports_fp8()


@contextlib.contextmanager
def auto_cast(enable=True, custom_white_list=None, custom_black_list=None,
              level="O1", dtype="bfloat16", use_promote=True):
    prev = (_amp.enabled, _amp.dtype, _amp.level, _amp.custom_white,
            _amp.custom_black)
    _amp.enabled = enable
    _amp.dtype = core.convert_dtype(dtype)
    _amp.level = level
    _amp.custom_white = set(custom_white_list or ())
    _amp.custom_black = set(custom_black_list or ())
    try:
        yield
    finally:
        (_amp.enabled, _amp.dtype, _amp.level, _amp.custom_white,
         _amp.custom_black) = prev


amp_guard = auto_cast


def decorate(models, optimizers=None, level="O2", dtype="bfloat16",
             master_weight=None, save_dtype=None, master_grad=False,
             excluded_layers=None):
    """O2: cast params to low precision, keep fp32 master weights in the
    optimizer (ref: amp/auto_cast.py::amp_decorate +
    fleet/utils/mix_precision_utils.py)."""
    d = core.convert_dtype(dtype)
    single = not isinstance(models, (list, tuple))
    model_list = [models] if single else list(models)
    opt_single = optimizers is not None and not isinstance(optimizers, (list, tuple))
    opt_list = ([optimizers] if opt_single else list(optimizers or []))

    if level == "O2":
        excluded = tuple(excluded_layers or ())
        for m in model_list:
            for layer in m.sublayers(include_self=True):
                from ..nn.layer.norm import LayerNorm, _BatchNormBase
                if isinstance(layer, (_BatchNormBase, LayerNorm)) or \
                        (excluded and isinstance(layer, excluded)):
                    continue
                for p in layer._parameters.values():
                    if p is not None and jnp.issubdtype(p.dtype, jnp.floating):
                        for opt in opt_list:
                            if (master_weight is None or master_weight) and \
                                    any(q is p for q in opt._parameter_list):
                                opt._master_weights[id(p)] = \
                                    p.data.astype(jnp.float32)
                        p.data = p.data.astype(d)
    if optimizers is None:
        return models if single else model_list
    return (models if single else model_list,
            optimizers if opt_single else opt_list)


class GradScaler:
    """Dynamic loss scaling with traced state (ref grad_scaler.py:578).

    State (`scale`, `good`/`bad` counters, `found_inf`) are jax scalars and
    every update is a jnp expression, so scale/unscale/step/update all trace
    cleanly inside a compiled TrainStep. The skip-update-on-inf semantic is
    a `jnp.where` blend of pre/post-step parameters and optimizer state —
    numerically identical to the reference's conditional skip.
    """

    def __init__(self, enable=True, init_loss_scaling=2.0 ** 16,
                 incr_ratio=2.0, decr_ratio=0.5, incr_every_n_steps=2000,
                 decr_every_n_nan_or_inf=1, use_dynamic_loss_scaling=True):
        self._enable = enable
        self._incr_ratio = float(incr_ratio)
        self._decr_ratio = float(decr_ratio)
        self._incr_every = int(incr_every_n_steps)
        self._decr_every = int(decr_every_n_nan_or_inf)
        self._dynamic = use_dynamic_loss_scaling
        self._state = {
            "scale": jnp.asarray(float(init_loss_scaling) if enable else 1.0,
                                 jnp.float32),
            "good": jnp.asarray(0, jnp.int32),
            "bad": jnp.asarray(0, jnp.int32),
            "found_inf": jnp.asarray(False, jnp.bool_),
        }
        self._unscaled = False

    # -- traced-state plumbing (TrainStep threads this like opt state) ------
    def _get_traced_state(self):
        return dict(self._state)

    def _set_traced_state(self, st):
        self._state = dict(st)

    @property
    def _scale(self):
        return self._state["scale"]

    @property
    def _found_inf(self):
        return self._state["found_inf"]

    def scale(self, var):
        if not self._enable:
            return var
        return var * Tensor(self._state["scale"].astype(
            var.dtype if jnp.issubdtype(var.dtype, jnp.floating)
            else jnp.float32), stop_gradient=True)

    def unscale_(self, optimizer):
        if not self._enable:
            return
        inv = (1.0 / self._state["scale"])
        found = jnp.asarray(False, jnp.bool_)
        for p in optimizer._parameter_list:
            if p.grad is None:
                continue
            g = p.grad.data.astype(jnp.float32) * inv
            found = found | ~jnp.all(jnp.isfinite(g))
            p.grad.data = g.astype(p.grad.dtype)
        self._state["found_inf"] = found
        self._unscaled = True

    def step(self, optimizer):
        if not self._enable:
            optimizer.step()
            return
        if not self._unscaled:
            self.unscale_(optimizer)
        found = self._state["found_inf"]
        # snapshot, run the update, then blend back where inf was found —
        # trace-compatible equivalent of "skip optimizer.step() on inf".
        # prime() first so lazily-created accumulators exist at their TRUE
        # initial values (e.g. Adagrad's initial_accumulator) before the
        # snapshot — otherwise a skipped first step would blend them to 0.
        # It makes only what is missing: from the second step on (and in
        # a compiled step, whose TrainStep primed before tracing) it
        # returns at once and the moments are not touched.
        if hasattr(optimizer, "prime"):
            optimizer.prime()
        old_params = [(p, p.data) for p in optimizer._parameter_list]
        old_state = dict(optimizer._state)
        old_master = dict(optimizer._master_weights)
        optimizer.step()
        for k, new in optimizer._state.items():
            old = old_state.get(k)
            if old is None:
                old = jnp.zeros_like(new)
            optimizer._state[k] = jnp.where(found, old, new)
        for p, old in old_params:
            p.data = jnp.where(found, old, p.data)
        for k, new in optimizer._master_weights.items():
            old = old_master.get(k)
            if old is not None:
                optimizer._master_weights[k] = jnp.where(found, old, new)
        self._unscaled = False

    def update(self):
        if not self._enable:
            return
        st = self._state
        if not self._dynamic:
            st["found_inf"] = jnp.asarray(False, jnp.bool_)
            return
        found = st["found_inf"]
        bad = jnp.where(found, st["bad"] + 1, jnp.asarray(0, jnp.int32))
        good = jnp.where(found, jnp.asarray(0, jnp.int32), st["good"] + 1)
        shrink = bad >= self._decr_every
        grow = good >= self._incr_every
        scale = st["scale"]
        scale = jnp.where(shrink,
                          jnp.maximum(scale * self._decr_ratio, 1.0), scale)
        scale = jnp.where(grow, scale * self._incr_ratio, scale)
        st["scale"] = scale
        st["bad"] = jnp.where(shrink, 0, bad)
        st["good"] = jnp.where(grow, 0, good)
        st["found_inf"] = jnp.asarray(False, jnp.bool_)

    def minimize(self, optimizer, scaled_loss):
        scaled_loss.backward()
        self.step(optimizer)
        self.update()

    def is_enable(self):
        return self._enable

    def is_use_dynamic_loss_scaling(self):
        return self._dynamic

    def get_init_loss_scaling(self):
        return float(np.asarray(self._state["scale"]))

    def state_dict(self):
        return {"scale": float(np.asarray(self._state["scale"])),
                "incr_ratio": self._incr_ratio,
                "decr_ratio": self._decr_ratio,
                "good": int(np.asarray(self._state["good"])),
                "bad": int(np.asarray(self._state["bad"]))}

    def load_state_dict(self, state):
        self._state["scale"] = jnp.asarray(
            state.get("scale", self.get_init_loss_scaling()), jnp.float32)
        self._state["good"] = jnp.asarray(state.get("good", 0), jnp.int32)
        self._state["bad"] = jnp.asarray(state.get("bad", 0), jnp.int32)


from . import debugging  # noqa: E402,F401  (ref: paddle.amp.debugging)
