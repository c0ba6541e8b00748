"""Optimizers (ref: python/paddle/optimizer/optimizer.py:103 base class +
per-optimizer phi kernels adamw_kernel etc.).

TPU-native design: update math is pure jnp on `.data` arrays — eagerly it
runs as-is; under a jit'd train step the same code traces into the compiled
program (the reference needs separate fused multi-tensor CUDA kernels for
this; XLA fuses the whole update chain for free).
"""
from __future__ import annotations

import time
from typing import Iterable, List, Optional

import jax
import jax.numpy as jnp

from ..autograd import no_grad
from ..framework import core
from ..observability import spans as _spans
from ..tensor import Tensor
from .lr import LRScheduler

__all__ = ["Optimizer", "SGD", "Momentum", "Adam", "AdamW", "Adamax",
           "Adagrad", "Adadelta", "RMSProp", "Lamb", "ASGD", "Rprop", "LBFGS"]


class Optimizer:
    def __init__(self, learning_rate=0.001, parameters=None, weight_decay=None,
                 grad_clip=None, name=None):
        if parameters is None:
            raise ValueError("parameters must be provided (dygraph-style)")
        self._parameter_list = list(parameters)
        self._lr = learning_rate
        self._weight_decay = weight_decay
        self._grad_clip = grad_clip
        self._state: dict = {}
        # id(p) -> the slot names p's update rule uses, as prime() last
        # traced them: what lets it see that nothing is missing
        self._slot_names: dict = {}
        self._step_count = 0
        # Optional master-weight map (fp32 copies for low-precision params),
        # populated by amp.decorate(level='O2') (ref: mix_precision_utils.py)
        self._master_weights: dict = {}

    # -- lr -----------------------------------------------------------------
    def get_lr(self):
        if isinstance(self._lr, LRScheduler):
            return float(self._lr())
        if isinstance(self._lr, (int, float)):
            return float(self._lr)
        return self._lr  # traced scalar inside a compiled TrainStep

    def set_lr(self, value):
        self._lr = value

    def set_lr_scheduler(self, scheduler):
        self._lr = scheduler

    @property
    def _learning_rate(self):
        return self._lr

    # -- state --------------------------------------------------------------
    def _get_state(self, p, name, init_fn):
        key = (id(p), name)
        if key not in self._state:
            self._state[key] = init_fn()
        return self._state[key]

    def prime(self):
        """Make the accumulator slots that are missing, now, in ONE compiled
        program, and leave every slot that exists as it is.

        State is otherwise created lazily inside the first `step()`, which
        widens the state pytree between the first and second compiled
        TrainStep call and forces an extra trace+compile of the full step
        (expensive for large models). A trainable parameter whose slots
        are all there is skipped; if that is all of them the call returns
        at once: no program, no device work, no write. The rest get what a
        FRESH optimizer starts from (`_fresh_slots`), under one `jax.jit`
        whose body the compiler folds to constants, each new slot of its
        target's shape placed as its target is (the master weight where
        there is one, else the parameter): the values depend on no input,
        so the partitioner would replicate them unless told. With tracers
        for targets (a `GradScaler.step` inside a compiled step) the same
        body runs inline. `_step_count`, the learning rate, the parameters
        and the slots that were there are not touched; a whole-step rule
        (LBFGS) has no `_apply_one` and returns quietly. Leaves one set-up
        event `optimizer.prime` a call that made anything.
        """
        todo = []
        for p in self._parameter_list:
            if p.stop_gradient:
                continue
            names = self._slot_names.get(id(p))
            if names is None or any((id(p), n) not in self._state
                                    for n in names):
                master = self._master_weights.get(id(p))
                todo.append((p, master if master is not None else p.data))
        if not todo:
            return
        t0 = time.perf_counter()
        params, targets = zip(*todo)
        inline = any(isinstance(w, jax.core.Tracer) for w in targets)
        placed = {id(p): (w.shape, None if inline
                          or len(w.sharding.device_set) == 1 else w.sharding)
                  for p, w in todo}

        keys = []  # of the slots made, in the rules' own order

        def make(targets):
            fresh = self._fresh_slots(params, targets)
            names = {id(p): () for p in params}
            for pid, name in fresh:
                names[pid] += (name,)
            self._slot_names.update(names)
            keys[:] = [k for k in fresh if k not in self._state]
            values = []
            for key in keys:
                shape, sharding = placed[key[0]]
                v = fresh[key]
                if sharding is not None and v.shape == shape:
                    v = jax.lax.with_sharding_constraint(v, sharding)
                values.append(v)
            return values

        try:
            if inline:
                values = make(targets)
            else:
                # traced first: what is missing is known only then, and an
                # optimizer that has it all (an eager step() made it)
                # compiles nothing
                traced = jax.jit(make).trace(targets)
                values = (traced.lower().compile()(targets)
                          if keys else [])
        except NotImplementedError:  # e.g. LBFGS (whole-step update)
            return
        if not keys:
            return
        kept = len(self._state)
        self._state.update(zip(keys, values))
        _spans.setup_event(
            "optimizer.prime", time.perf_counter() - t0,
            programs=0 if inline else 1, slots_made=len(keys),
            slots_kept=kept, parameters=len({pid for pid, _ in keys}))

    def _fresh_slots(self, params, targets):
        """{(id(p), name): value} of every slot a fresh optimizer holds for
        `params` after one update: each rule run once against an EMPTY
        state with a zero gradient and a zero learning rate, the update
        itself discarded (zeros, Adagrad's initial accumulator, ...).
        The state proper and `_step_count` are as before on return."""
        saved = self._state, self._step_count
        # Adam-style bias correction needs t >= 1
        self._state, self._step_count = {}, 1
        try:
            for p, w in zip(params, targets):
                self._apply_one(p, w, jnp.zeros_like(w), 0.0)
            return self._state
        finally:
            self._state, self._step_count = saved

    def state_dict(self):
        # group state by param id ONCE — the former params × state nested
        # scan was quadratic in model size (large models: thousands of
        # params × several accumulators each)
        by_pid: dict = {}
        for (pid, name), v in self._state.items():
            by_pid.setdefault(pid, []).append((name, v))
        out = {}
        for i, p in enumerate(self._parameter_list):
            for name, v in by_pid.get(id(p), ()):
                out[f"{p.name or i}.{name}"] = v
        out["@step"] = self._step_count
        if isinstance(self._lr, LRScheduler):
            out["LR_Scheduler"] = self._lr.state_dict()
        return out

    def set_state_dict(self, state):
        self._step_count = int(state.get("@step", 0))
        # one pass over the state dict against a prefix index (param names
        # may themselves contain dots, so try every '.'-split of each key)
        prefix_map: dict = {}
        for i, p in enumerate(self._parameter_list):
            prefix_map.setdefault(f"{p.name or i}.", []).append(p)
        for k, v in state.items():
            if not isinstance(k, str):
                continue
            pos = k.find(".")
            while pos != -1:
                for p in prefix_map.get(k[:pos + 1], ()):
                    name = k[pos + 1:]
                    arr = v.data if isinstance(v, Tensor) else jnp.asarray(v)
                    self._state[(id(p), name)] = arr
                pos = k.find(".", pos + 1)
        if isinstance(self._lr, LRScheduler) and "LR_Scheduler" in state:
            self._lr.set_state_dict(state["LR_Scheduler"])

    # -- step ---------------------------------------------------------------
    def clear_grad(self, set_to_zero=True):
        # set_to_zero keeps a zero grad Tensor in place (the reference's
        # in-place zeroing); False drops the grad entirely. One shared
        # implementation with Tensor.clear_gradient.
        for p in self._parameter_list:
            p.clear_gradient(set_to_zero)

    clear_gradients = clear_grad

    def _decay_coeff(self):
        wd = self._weight_decay
        if wd is None:
            return 0.0
        if hasattr(wd, "_coeff"):
            return float(wd._coeff)
        return float(wd)

    @no_grad()
    def step(self):
        self._step_count += 1
        if self._grad_clip is not None:
            self._grad_clip(self._parameter_list)
        lr = self.get_lr()
        for p in self._parameter_list:
            if p.grad is None or p.stop_gradient:
                continue
            g = p.grad.data
            master = self._master_weights.get(id(p))
            target = master if master is not None else p.data
            if g.dtype != target.dtype:
                g = g.astype(target.dtype)
            plr = lr * p.optimize_attr.get("learning_rate", 1.0) \
                if hasattr(p, "optimize_attr") else lr
            if p.regularizer is not None:
                g = g + p.regularizer(target)
            # update math may promote (the LR is a traced non-weak f32 scalar
            # inside TrainStep): keep the stored weight in its own dtype, or
            # bf16 params silently become f32 after one step (recompiles +
            # f32 matmuls from step 2 on)
            new = self._apply_one(p, target, g, plr).astype(target.dtype)
            if master is not None:
                self._master_weights[id(p)] = new
                p.data = new.astype(p.dtype)
            else:
                p.data = new

    def _apply_one(self, p, w, g, lr):
        raise NotImplementedError

    def minimize(self, loss, startup_program=None, parameters=None,
                 no_grad_set=None):
        loss.backward()
        self.step()
        return None, None


class SGD(Optimizer):
    def __init__(self, learning_rate=0.001, parameters=None, weight_decay=None,
                 grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip)

    def _apply_one(self, p, w, g, lr):
        wd = self._decay_coeff()
        if wd:
            g = g + wd * w
        return w - lr * g


class Momentum(Optimizer):
    def __init__(self, learning_rate=0.001, momentum=0.9, parameters=None,
                 use_nesterov=False, weight_decay=None, grad_clip=None,
                 name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip)
        self._momentum = momentum
        self._nesterov = use_nesterov

    def _apply_one(self, p, w, g, lr):
        wd = self._decay_coeff()
        if wd:
            g = g + wd * w
        v = self._get_state(p, "velocity", lambda: jnp.zeros_like(w))
        v = self._momentum * v + g
        self._state[(id(p), "velocity")] = v
        if self._nesterov:
            return w - lr * (g + self._momentum * v)
        return w - lr * v


class Adam(Optimizer):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, parameters=None, weight_decay=None,
                 grad_clip=None, lazy_mode=False, multi_precision=False,
                 use_multi_tensor=False, name=None, amsgrad=False):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip)
        self._beta1 = beta1
        self._beta2 = beta2
        self._eps = epsilon
        self._amsgrad = amsgrad
        self._decoupled = False  # Adam: L2 into grad

    def _apply_one(self, p, w, g, lr):
        b1 = float(self._beta1.item() if hasattr(self._beta1, "item") else self._beta1)
        b2 = float(self._beta2.item() if hasattr(self._beta2, "item") else self._beta2)
        wd = self._decay_coeff()
        if wd and not self._decoupled:
            g = g + wd * w
        m = self._get_state(p, "moment1", lambda: jnp.zeros_like(w))
        v = self._get_state(p, "moment2", lambda: jnp.zeros_like(w))
        t = self._step_count
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        self._state[(id(p), "moment1")] = m
        self._state[(id(p), "moment2")] = v
        mhat = m / (1 - b1 ** t)
        if self._amsgrad:
            vmax = self._get_state(p, "moment2_max", lambda: jnp.zeros_like(w))
            vmax = jnp.maximum(vmax, v)
            self._state[(id(p), "moment2_max")] = vmax
            vhat = vmax / (1 - b2 ** t)
        else:
            vhat = v / (1 - b2 ** t)
        out = w - lr * mhat / (jnp.sqrt(vhat) + self._eps)
        if wd and self._decoupled:
            out = out - lr * wd * w
        return out


class AdamW(Adam):
    """Decoupled weight decay (ref: python/paddle/optimizer/adamw.py +
    phi adamw_kernel)."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, parameters=None, weight_decay=0.01,
                 lr_ratio=None, apply_decay_param_fun=None, grad_clip=None,
                 lazy_mode=False, multi_precision=False, name=None,
                 amsgrad=False):
        super().__init__(learning_rate, beta1, beta2, epsilon, parameters,
                         weight_decay, grad_clip, amsgrad=amsgrad)
        self._decoupled = True
        self._apply_decay_param_fun = apply_decay_param_fun
        self._lr_ratio = lr_ratio

    def _apply_one(self, p, w, g, lr):
        if self._lr_ratio is not None:
            lr = lr * self._lr_ratio(p)
        skip_decay = (self._apply_decay_param_fun is not None
                      and not self._apply_decay_param_fun(p.name))
        wd = 0.0 if skip_decay else self._decay_coeff()
        b1, b2 = float(self._beta1), float(self._beta2)
        m = self._get_state(p, "moment1", lambda: jnp.zeros_like(w))
        v = self._get_state(p, "moment2", lambda: jnp.zeros_like(w))
        t = self._step_count
        # paddle adamw: decay applied to weights before update (lr-coupled)
        w = w * (1.0 - lr * wd)
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        self._state[(id(p), "moment1")] = m
        self._state[(id(p), "moment2")] = v
        mhat = m / (1 - b1 ** t)
        vhat = v / (1 - b2 ** t)
        return w - lr * mhat / (jnp.sqrt(vhat) + self._eps)


class Adamax(Optimizer):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, parameters=None, weight_decay=None,
                 grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip)
        self._beta1, self._beta2, self._eps = beta1, beta2, epsilon

    def _apply_one(self, p, w, g, lr):
        wd = self._decay_coeff()
        if wd:
            g = g + wd * w
        m = self._get_state(p, "moment", lambda: jnp.zeros_like(w))
        u = self._get_state(p, "inf_norm", lambda: jnp.zeros_like(w))
        t = self._step_count
        m = self._beta1 * m + (1 - self._beta1) * g
        u = jnp.maximum(self._beta2 * u, jnp.abs(g))
        self._state[(id(p), "moment")] = m
        self._state[(id(p), "inf_norm")] = u
        return w - lr / (1 - self._beta1 ** t) * m / (u + self._eps)


class Adagrad(Optimizer):
    def __init__(self, learning_rate, epsilon=1e-6, parameters=None,
                 weight_decay=None, grad_clip=None, name=None,
                 initial_accumulator_value=0.0):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip)
        self._eps = epsilon
        self._init_acc = initial_accumulator_value

    def _apply_one(self, p, w, g, lr):
        wd = self._decay_coeff()
        if wd:
            g = g + wd * w
        acc = self._get_state(p, "moment",
                              lambda: jnp.full_like(w, self._init_acc))
        acc = acc + g * g
        self._state[(id(p), "moment")] = acc
        return w - lr * g / (jnp.sqrt(acc) + self._eps)


class Adadelta(Optimizer):
    def __init__(self, learning_rate=0.001, epsilon=1e-6, rho=0.95,
                 parameters=None, weight_decay=None, grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip)
        self._eps = epsilon
        self._rho = rho

    def _apply_one(self, p, w, g, lr):
        wd = self._decay_coeff()
        if wd:
            g = g + wd * w
        avg_sq = self._get_state(p, "avg_squared_grad",
                                 lambda: jnp.zeros_like(w))
        avg_up = self._get_state(p, "avg_squared_update",
                                 lambda: jnp.zeros_like(w))
        avg_sq = self._rho * avg_sq + (1 - self._rho) * g * g
        update = (jnp.sqrt(avg_up + self._eps)
                  / jnp.sqrt(avg_sq + self._eps)) * g
        avg_up = self._rho * avg_up + (1 - self._rho) * update * update
        self._state[(id(p), "avg_squared_grad")] = avg_sq
        self._state[(id(p), "avg_squared_update")] = avg_up
        return w - lr * update


class RMSProp(Optimizer):
    def __init__(self, learning_rate, rho=0.95, epsilon=1e-6, momentum=0.0,
                 centered=False, parameters=None, weight_decay=None,
                 grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip)
        self._rho, self._eps = rho, epsilon
        self._momentum, self._centered = momentum, centered

    def _apply_one(self, p, w, g, lr):
        wd = self._decay_coeff()
        if wd:
            g = g + wd * w
        ms = self._get_state(p, "mean_square", lambda: jnp.zeros_like(w))
        ms = self._rho * ms + (1 - self._rho) * g * g
        self._state[(id(p), "mean_square")] = ms
        if self._centered:
            mg = self._get_state(p, "mean_grad", lambda: jnp.zeros_like(w))
            mg = self._rho * mg + (1 - self._rho) * g
            self._state[(id(p), "mean_grad")] = mg
            denom = jnp.sqrt(ms - mg * mg + self._eps)
        else:
            denom = jnp.sqrt(ms + self._eps)
        mom = self._get_state(p, "momentum", lambda: jnp.zeros_like(w))
        mom = self._momentum * mom + lr * g / denom
        self._state[(id(p), "momentum")] = mom
        return w - mom


class Lamb(Optimizer):
    def __init__(self, learning_rate=0.001, lamb_weight_decay=0.01, beta1=0.9,
                 beta2=0.999, epsilon=1e-6, parameters=None, grad_clip=None,
                 exclude_from_weight_decay_fn=None, multi_precision=False,
                 name=None):
        super().__init__(learning_rate, parameters, lamb_weight_decay,
                         grad_clip)
        self._beta1, self._beta2, self._eps = beta1, beta2, epsilon
        self._exclude_fn = exclude_from_weight_decay_fn

    def _apply_one(self, p, w, g, lr):
        wd = self._decay_coeff()
        if self._exclude_fn is not None and self._exclude_fn(p):
            wd = 0.0
        m = self._get_state(p, "moment1", lambda: jnp.zeros_like(w))
        v = self._get_state(p, "moment2", lambda: jnp.zeros_like(w))
        t = self._step_count
        m = self._beta1 * m + (1 - self._beta1) * g
        v = self._beta2 * v + (1 - self._beta2) * g * g
        self._state[(id(p), "moment1")] = m
        self._state[(id(p), "moment2")] = v
        mhat = m / (1 - self._beta1 ** t)
        vhat = v / (1 - self._beta2 ** t)
        r = mhat / (jnp.sqrt(vhat) + self._eps) + wd * w
        w_norm = jnp.linalg.norm(w.astype(jnp.float32))
        r_norm = jnp.linalg.norm(r.astype(jnp.float32))
        trust = jnp.where((w_norm > 0) & (r_norm > 0), w_norm / r_norm, 1.0)
        return w - lr * trust.astype(w.dtype) * r


class ASGD(Optimizer):
    def __init__(self, learning_rate=0.001, batch_num=1, parameters=None,
                 weight_decay=None, grad_clip=None, multi_precision=False,
                 name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip)
        self._batch_num = batch_num

    def _apply_one(self, p, w, g, lr):
        wd = self._decay_coeff()
        if wd:
            g = g + wd * w
        n = self._batch_num
        d = self._get_state(p, "d", lambda: jnp.zeros_like(w))
        ys = self._get_state(p, "ys", lambda: jnp.zeros((n,) + w.shape, w.dtype))
        idx = (self._step_count - 1) % n
        old_y = ys[idx]
        d = d - old_y + g
        ys = ys.at[idx].set(g)
        self._state[(id(p), "d")] = d
        self._state[(id(p), "ys")] = ys
        return w - lr / min(self._step_count, n) * d


class Rprop(Optimizer):
    def __init__(self, learning_rate=0.001, learning_rate_range=(1e-5, 50),
                 parameters=None, etas=(0.5, 1.2), grad_clip=None,
                 multi_precision=False, name=None):
        super().__init__(learning_rate, parameters, None, grad_clip)
        self._lr_range = learning_rate_range
        self._etas = etas

    def _apply_one(self, p, w, g, lr):
        prev_g = self._get_state(p, "prev_grad", lambda: jnp.zeros_like(w))
        lrs = self._get_state(p, "lrs", lambda: jnp.full_like(w, lr))
        sign = jnp.sign(g * prev_g)
        lrs = jnp.clip(jnp.where(sign > 0, lrs * self._etas[1],
                                 jnp.where(sign < 0, lrs * self._etas[0], lrs)),
                       self._lr_range[0], self._lr_range[1])
        g_eff = jnp.where(sign < 0, 0.0, g)
        self._state[(id(p), "prev_grad")] = g_eff
        self._state[(id(p), "lrs")] = lrs
        return w - lrs * jnp.sign(g_eff)


class LBFGS(Optimizer):
    """Limited-memory BFGS with strong-Wolfe line search
    (ref: python/paddle/optimizer/lbfgs.py). Requires a closure."""

    def __init__(self, learning_rate=1.0, max_iter=20, max_eval=None,
                 tolerance_grad=1e-7, tolerance_change=1e-9, history_size=100,
                 line_search_fn=None, parameters=None, weight_decay=None,
                 grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip)
        self._max_iter = max_iter
        self._tol_grad = tolerance_grad
        self._tol_change = tolerance_change
        self._history = history_size
        self._line_search = line_search_fn
        self._s: List = []
        self._y: List = []
        self._prev_flat_grad = None

    def _gather(self):
        ps = [p for p in self._parameter_list if not p.stop_gradient]
        flat_w = jnp.concatenate([p.data.ravel() for p in ps])
        flat_g = jnp.concatenate([
            (p.grad.data if p.grad is not None else jnp.zeros_like(p.data)).ravel()
            for p in ps])
        return ps, flat_w, flat_g

    def _scatter(self, ps, flat_w):
        off = 0
        for p in ps:
            n = p.size
            p.data = flat_w[off:off + n].reshape(p.data.shape)
            off += n

    def step(self, closure):
        with no_grad():
            pass
        loss = closure()
        for _ in range(self._max_iter):
            ps, w, g = self._gather()
            if float(jnp.max(jnp.abs(g))) <= self._tol_grad:
                break
            # two-loop recursion
            q = g
            alphas = []
            for s, y in zip(reversed(self._s), reversed(self._y)):
                rho = 1.0 / (jnp.dot(y, s) + 1e-10)
                a = rho * jnp.dot(s, q)
                q = q - a * y
                alphas.append((a, rho))
            if self._y:
                gamma = (jnp.dot(self._s[-1], self._y[-1])
                         / (jnp.dot(self._y[-1], self._y[-1]) + 1e-10))
                q = q * gamma
            for (a, rho), s, y in zip(reversed(alphas), self._s, self._y):
                b = rho * jnp.dot(y, q)
                q = q + (a - b) * s
            d = -q
            lr = self.get_lr()
            new_w = w + lr * d
            with no_grad():
                self._scatter(ps, new_w)
            self.clear_grad(set_to_zero=False)
            loss = closure()
            _, w2, g2 = self._gather()
            s_vec = w2 - w
            y_vec = g2 - g
            if float(jnp.dot(s_vec, y_vec)) > 1e-10:
                self._s.append(s_vec)
                self._y.append(y_vec)
                if len(self._s) > self._history:
                    self._s.pop(0)
                    self._y.pop(0)
            if float(jnp.max(jnp.abs(s_vec))) < self._tol_change:
                break
        return loss
