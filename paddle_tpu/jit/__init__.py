"""paddle_tpu.jit — dygraph-to-compiled bridge.

Replaces the reference's THREE graph-capture systems
(ref: python/paddle/jit/dy2static AST transforms, jit/sot bytecode tracing,
and the static Program/Executor stack, ~70k LoC combined) with one
mechanism: the eager vjp-tape runs unmodified under `jax.jit` tracing, so a
whole Paddle-style train step — forward, `loss.backward()`,
`optimizer.step()` — traces into ONE XLA executable. No graph breaks, no
bytecode guards; Python control flow is resolved at trace time exactly like
SOT's static path.

`to_static(layer)`     — compiled forward (inference / eval)
`TrainStep(model, opt, fn)` — compiled full training step (fwd+bwd+update)
"""
from __future__ import annotations

import functools
import itertools
import time
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from ..autograd import tape as _tape
from ..framework import core
from ..kernels.flash_attention import SPLASH_RESIDUALS
from ..observability import device_events as _devev
from ..observability import goodput as _goodput
from ..observability import metrics as _om
from ..observability import scopes as _scopes
from ..observability import spans as _spans
from ..tensor import Tensor

__all__ = ["to_static", "not_to_static", "TrainStep", "train_step", "save",
           "load", "ignore_module", "enable_to_static", "InputSpec",
           "TranslatedLayer"]

_to_static_enabled = True


def enable_to_static(flag: bool):
    global _to_static_enabled
    _to_static_enabled = bool(flag)


def _tree_unbox(x):
    """Tensor -> array, pass through everything else (pytree-mapped)."""
    return jax.tree_util.tree_map(
        lambda v: v.data if isinstance(v, Tensor) else v, x,
        is_leaf=lambda v: isinstance(v, Tensor))


def _tree_box(x):
    return jax.tree_util.tree_map(
        lambda v: Tensor(v) if isinstance(v, jax.Array) else v, x)


def capture_state(model):
    """Split a model's state into (trainable params, everything else) as
    raw arrays — shared by TrainStep and the auto-parallel Engine."""
    from ..tensor import Parameter
    params, buffers = {}, {}
    for k, t in model.state_dict().items():
        if isinstance(t, Parameter) and not t.stop_gradient:
            params[k] = t.data
        else:
            buffers[k] = t.data
    return params, buffers


class StaticFunction:
    """Compiled wrapper over a Layer (or bound layer method)."""

    def __init__(self, function, layer=None, input_spec=None):
        self._fn = function
        self._layer = layer
        if layer is None and hasattr(function, "__self__"):
            from ..nn.layer.layers import Layer
            if isinstance(function.__self__, Layer):
                self._layer = function.__self__
        self._compiled = None
        self._input_spec = input_spec
        self._fallback = False
        self._sot = None
        self._ast_fn = None           # dy2static-lowered variant
        self._ast_tried = False

    def _build(self, fn=None):
        layer = self._layer
        fn = fn or self._fn

        @functools.partial(jax.jit)
        def compiled(state, key, args, kwargs):
            def run():
                with core.rng_key_context(key):
                    with core.no_grad_guard():
                        out = fn(*_tree_box(args), **_tree_box(kwargs))
                    new_state = ({k: t.data for k, t in layer.state_dict().items()}
                                 if layer is not None else {})
                    return _tree_unbox(out), new_state
            if layer is not None:
                with layer.use_state(state):
                    return run()
            return run()

        self._compiled = compiled

    def __call__(self, *args, **kwargs):
        if not _to_static_enabled or self._fallback:
            return self._fn(*args, **kwargs)
        if self._sot is not None:     # split at a recorded graph break
            from .sot import SotCaptureError
            try:
                return self._sot(*args, **kwargs)
            except SotCaptureError:
                # machinery failure (guard thrash, non-replayable op) —
                # user exceptions propagate unchanged
                self._sot = None
                self._fallback = True
                return self._fn(*args, **kwargs)
        if self._compiled is None:
            self._build()
        state = ({k: t.data for k, t in self._layer.state_dict().items()}
                 if self._layer is not None else {})
        key = core.next_rng_key()
        try:
            out, new_state = self._compiled(state, key, _tree_unbox(args),
                                            _tree_unbox(kwargs))
        except (jax.errors.TracerBoolConversionError,
                jax.errors.ConcretizationTypeError,
                jax.errors.TracerIntegerConversionError,
                jax.errors.TracerArrayConversionError,
                jax.errors.NonConcreteBooleanIndexError) as e:
            # 1st recovery: dy2static AST lowering (ref transformers/
            # ifelse_transformer.py + while_loop_transformer.py) — rewrite
            # the Python if/while into lax.cond/lax.while_loop so the
            # whole function STAYS one executable with no per-branch or
            # per-trip-count respecialization (VERDICT r3 #5).
            if not self._ast_tried:
                self._ast_tried = True
                from .dy2static import ast_rewrite
                try:
                    self._ast_fn = ast_rewrite(self._fn)
                except Exception:
                    self._ast_fn = None
                if self._ast_fn is not None:
                    try:
                        self._build(self._ast_fn)
                        out, new_state = self._compiled(
                            state, key, _tree_unbox(args),
                            _tree_unbox(kwargs))
                        if self._layer is not None:
                            sd = self._layer.state_dict()
                            for k, v in new_state.items():
                                if k in sd:
                                    sd[k].data = v
                        return _tree_box(out)
                    except Exception:
                        # unloweable after all (shape-varying carry,
                        # name errors): rebuild the original and fall
                        # through to the SOT fragment path
                        self._ast_fn = None
                        self._build()
            # 2nd recovery: SOT graph break (ref jit/sot/
            # opcode_executor.py): split at the unsupported construct
            # and stitch compiled fragments around the host-side value
            # pull instead of de-optimizing the whole function to eager.
            # Guarded specializations re-capture when the pulled value
            # takes the other branch.
            from .sot import SotCaptureError, SubgraphProgram
            import warnings
            warnings.warn(
                f"to_static: data-dependent control flow broke whole-"
                f"function tracing ({type(e).__name__}); splitting into "
                "compiled sub-graph fragments at the break (ref SOT "
                "graph-break semantics)", stacklevel=2)
            self._sot = SubgraphProgram(self._fn, self._layer)
            try:
                return self._sot(*args, **kwargs)
            except SotCaptureError:
                # not replayable (rng/state mutation in capture):
                # permanent eager fallback, as before round 3
                self._sot = None
                self._fallback = True
                return self._fn(*args, **kwargs)
        if self._layer is not None:
            sd = self._layer.state_dict()
            for k, v in new_state.items():
                if k in sd:
                    sd[k].data = v
        return _tree_box(out)

    @property
    def forward(self):
        return self


def to_static(function=None, input_spec=None, build_strategy=None,
              backend=None, **kwargs):
    """ref: python/paddle/jit/api.py::to_static. Decorator or call."""
    from ..nn.layer.layers import Layer

    def decorate(f):
        if isinstance(f, Layer):
            static = StaticFunction(f.forward, layer=f, input_spec=input_spec)
            f.forward = static
            return f
        return StaticFunction(f, input_spec=input_spec)

    if function is None:
        return decorate
    return decorate(function)


def not_to_static(fn):
    fn._not_to_static = True
    return fn


def ignore_module(modules):
    return None


def _quant_sync_grads(model, ef, axis, nranks, cfg):
    """Quantized data-parallel gradient sync (ISSUE 8): inside the
    shard_map-wrapped step body, replace every trainable param's LOCAL
    grad with the blockwise-quantized mean over the `axis` shards
    (collective.grad_sync_all_reduce — the explicit EQuARX chain that
    stands in for the implicit GSPMD psum). `ef` carries this shard's
    error-feedback residuals ((1, padded) slices of the dp-sharded
    state); returns the updated residual tree."""
    from ..distributed import collective as _coll
    from ..tensor import Parameter
    new_ef = dict(ef or {})
    for k, t in model.state_dict().items():
        if not (isinstance(t, Parameter) and not t.stop_gradient):
            continue
        g = t.grad
        if g is None:
            continue
        garr = g.data if isinstance(g, Tensor) else g
        res = ef[k].reshape(-1) if ef and k in ef else None
        synced, new_res = _coll.grad_sync_all_reduce(
            garr, axis=axis, nranks=nranks, cfg=cfg, residual=res)
        t.grad = Tensor(synced)
        if new_res is not None and ef and k in ef:
            new_ef[k] = new_res.reshape(ef[k].shape)
    return new_ef


# per-rank optimizer-state footprint of a compiled TrainStep (ISSUE 16):
# recorded once per build, after the first step materializes the state —
# the ZeRO HBM saving (and any regression) is visible in /metrics
_OPT_STATE_BYTES = _om.gauge(
    "train.opt_state_bytes",
    "per-rank optimizer-state bytes of a compiled TrainStep by executable")


def _per_rank_nbytes(arr):
    """Bytes ONE rank holds of `arr`: the addressable-shard size for
    sharded jax Arrays (ZeRO state slices), the full buffer for
    replicated/host arrays."""
    try:
        if isinstance(arr, jax.Array) and len(arr.sharding.device_set) > 1:
            shards = arr.addressable_shards
            if shards:
                return int(shards[0].data.nbytes)
    except Exception:
        pass
    return int(getattr(arr, "nbytes", 0) or 0)


def _zero_sharded_update(model, opt, ef, axis, nranks, stage, cfg, block):
    """ZeRO-1/2 weight update (arxiv 2004.13336), inside the
    shard_map-wrapped step body after backward: every trainable param's
    LOCAL grad is mean-reduce-scattered over `axis`
    (collective.zero_grad_reduce_scatter — quantized phase-1 chain when
    `cfg` is armed), the optimizer update runs on THIS rank's flat
    (s,)-shard of the param with shard-shaped accumulator state (lazily
    zeros_like(w_shard) — 1/nranks the replicated footprint), and the
    updated shards are all-gathered back to the replicated param
    (collective.zero_param_all_gather, always exact). The flat layout is
    quantization/comm.py's shard_sizes(numel, nranks, block) contract —
    padding at the tail, so padded lanes carry zero grads and zero
    moments and never reach the unpadded weights. Returns the updated
    error-feedback residual tree (quantized wire only)."""
    from ..distributed import collective as _coll
    from ..quantization import comm as _qcomm
    from ..tensor import Parameter
    opt._step_count += 1
    lr = opt.get_lr()
    new_ef = dict(ef or {})
    for k, t in model.state_dict().items():
        if not (isinstance(t, Parameter) and not t.stop_gradient):
            continue
        g = t.grad
        if g is None:
            continue
        garr = g.data if isinstance(g, Tensor) else g
        res = ef[k].reshape(-1) if ef and k in ef else None
        shard_g, new_res = _coll.zero_grad_reduce_scatter(
            garr, axis=axis, nranks=nranks, stage=stage, block=block,
            cfg=cfg, residual=res)
        numel = int(t.data.size)
        s, padded = _qcomm.shard_sizes(numel, nranks, block)
        w_flat = jnp.pad(t.data.ravel(), (0, padded - numel))
        start = jax.lax.axis_index(axis) * s
        w_shard = jax.lax.dynamic_slice(w_flat, (start,), (s,))
        gs = shard_g.astype(w_shard.dtype)
        plr = lr * t.optimize_attr.get("learning_rate", 1.0) \
            if hasattr(t, "optimize_attr") else lr
        if t.regularizer is not None:
            gs = gs + t.regularizer(w_shard)
        with jax.named_scope("optimizer"):
            new_shard = opt._apply_one(t, w_shard, gs,
                                       plr).astype(w_shard.dtype)
        full = _coll.zero_param_all_gather(new_shard, axis=axis)
        t.data = full[:numel].reshape(t.data.shape)
        if new_res is not None and ef and k in ef:
            new_ef[k] = new_res.reshape(ef[k].shape)
    return new_ef


# what TrainStep's default remat policy keeps across the backward, by
# jax.ad_checkpoint.checkpoint_name: the dense decoder's matmul outputs
# (models/llama.py and kernels/rope.py stamp them, so norms and
# activations recompute instead of living through the backward) and what
# the attention kernel's backward needs from its forward
# (kernels/flash_attention.py: out and logsumexp, so the forward kernel
# runs once a step)
KEPT_CHECKPOINT_NAMES = ("llama_qkv", "llama_attn_o", "llama_swiglu",
                         "llama_mlp_down", SPLASH_RESIDUALS)


def resolve_remat_policy(policy):
    """Map TrainStep's remat_policy= knob onto a jax.checkpoint policy.

    None             -> jax.checkpoint's own default (save nothing,
                        recompute everything) — bitwise the pre-knob remat
    "save_matmul_outputs" (the TrainStep default) ->
                        save_only_these_names over KEPT_CHECKPOINT_NAMES:
                        the checkpoint_name-stamped matmul outputs and
                        the attention kernel's residuals; models that
                        stamp no names degrade to the save-nothing
                        default
    "nothing"        -> nothing_saveable (explicit recompute-everything)
    "dots"           -> checkpoint_dots (save every unnamed matmul too)
    callable         -> passed through (any jax.checkpoint_policies
                        predicate)

    Policies change memory/recompute placement only, never values.
    """
    if policy is None or callable(policy):
        return policy
    if policy == "save_matmul_outputs":
        return jax.checkpoint_policies.save_only_these_names(
            *KEPT_CHECKPOINT_NAMES)
    if policy in ("nothing", "recompute_all"):
        return jax.checkpoint_policies.nothing_saveable
    if policy == "dots":
        return jax.checkpoint_policies.checkpoint_dots
    raise ValueError(
        f"TrainStep: unknown remat_policy {policy!r} — expected None, "
        f"'save_matmul_outputs', 'nothing', 'dots' or a "
        f"jax.checkpoint_policies callable")


class _LoweredStep:
    """What `TrainStep.lower()` hands back: jax's `Lowered` (`as_text`,
    `cost_analysis`, ... are its own), whose `compile()` also leaves the
    set-up event `train_step.memory`: the compiler's count of the bytes
    a device holds while the executable runs."""

    __slots__ = ("_lowered", "_tag", "_devices")

    def __init__(self, lowered, tag, devices):
        self._lowered, self._tag, self._devices = lowered, tag, devices

    def __getattr__(self, name):
        return getattr(self._lowered, name)

    def compile(self, *args, **kwargs):
        compiled = self._lowered.compile(*args, **kwargs)
        t0 = time.perf_counter()
        mem = compiled.memory_analysis()
        if mem is None:                 # a backend that keeps no count
            return compiled
        attrs = {k + "_bytes": int(getattr(mem, k + "_size_in_bytes"))
                 for k in ("argument", "output", "alias", "temp",
                           "generated_code")}
        # the terms' sum refuses nothing: `temp_bytes` is the size of the
        # temporaries' region, not what is live at once (Yi at depth 6
        # sums to 18.1 GiB and compiles for 15.75; PERF.md section 4).
        # The compiler's own peak (arguments + the program's fullest
        # moment) is the number its refusal "used X of Y" prints
        attrs["sum_bytes"] = (
            attrs["argument_bytes"] + attrs["output_bytes"]
            - attrs["alias_bytes"] + attrs["temp_bytes"]
            + attrs["generated_code_bytes"])
        attrs["peak_bytes"] = int(getattr(mem, "peak_memory_in_bytes", 0)
                                  or attrs["sum_bytes"])
        try:
            stats = self._devices[0].memory_stats() or {}
        except RuntimeError:            # a device this process cannot
            stats = {}                  # address (described, remote)
        if "bytes_limit" in stats:
            attrs["bytes_limit"] = int(stats["bytes_limit"])
        _spans.setup_event("train_step.memory", executable=self._tag,
                           devices=len(self._devices),
                           dur_s=time.perf_counter() - t0, **attrs)
        return compiled


# ordinal suffixes for TrainStep executable tags (see _exec_tag)
_TRAIN_STEP_TAGS = itertools.count(1)


class TrainStep:
    """One-call compiled training step: forward + backward + optimizer update
    in a single XLA executable (the TPU-native answer to the reference's
    Program+InterpreterCore pipeline, ref SURVEY §3.3).

    step_fn: callable(*batch_tensors) -> loss Tensor; must route all model
    calls through `model` and set grads only via the tape.

    Optional `shard`: a paddle_tpu.distributed.ShardingPlan that places
    params/optimizer state/batch on a mesh (GSPMD partitioning).

    Optional `accumulate_steps=k` (ref: the GradientMerge meta-optimizer
    pass, fleet/meta_optimizers/gradient_merge_optimizer.py): the batch
    is split into k micro-batches on its leading axis and a lax.scan
    inside the SAME executable accumulates gradients across them, with
    ONE optimizer update at the end — activation memory drops ~k-fold
    while the optimizer sees the full global batch. The reference
    replays the program k times and conditions the update on a step
    counter; under XLA the scan keeps it a single compiled step with no
    host round-trips. Requires batch leading dims divisible by k;
    incompatible with a GradScaler (bf16 training needs no loss
    scaling — pass scaler=None).
    """

    def __init__(self, model, optimizer, step_fn, scaler=None, shard=None,
                 donate=True, accumulate_steps=1,
                 remat_policy="save_matmul_outputs"):
        self.model = model
        self.optimizer = optimizer
        self.step_fn = step_fn
        self.scaler = scaler
        self.shard = shard
        if shard is not None and hasattr(shard, "attach_model"):
            shard.attach_model(model)
        if shard is not None and getattr(shard, "grad_sync", None):
            if scaler is not None:
                raise ValueError(
                    "quantized grad sync (ShardingPlan(grad_sync=...)) is "
                    "incompatible with a GradScaler: the chain reduces "
                    "unscaled f32 gradients (bf16 training does not need "
                    "loss scaling)")
            if int(accumulate_steps) > 1:
                raise ValueError(
                    "quantized grad sync does not compose with "
                    "accumulate_steps > 1 yet — the gradient-merge scan "
                    "owns the backward/update interleaving")
        if shard is not None and getattr(shard, "zero", 0):
            if scaler is not None:
                raise ValueError(
                    "the ZeRO sharded update (ShardingPlan(zero=...)) is "
                    "incompatible with a GradScaler: the reduce-scatter "
                    "chain works on unscaled f32 gradients (bf16 training "
                    "does not need loss scaling)")
            if int(accumulate_steps) > 1:
                raise ValueError(
                    "the ZeRO sharded update does not compose with "
                    "accumulate_steps > 1 yet — the gradient-merge scan "
                    "owns the backward/update interleaving")
            if getattr(optimizer, "_grad_clip", None) is not None:
                raise ValueError(
                    "the ZeRO sharded update does not support grad_clip "
                    "yet: global-norm clipping needs a cross-shard norm "
                    "before the per-shard update")
            if getattr(optimizer, "_master_weights", None):
                raise ValueError(
                    "the ZeRO sharded update does not compose with amp O2 "
                    "master weights yet (fp8/f32 master-weight sharding is "
                    "a planned follow-on) — use amp level O1 or zero=0")
            from ..optimizer.optimizer import ASGD, LBFGS, Lamb
            if isinstance(optimizer, (Lamb, ASGD, LBFGS)):
                raise ValueError(
                    f"the ZeRO sharded update supports elementwise "
                    f"per-shard optimizers only; "
                    f"{type(optimizer).__name__} needs whole-parameter "
                    f"reductions (trust ratios / multi-row state) — use "
                    f"zero=0 or an Adam-family/SGD optimizer")
        # make the plan visible to DataLoader prefetchers so batches
        # stage straight into the mesh layout (io/prefetch.py picks up
        # the active plan's batch_spec at iteration time). Latest step
        # wins: an unsharded TrainStep clears a predecessor's plan so
        # loaders don't keep staging into a dead job's mesh layout
        from ..io import prefetch as _prefetch
        _prefetch.set_active_plan(shard)
        self._compiled = None
        self._donate = donate
        # jax.checkpoint policy armed while the step traces (consumed by
        # the models' remat sites via core.current_remat_policy). The
        # default saves the checkpoint_name-stamped matmul outputs and
        # the attention kernel's residuals (KEPT_CHECKPOINT_NAMES) so
        # norms/activations recompute instead of living across the
        # backward; models that stamp no names degrade to
        # jax.checkpoint's save-nothing default — bitwise the old remat
        self._remat_policy = resolve_remat_policy(remat_policy)
        self._key_base = None     # per-instance RNG base (see __call__)
        # stable executable tag stamped at trace time: per-execution
        # device telemetry (xla.dispatch_seconds, per-execution collective
        # counts) and compile attribution key on it. First instance is
        # plain "train_step" so single-step jobs need no label juggling.
        n = next(_TRAIN_STEP_TAGS)
        self._exec_tag = "train_step" if n == 1 else f"train_step_{n}"
        self._step_flops = None   # executable cost_analysis FLOPs (MFU)
        self._traces = 0          # times the step body was traced
        self._ledger_trace = 0    # the trace whose residuals were noted
        self._executed = False    # the compiled step has run once
        self._accum = int(accumulate_steps)
        self._quant = None        # (axis, nranks, CommQuantConfig) at build
        # (axis, nranks, zero_stage, cfg_or_None, block) at build
        self._zero = None
        self._ef_state = None     # error-feedback residuals (dp-sharded)
        self._opt_state_bytes = None  # per-rank bytes, set after build step
        if self._accum > 1 and scaler is not None:
            raise ValueError(
                "accumulate_steps > 1 is incompatible with a GradScaler: "
                "micro-grads are merged unscaled inside one executable "
                "(bf16 training does not need loss scaling)")

    def _capture_state(self):
        return capture_state(self.model)

    def _ensure_ef_state(self, params):
        """Allocate the error-feedback residual tree on first use: one
        zero (nranks, padded) f32 array per trainable param, sharded on
        the sync axis so each dp shard carries its OWN residual across
        steps (optimizer-adjacent state — it is this TrainStep's, not
        the optimizer dict's, because it is per-rank rather than
        replicated). Empty when error feedback is off (or the ZeRO wire
        is exact)."""
        if self._quant is not None:
            axis, nranks, cfg = self._quant
        else:
            axis, nranks, _stage, cfg, _block = self._zero
        if cfg is None or not cfg.error_feedback:
            return {}
        if self._ef_state is None:
            import numpy as _np
            from jax.sharding import NamedSharding, PartitionSpec as _P

            from ..quantization import comm as _qcomm
            sharding = NamedSharding(self.shard.mesh, _P(axis))
            self._ef_state = {
                k: jax.device_put(
                    _np.zeros(
                        (nranks,
                         _qcomm.shard_sizes(v.size, nranks, cfg.block)[1]),
                        _np.float32), sharding)
                for k, v in params.items()}
        return self._ef_state

    def _state_keys(self):
        """(to_names, to_ids): optimizer state is keyed by id(param) on
        the host and crosses the jit boundary keyed by parameter NAME.
        An id is a memory address: as a pytree key it names — and, by
        sort order, places — the step's arguments differently in every
        process, so no two processes would ever lower the same program
        and the persistent compile cache could never hit."""
        name_of = {id(t): k for k, t in self.model.state_dict().items()}
        for i, p in enumerate(self.optimizer._parameter_list):
            name_of.setdefault(id(p), f"optimizer.param.{i}")
        id_of = {n: i for i, n in name_of.items()}

        def rekey(table):
            def key(k):
                if isinstance(k, tuple):
                    return (table.get(k[0], k[0]),) + k[1:]
                return table.get(k, k)
            return lambda state: {key(k): v for k, v in state.items()}

        return rekey(name_of), rekey(id_of)

    def _build(self):
        model = self.model
        opt = self.optimizer
        step_fn = self.step_fn
        to_names, to_ids = self._to_names, self._to_ids = self._state_keys()
        scaler = self.scaler
        accum = self._accum
        tag = self._exec_tag
        # quantized grad sync arms at BUILD time so the kill switch
        # (FLAGS_quant_collectives=0) restores the plain GSPMD-psum
        # compile path bitwise, opted-in plan or not
        quant = None
        # the ZeRO sharded update likewise arms at BUILD time
        # (FLAGS_zero=0 restores the replicated compile paths bitwise);
        # when armed it OWNS the step body — grad_sync then only selects
        # the wire mode of the ZeRO reduce-scatter
        zero = None
        if self.shard is not None and getattr(self.shard, "zero", 0) and \
                self.shard.zero_armed():
            axis, nranks = self.shard.quant_sync_axis()
            if getattr(opt, "_master_weights", None):
                raise ValueError(
                    "the ZeRO sharded update does not compose with amp O2 "
                    "master weights yet — use amp level O1 or zero=0")
            cfg = self.shard.zero_wire_config()
            zero = (axis, nranks, self.shard.zero, cfg,
                    self.shard.zero_block())
        elif self.shard is not None and \
                getattr(self.shard, "grad_sync", None) and \
                core.get_bool_flag("FLAGS_quant_collectives", True):
            from ..quantization import comm as _qcomm
            axis, nranks = self.shard.quant_sync_axis()
            cfg = _qcomm.resolve_config(
                self.shard.grad_sync, self.shard.grad_sync_block,
                self.shard.grad_sync_error_feedback)
            quant = (axis, nranks, cfg)
        self._quant = quant
        self._zero = zero

        def run_accum(batch, key):
            """Gradient-merge path: lax.scan over k micro-batches, grads
            accumulated as the carry, one optimizer update at the end.
            Runs under model.use_state, so sd tensors are the traced
            params."""
            from ..tensor import Tensor as _TT
            sd = model.state_dict()
            pkeys = [k for k, t in sd.items()
                     if not getattr(t, "stop_gradient", True)]
            ptensors = [sd[k] for k in pkeys]
            pset = set(pkeys)
            # non-trainable state (BatchNorm running stats, …) mutates
            # during forward; thread it through the scan carry so body
            # tracers never leak into the outer trace and the final
            # values are the k-th micro-step's, same as k eager steps
            btensors = [t for k, t in sd.items() if k not in pset]

            def split_leading(x):
                if x.shape[0] % accum:
                    raise ValueError(
                        f"accumulate_steps={accum} must divide the batch "
                        f"leading dim {x.shape[0]}")
                return x.reshape((accum, x.shape[0] // accum) + x.shape[1:])

            micro = jax.tree_util.tree_map(split_leading, batch)
            mkeys = jax.random.key_data(jax.random.split(key, accum))
            zero = [jnp.zeros_like(p.data) for p in ptensors]
            # which params the loss actually reaches is STATIC (the scan
            # body traces once); record it so untouched params keep
            # grad=None and are skipped by opt.step() exactly like the
            # non-accumulating path (no spurious weight-decay updates)
            touched = set()

            def body(carry, xs):
                acc, loss_sum, bufs = carry
                mb, mk = xs
                for t, b in zip(btensors, bufs):
                    t.data = b
                with core.rng_key_context(jax.random.wrap_key_data(mk)):
                    with _scopes.phase("forward", tag):
                        loss = step_fn(*_tree_box(mb))
                    self._note_residuals(
                        loss, ([p.data for p in ptensors], bufs, mb))
                    with _scopes.phase("backward", tag):
                        loss.backward()
                new_acc = []
                for i, (a, p) in enumerate(zip(acc, ptensors)):
                    g = p.grad
                    if g is None:
                        new_acc.append(a)
                    else:
                        touched.add(i)
                        gd = g.data if isinstance(g, _TT) else g
                        new_acc.append(a + gd.astype(a.dtype))
                opt.clear_grad(set_to_zero=False)
                return (new_acc,
                        loss_sum + loss.data.astype(jnp.float32),
                        [t.data for t in btensors]), None

            (grads, loss_sum, final_bufs), _ = jax.lax.scan(
                body, (zero, jnp.float32(0),
                       [t.data for t in btensors]), (micro, mkeys))
            for t, b in zip(btensors, final_bufs):
                t.data = b
            inv_k = 1.0 / accum
            for i, (p, g) in enumerate(zip(ptensors, grads)):
                if i in touched:
                    p.grad = _TT((g * inv_k).astype(g.dtype))
            with _scopes.phase("optimizer", tag):
                opt.step()
            return _TT(loss_sum * inv_k)

        def _pure_body(params, buffers, opt_state, master, scaler_state,
                       step_i, lr, key, batch, ef=None):
            # key travels as raw uint32 key-data (host numpy — typed PRNG
            # keys are committed device arrays, which a multi-process
            # mesh jit cannot accept); rewrap to a typed key here. The
            # per-step stream derives from the step counter IN-TRACE
            # (domain-tagged so it cannot collide with the eager
            # fold_in(counter) stream) — no per-call device RNG work.
            key = jax.random.wrap_key_data(key)
            key = jax.random.fold_in(
                jax.random.fold_in(key, 0x54524E), step_i)
            if quant is not None or zero is not None:
                # per-shard randomness: the body runs once per dp shard
                # (shard_map), each on its own batch slice — distinct
                # dropout masks per shard, like the GSPMD global mask
                key = jax.random.fold_in(
                    key, jax.lax.axis_index((quant or zero)[0]))
            state = {}
            state.update(params)
            state.update(buffers)
            saved_state = opt._state
            saved_step = opt._step_count
            saved_master = opt._master_weights
            saved_lr = opt._lr
            saved_scaler = (scaler._get_traced_state()
                            if scaler is not None else None)
            with model.use_state(state):
                with core.rng_key_context(key):
                    opt._state = to_ids(opt_state)
                    opt._step_count = step_i
                    opt._master_weights = to_ids(master)
                    # ALWAYS run the compiled update off the per-call lr
                    # argument: __call__ evaluates scheduler/value on the
                    # host each step. Keeping a scheduler object here
                    # would bake float(scheduler()) at TRACE time — the
                    # schedule would silently never reach the weights.
                    opt._lr = lr
                    if scaler is not None:
                        scaler._set_traced_state(scaler_state)
                    try:
                        new_ef = ef
                        if accum > 1:
                            loss = run_accum(batch, key)
                        else:
                            with _scopes.phase("forward", tag):
                                loss = step_fn(*_tree_box(batch))
                            self._note_residuals(
                                loss, (params, buffers, batch))
                            with _scopes.phase("backward", tag):
                                (scaler.scale(loss) if scaler is not None
                                 else loss).backward()
                        if zero is not None:
                            # ZeRO sharded update: backward yields LOCAL
                            # grads (per-shard body); the rs -> shard
                            # update -> ag sequence replaces opt.step()
                            with _scopes.phase("grad_sync", tag):
                                new_ef = _zero_sharded_update(
                                    model, opt, ef, zero[0], zero[1],
                                    zero[2], zero[3], zero[4])
                        elif quant is not None:
                            # quantized DP sync: the body is per-shard
                            # (shard_map) so backward yields LOCAL
                            # grads; the explicit quantized chain is
                            # their mean before the update
                            with _scopes.phase("grad_sync", tag):
                                new_ef = _quant_sync_grads(
                                    model, ef, quant[0], quant[1], quant[2])
                            with _scopes.phase("optimizer", tag):
                                opt.step()
                        elif scaler is not None:
                            with _scopes.phase("optimizer", tag):
                                scaler.step(opt)
                                scaler.update()
                        elif accum == 1:
                            with _scopes.phase("optimizer", tag):
                                opt.step()
                        # in-trace: drop grads entirely — zero-filled
                        # grads here would be traced values leaking out
                        opt.clear_grad(set_to_zero=False)
                        sd = model.state_dict()
                        new_params = {k: sd[k].data for k in params}
                        new_buffers = {k: sd[k].data for k in buffers}
                        new_opt_state = to_names(opt._state)
                        new_master = to_names(opt._master_weights)
                        new_scaler = (scaler._get_traced_state()
                                      if scaler is not None else {})
                    finally:
                        opt._state = saved_state
                        opt._step_count = saved_step
                        opt._master_weights = saved_master
                        opt._lr = saved_lr
                        if scaler is not None:
                            scaler._set_traced_state(saved_scaler)
            if quant is not None or zero is not None:
                # global loss = mean of the per-shard means; float
                # buffers (BatchNorm running stats) likewise averaged so
                # the replicated outputs are well-defined — each shard
                # saw only its batch slice
                axis = (quant or zero)[0]
                new_buffers = {
                    k: (jax.lax.pmean(v, axis)
                        if jnp.issubdtype(v.dtype, jnp.floating) else v)
                    for k, v in new_buffers.items()}
                return (jax.lax.pmean(loss.data, axis), new_params,
                        new_buffers, new_opt_state, new_master,
                        new_scaler, new_ef)
            return (loss.data, new_params, new_buffers, new_opt_state,
                    new_master, new_scaler)

        remat_pol = self._remat_policy

        def pure(params, buffers, opt_state, master, scaler_state, step_i,
                 lr, key, batch, ef=None):
            # Python that runs only while the step TRACES: count it. A
            # trace after the first execution is a recompile in the
            # middle of a run (new shapes, a widened state tree)
            self._traces += 1
            _spans.setup_event("train_step.traced", executable=tag,
                               n=self._traces,
                               after_first_execution=self._executed)
            _devev.note_step_traced(None)
            # arm the jax.checkpoint policy for THIS trace — the models'
            # remat sites (_scan_stack/_recompute_stack) read it via
            # core.current_remat_policy() while the body traces
            with core.remat_policy_guard(remat_pol):
                out = _pure_body(params, buffers, opt_state, master,
                                 scaler_state, step_i, lr, key, batch, ef)
            _devev.note_step_traced(tag)
            return out

        # FLAGS_eager_delete_tensor_gb < 0 disables buffer donation (the
        # reference's eager-deletion kill switch maps to donation here);
        # FLAGS_max_inplace_grad_add > 0 is the explicit opt-IN for
        # in-place grad-buffer reuse and overrides that veto
        flag_gb = core.get_flag("FLAGS_eager_delete_tensor_gb", 0.0)
        force_inplace = int(float(
            core.get_flag("FLAGS_max_inplace_grad_add", 0) or 0)) > 0
        donate_ok = self._donate and (
            force_inplace or float(flag_gb or 0.0) >= 0.0)
        donate = (0, 1, 2, 3) if donate_ok else ()
        if zero is not None:
            # ef (arg 9) is consumed and returned every step, like quant
            zdonate = donate + (9,) if donate_ok else ()
            self._compiled = self.shard.compile_zero_train_step(
                pure, zdonate)
        elif quant is not None:
            # the error-feedback residual tree (arg 9) is donated too:
            # it is consumed and returned every step
            qdonate = donate + (9,) if donate_ok else ()
            self._compiled = self.shard.compile_quantized_train_step(
                pure, qdonate)
        elif self.shard is not None:
            self._compiled = self.shard.compile_train_step(pure, donate)
        else:
            self._compiled = jax.jit(pure, donate_argnums=donate)

    def _note_residuals(self, loss, step_inputs):
        """The set-up events `train_step.residuals` of this trace: what
        the forward left the backward, summed by "scope:op" with one
        total under "*" (`autograd.tape.kept_residuals`; `step_inputs`
        are counted apart, as `state_bytes`). Once a trace: the first
        walk, where the body runs inside a loop over micro-batches.
        Shapes are the traced ones: the whole program's under GSPMD, a
        shard's where the body runs under shard_map (`shapes`)."""
        if self._ledger_trace == self._traces:
            return
        self._ledger_trace = self._traces
        t0 = time.perf_counter()
        by_key, state_bytes = _tape.kept_residuals(
            [loss], jax.tree_util.tree_leaves(step_inputs))
        tag = self._exec_tag
        for key, (nbytes, arrays) in by_key.items():
            _spans.setup_event("train_step.residuals", executable=tag,
                               trace=self._traces, scope=key, bytes=nbytes,
                               arrays=arrays)
        per_shard = self._quant is not None or self._zero is not None
        _spans.setup_event(
            "train_step.residuals", executable=tag, trace=self._traces,
            scope="*", bytes=sum(b for b, _ in by_key.values()),
            arrays=sum(n for _, n in by_key.values()),
            state_bytes=state_bytes,
            shapes="shard" if per_shard else "global",
            dur_s=time.perf_counter() - t0)

    def _call_args(self, batch):
        """The compiled step's arguments for this batch and the current
        model/optimizer state (building the step on first use)."""
        if self._compiled is None:
            # materialize optimizer state before the first trace: otherwise
            # the state tree widens after step 1 and the whole step
            # recompiles (minutes for large models). prime() makes what is
            # missing in ONE compiled program (a restored optimizer's
            # slots are left as they are, at no program). NOT under an armed
            # ZeRO plan: priming would allocate the full replicated
            # state the mode exists to avoid — the body creates
            # shard-shaped slots inside the first step instead (one
            # extra compile, 1/nranks the state HBM from step 0 on)
            zero_pending = (self.shard is not None
                            and getattr(self.shard, "zero", 0)
                            and self.shard.zero_armed())
            if hasattr(self.optimizer, "prime") and not zero_pending:
                self.optimizer.prime()
            self._build()
        opt = self.optimizer
        params, buffers = self._capture_state()
        # host scalars, not committed device arrays: on a multi-PROCESS
        # mesh jit can place numpy inputs into replicated shardings but
        # cannot reshard a single-local-device jax array onto devices it
        # does not own
        import numpy as _np
        lr = _np.float32(opt.get_lr())
        # opt.step() inside the compiled fn performs the +1 itself
        step_i = _np.int32(opt._step_count)
        if core._rng.stack:
            # an active rng_key_context must keep steering compiled-step
            # randomness (the fleet TP rng-tracker pattern): split the
            # context key per call, as before
            key = _np.asarray(jax.random.key_data(core.next_rng_key()))
        else:
            if self._key_base is None:
                # one fold of the globally-advancing eager counter per
                # TrainStep INSTANCE: distinct streams for successive
                # TrainSteps even when their step counters overlap,
                # deterministic under paddle.seed, and base-cache
                # invalidation (seed / set_rng_state) is respected
                self._key_base = _np.asarray(
                    jax.random.key_data(core.next_rng_key()))
                self._key_base_src = core.base_rng_key_data()
            elif self._key_base_src is not core.base_rng_key_data():
                self._key_base = _np.asarray(
                    jax.random.key_data(core.next_rng_key()))
                self._key_base_src = core.base_rng_key_data()
            key = self._key_base
        batch_arrays = _tree_unbox(batch)
        if self.shard is not None and hasattr(self.shard, "reshard_batch"):
            # committed prefetched batches must match the compiled batch
            # in_shardings — see ShardingPlan.reshard_batch
            batch_arrays = self.shard.reshard_batch(batch_arrays)
        scaler_state = (self.scaler._get_traced_state()
                        if self.scaler is not None else {})
        call_args = (params, buffers, self._to_names(opt._state),
                     self._to_names(opt._master_weights), scaler_state,
                     step_i, lr, key, batch_arrays)
        if self._quant is not None or self._zero is not None:
            call_args = call_args + (self._ensure_ef_state(params),)
        return call_args

    def lower(self, *batch):
        """The step as jax lowers it for this batch and the current state
        — the program `__call__` runs, for `.compile().as_text()` /
        `.memory_analysis()`. Nothing executes. `.compile()` leaves the
        set-up event `train_step.memory` (observability/scopes.SETUP)."""
        tag = self._exec_tag
        with _spans.setup_span("train_step.lower", executable=tag):
            with _spans.setup_span("train_step.call_args", executable=tag):
                call_args = self._call_args(batch)
            with _devev.tagged(tag):
                lowered = self._compiled.lower(*call_args)
        devices = (list(self.shard.mesh.devices.flat)
                   if self.shard is not None else jax.devices()[:1])
        return _LoweredStep(lowered, tag, devices)

    def __call__(self, *batch):
        bench = core.get_bool_flag("FLAGS_benchmark")
        if bench:
            import time as _time
            _t0 = _time.perf_counter()
        armed = _om.enabled()
        # three host parts, each a TraceAnnotation (_scopes.STEP_SPANS):
        # with no profiler session one costs a relaxed atomic load; in a
        # traced run they name the device's idle gaps
        with jax.profiler.TraceAnnotation("train_step.call_args"):
            call_args = self._call_args(batch)
        opt = self.optimizer
        if armed and self._step_flops is None:
            # must run BEFORE the call: args 0-3 are donated by it
            self._step_flops = self._lower_flops(call_args)
        # execution window (disarmed: one bool check): xla.dispatch_seconds
        # {executable=tag} + per-execution collective counts replayed from
        # the tag's trace-time composition (observability/device_events.py)
        with _devev.execution(self._exec_tag), \
                jax.profiler.TraceAnnotation("train_step.dispatch"):
            outs = self._compiled(*call_args)
        self._executed = True
        with jax.profiler.TraceAnnotation("train_step.write_back"):
            if self._quant is not None or self._zero is not None:
                (loss, new_params, new_buffers, new_opt_state, new_master,
                 new_scaler, new_ef) = outs
                if new_ef:
                    self._ef_state = new_ef
            else:
                (loss, new_params, new_buffers, new_opt_state, new_master,
                 new_scaler) = outs
            sd = self.model.state_dict()
            for k, v in new_params.items():
                sd[k].data = v
            for k, v in new_buffers.items():
                sd[k].data = v
            opt._state = self._to_ids(new_opt_state)
            opt._master_weights = self._to_ids(new_master)
        if self._opt_state_bytes is None:
            # the build step materialized every state slot (primed, or
            # shard-created under ZeRO) — record the per-rank footprint
            self._opt_state_bytes = self.opt_state_bytes_per_rank()
            if armed:
                _OPT_STATE_BYTES.set(self._opt_state_bytes,
                                     executable=self._exec_tag)
        if self.scaler is not None:
            self.scaler._set_traced_state(new_scaler)
        opt._step_count += 1
        if bench:
            import sys as _sys
            jax.block_until_ready(loss)
            print(f"TrainStep[{opt._step_count}]: "
                  f"{(_time.perf_counter() - _t0) * 1e3:.2f} ms",
                  file=_sys.stderr)
        if core.get_bool_flag("FLAGS_check_nan_inf"):
            # compiled-path sweep: values can't be branched on at trace
            # time, so the check runs on the step RESULT; rerun in eager
            # mode for per-op localization (tape._check_nan_inf)
            import numpy as _np
            if not _np.isfinite(_np.asarray(loss)).all():
                raise FloatingPointError(
                    "NaN or Inf in TrainStep loss (FLAGS_check_nan_inf). "
                    "Rerun the step eagerly (without TrainStep) to get the "
                    "failing op's name.")
            bad = [k for k, v in new_params.items()
                   if jnp.issubdtype(v.dtype, jnp.floating)
                   and not _np.isfinite(_np.asarray(v)).all()]
            if bad:
                raise FloatingPointError(
                    f"NaN or Inf in updated parameters {bad[:5]} "
                    "(FLAGS_check_nan_inf)")
        if armed:
            # close this step's goodput window: whatever the window's
            # wall wasn't attributed (data wait, host pulls, compile,
            # checkpoint/elastic stalls) is productive device-execute;
            # the executable's own FLOPs feed the live MFU gauge
            _goodput.step_boundary(flops=self._step_flops)
        return Tensor(loss)

    def opt_state_bytes_per_rank(self):
        """Bytes of optimizer state (accumulators + amp master weights)
        ONE rank holds: sharded ZeRO slots count a single shard,
        replicated slots their full buffer. Also exported as the
        train.opt_state_bytes gauge once per build."""
        opt = self.optimizer
        return sum(_per_rank_nbytes(v) for v in opt._state.values()) + \
            sum(_per_rank_nbytes(v) for v in opt._master_weights.values())

    def _lower_flops(self, call_args):
        """The executable's own FLOP count via lowered.cost_analysis()
        (the distributed/auto_parallel/cost_model.py seam) — one extra
        abstract trace, paid only on the first ARMED call."""
        try:
            with _devev.tagged(self._exec_tag):
                lowered = self._compiled.lower(*call_args)
            ca = lowered.cost_analysis() or {}
            if isinstance(ca, (list, tuple)):
                ca = ca[0] if ca else {}
            return float(ca.get("flops", 0.0) or 0.0)
        except Exception:
            return 0.0


def train_step(model, optimizer, step_fn, **kw):
    return TrainStep(model, optimizer, step_fn, **kw)


class InputSpec:
    """ref: paddle.static.InputSpec — shape/dtype signature for export."""

    def __init__(self, shape, dtype="float32", name=None):
        self.shape = tuple(shape)
        self.dtype = dtype
        self.name = name


def save(layer, path, input_spec=None, **configs):
    """ref: paddle.jit.save (python/paddle/jit/api.py). Persists BOTH the
    weights (`path.pdparams`) and, when `input_spec` is given, a serialized
    StableHLO program (`path.pdmodel` via jax.export) — the TPU-native
    inference artifact: `jit.load` runs it WITHOUT the model's Python code,
    like the reference's saved Program + TranslatedLayer."""
    from ..framework import io as fio
    fio.save(layer.state_dict(), path + ".pdparams")
    if input_spec is None:
        return
    from jax import export as jexport

    from ..framework import core

    state = {k: t.data for k, t in layer.state_dict().items()}

    def fwd(state, *inputs):
        with layer.use_state(state), core.no_grad_guard():
            out = layer(*_tree_box(list(inputs)))
        return _tree_unbox(out)

    # dynamic dims (None/-1) export as symbolic shapes so the artifact
    # accepts any size there (jax.export shape polymorphism)
    abstract = []
    for i, s in enumerate(input_spec):
        dt = core.convert_dtype(getattr(s, "dtype", "float32"))
        if any(d is None or d == -1 for d in s.shape):
            dims = ",".join(
                f"b{i}_{j}" if (d is None or d == -1) else str(d)
                for j, d in enumerate(s.shape))
            abstract.append(jax.ShapeDtypeStruct(
                jexport.symbolic_shape(dims), dt))
        else:
            abstract.append(jax.ShapeDtypeStruct(tuple(s.shape), dt))
    state_abs = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), state)
    try:   # portable artifact when every op lowers for both platforms
        exp = jexport.export(jax.jit(fwd), platforms=("cpu", "tpu"))(
            state_abs, *abstract)
    except Exception as e:
        import warnings
        warnings.warn(
            f"jit.save: multi-platform (cpu+tpu) lowering failed "
            f"({type(e).__name__}: {str(e)[:200]}); exporting for the "
            f"current backend only — the artifact will not load on other "
            "platforms", stacklevel=2)
        exp = jexport.export(jax.jit(fwd))(state_abs, *abstract)
    from ..framework.io import atomic_write
    blob = exp.serialize()
    # atomic commit: a crash mid-serialize must not tear the inference
    # artifact or destroy the previous one (ROADMAP lint-coverage item)
    atomic_write(path + ".pdmodel", lambda f: f.write(blob))


class TranslatedLayer:
    """Runs an exported program without model code (ref: jit/translated_layer)."""

    def __init__(self, exported, state):
        self._exported = exported
        self._state = state

    def __call__(self, *inputs):
        arrs = [x.data if isinstance(x, Tensor) else jnp.asarray(x)
                for x in inputs]
        out = self._exported.call(self._state, *arrs)
        return jax.tree_util.tree_map(
            lambda a: Tensor(a, stop_gradient=True), out)

    forward = __call__

    def state_dict(self):
        return {k: Tensor(v, stop_gradient=True)
                for k, v in self._state.items()}

    def eval(self):
        return self

    def train(self):
        raise RuntimeError("exported inference programs cannot be trained")


def load(path, **configs):
    """paddle.jit.load: with a .pdmodel artifact returns a TranslatedLayer
    (callable, no model code needed); otherwise the raw state dict."""
    import os

    from ..framework import io as fio
    state = fio.load(path + ".pdparams")
    if not os.path.exists(path + ".pdmodel"):
        return state
    from jax import export as jexport
    with open(path + ".pdmodel", "rb") as f:
        exp = jexport.deserialize(f.read())
    arrs = {k: (v.data if isinstance(v, Tensor) else jnp.asarray(v))
            for k, v in state.items()}
    return TranslatedLayer(exp, arrs)
