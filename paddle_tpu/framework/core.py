"""Global framework state: dtypes, default device, RNG, grad mode.

TPU-native re-design of the reference's global state:
  - dtype registry  (ref: paddle/phi/common/data_type.h)
  - flags           (ref: paddle/phi/core/flags.cc — 136 exported flags)
  - RNG             (ref: paddle/phi/core/generator.cc) — here a functional
    JAX key-stack so randomness is traceable under jit.
"""
from __future__ import annotations

import contextlib
import os
import threading
import zlib
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

# ---------------------------------------------------------------------------
# dtypes
# ---------------------------------------------------------------------------

_DTYPE_ALIASES = {
    "float32": jnp.float32, "fp32": jnp.float32, "float": jnp.float32,
    "float64": jnp.float64, "fp64": jnp.float64, "double": jnp.float64,
    "float16": jnp.float16, "fp16": jnp.float16, "half": jnp.float16,
    "bfloat16": jnp.bfloat16, "bf16": jnp.bfloat16,
    "int8": jnp.int8, "int16": jnp.int16, "int32": jnp.int32,
    "int64": jnp.int64, "uint8": jnp.uint8, "uint16": jnp.uint16,
    "uint32": jnp.uint32, "uint64": jnp.uint64,
    "bool": jnp.bool_,
    "complex64": jnp.complex64, "complex128": jnp.complex128,
    "float8_e4m3fn": jnp.float8_e4m3fn, "float8_e5m2": jnp.float8_e5m2,
}

# canonical names exposed as module-level dtype objects (paddle.float32 etc.)
DTYPE_NAMES = [
    "float32", "float64", "float16", "bfloat16", "int8", "int16", "int32",
    "int64", "uint8", "bool", "complex64", "complex128",
]


def convert_dtype(dtype: Any):
    """Normalize a user-facing dtype (str / np / jnp dtype) to a jnp dtype.

    With x64 disabled (the TPU-friendly default), 64-bit requests silently
    narrow to their 32-bit counterparts, mirroring JAX's own behavior.
    """
    if dtype is None:
        return None
    if isinstance(dtype, str):
        d = _DTYPE_ALIASES.get(dtype)
        if d is None:
            raise ValueError(f"Unknown dtype {dtype!r}")
        return jnp.dtype(d) if not jax.config.jax_enable_x64 else np.dtype(d)
    try:
        return jnp.dtype(dtype)  # canonicalizes under current x64 setting
    except TypeError:
        raise ValueError(f"Unknown dtype {dtype!r}")


def dtype_name(dtype) -> str:
    return np.dtype(dtype).name if dtype is not None else "None"


_default_dtype = jnp.float32


def set_default_dtype(d):
    global _default_dtype
    d = convert_dtype(d)
    if np.dtype(d).kind != "f":
        raise TypeError("default dtype must be floating point")
    _default_dtype = d


def get_default_dtype():
    return _default_dtype


# ---------------------------------------------------------------------------
# device (ref: paddle.set_device / phi::Place)
# ---------------------------------------------------------------------------

_device: Optional[str] = None


def set_device(device: str):
    """'tpu', 'cpu', 'tpu:0' — maps onto jax default device."""
    global _device
    name = device.split(":")[0]
    idx = int(device.split(":")[1]) if ":" in device else 0
    plats = {d.platform for d in jax.devices()}
    if name in ("gpu", "cuda"):
        name = "tpu" if "tpu" in plats else "cpu"
    if name == "tpu" and "tpu" not in plats:
        # single-host CPU emulation (tests); stay on default backend
        name = jax.default_backend()
    devs = [d for d in jax.devices() if d.platform == name] or jax.devices()
    jax.config.update("jax_default_device", devs[min(idx, len(devs) - 1)])
    _device = device
    return device


def get_device() -> str:
    if _device is not None:
        return _device
    return jax.default_backend() + ":0"


def is_compiled_with_cuda() -> bool:
    return False


def is_compiled_with_xpu() -> bool:
    return False


def is_compiled_with_tpu() -> bool:
    return True


# ---------------------------------------------------------------------------
# grad mode (ref: egr::Controller tracer state)
# ---------------------------------------------------------------------------

class _GradState(threading.local):
    def __init__(self):
        self.enabled = True


_grad_state = _GradState()


def is_grad_enabled() -> bool:
    return _grad_state.enabled


def set_grad_enabled(flag: bool):
    _grad_state.enabled = bool(flag)


@contextlib.contextmanager
def no_grad_guard():
    prev = _grad_state.enabled
    _grad_state.enabled = False
    try:
        yield
    finally:
        _grad_state.enabled = prev


# ---------------------------------------------------------------------------
# Remat policy: a trace-time context threading a jax.checkpoint `policy`
# (e.g. save_only_these_names over checkpoint_name-stamped matmul
# outputs) from jit.TrainStep down to the jax.checkpoint sites inside
# the models (_scan_stack/_recompute_stack). None (the default) leaves
# jax.checkpoint at its save-nothing default — bitwise today's remat.
# ---------------------------------------------------------------------------

class _RematState(threading.local):
    def __init__(self):
        self.policy = None


_remat_state = _RematState()


def current_remat_policy():
    """The jax.checkpoint policy callable armed for this trace (None =
    jax.checkpoint's default: save nothing, recompute everything)."""
    return _remat_state.policy


def remat_keeps(name: str) -> bool:
    """Whether the policy armed for this trace keeps a value stamped
    `checkpoint_name(.., name)` across the backward (asked of the policy
    itself, as jax.checkpoint asks: the name primitive and its name)."""
    policy = _remat_state.policy
    if policy is None:
        return False
    from jax.ad_checkpoint import Saveable, checkpoint_name
    stamp = jax.make_jaxpr(lambda a: checkpoint_name(a, name))(0.0).eqns[0]
    kept = policy(stamp.primitive, *(v.aval for v in stamp.invars),
                  **stamp.params)
    return kept is True or kept is Saveable


@contextlib.contextmanager
def remat_policy_guard(policy):
    prev = _remat_state.policy
    _remat_state.policy = policy
    try:
        yield
    finally:
        _remat_state.policy = prev


# ---------------------------------------------------------------------------
# RNG: stateful shell over functional JAX keys.
#
# Eager ops fold a counter into the global key (fast, reproducible).
# Under `jit`/functional training steps, a key can be pushed on a
# context stack so randomness is traced (ref: phi Generator + paddle.seed).
# ---------------------------------------------------------------------------

class RandomState(threading.local):
    def __init__(self):
        # key creation is LAZY: materializing a PRNG key initializes the
        # XLA backend, and `import paddle_tpu` must not do that — multi-
        # host users call jax.distributed.initialize / init_parallel_env
        # after import, which JAX requires to happen before first backend
        # use (SURVEY §2.4 bootstrap)
        self.key = None
        self.counter = 0
        self.stack = []  # traced keys pushed by functional contexts
        self._base_data = None   # host cache for base_rng_key_data()

    def seed(self, s: int):
        self.key = jax.random.key(s)
        self.counter = 0
        self._base_data = None   # host cache for base_rng_key_data()

    def next_key(self):
        if self.stack:
            # functional/traced mode: split the context key in place
            k, sub = jax.random.split(self.stack[-1])
            self.stack[-1] = k
            return sub
        if self.key is None:
            self.key = jax.random.key(0)
        self.counter += 1
        return jax.random.fold_in(self.key, self.counter)


_rng = RandomState()

# last paddle.seed value, PROCESS-global (the key-stack RandomState above
# is thread-local): DataLoader worker/prefetch threads derive their host
# numpy seeds from this, and a fresh thread must see the seed set by the
# main thread, not a blank thread-local
_seed_value: Optional[int] = None


def seed(s: int):
    global _seed_value, _data_instance_seq
    _seed_value = int(s)
    _data_instance_seq = 0
    _rng.seed(s)
    return _rng


_data_instance_seq = 0


def next_data_instance() -> int:
    """Monotonic id decorrelating sibling samplers' derived seeds (two
    shuffled loaders must not emit the same permutation). Reset by
    `seed()` so a re-seeded run reconstructs the same ids in the same
    construction order — reproducibility is preserved. Consequence: two
    samplers constructed under identical (seed value, construction
    index) pairs — e.g. one before and one after re-seeding with the
    SAME value — shuffle in lockstep; re-seed with a different value or
    pass explicit `generator`s to decorrelate them."""
    global _data_instance_seq
    v = _data_instance_seq
    _data_instance_seq += 1
    return v


def data_seed(*salt) -> Optional[int]:
    """Host-side numpy seed derived from `paddle.seed` for the data
    pipeline (io samplers, random_split, shuffle order): deterministic
    per (seed, *salt), touches no device state, readable from any
    thread. None when the process was never seeded — callers fall back
    to nondeterministic numpy seeding (the pre-seed behavior)."""
    if _seed_value is None:
        return None
    h = _seed_value & 0xFFFFFFFF
    for s in salt:
        h = (h * 1000003 + zlib.crc32(str(s).encode())) & 0xFFFFFFFF
    return h


def next_rng_key():
    return _rng.next_key()


def base_rng_key_data():
    """The seed key's raw uint32 data as HOST numpy, cached per seed.

    Compiled steps (TrainStep) take this once-per-seed constant and
    fold the step counter in INSIDE the executable — the previous
    per-call `fold_in` + `key_data` ran two tiny device programs per
    step, a synchronous device round trip each, for what is a
    host-side constant."""
    if _rng.key is None:
        _rng.seed(0)
    if _rng._base_data is None:
        _rng._base_data = np.asarray(jax.random.key_data(_rng.key))
    return _rng._base_data


@contextlib.contextmanager
def rng_key_context(key):
    _rng.stack.append(key)
    try:
        yield
    finally:
        _rng.stack.pop()


def get_rng_state():
    return (_rng.key, _rng.counter)


def set_rng_state(state):
    _rng.key, _rng.counter = state
    _rng._base_data = None   # restored key invalidates the host cache


# ---------------------------------------------------------------------------
# flags (ref: paddle/phi/core/flags.cc; paddle.set_flags)
# ---------------------------------------------------------------------------

_flags: dict = {
    # -- debugging (consumed by autograd/tape.py + jit TrainStep) ------
    "FLAGS_check_nan_inf": False,
    # warn-and-continue variant of the nan/inf sweep
    # (amp.debugging DebugMode.CHECK_NAN_INF / CHECK_ALL)
    "FLAGS_check_nan_inf_warn_only": False,
    # 0 = raise on nan/inf, 1 = warn only (alias view of the above,
    # matching the reference's numeric level knob)
    "FLAGS_check_nan_inf_level": 0,
    # exception verbosity of tape op errors: 0 terse, >=1 full op
    # context (consumed by tape._op_error)
    "FLAGS_call_stack_level": 1,
    # -- determinism (consumed below in _apply_flag) -------------------
    "FLAGS_cudnn_deterministic": False,
    "FLAGS_cpu_deterministic": False,
    "FLAGS_embedding_deterministic": 0,
    # -- eager dispatch cache (consumed by autograd/tape.apply_op): the
    # compile-once fast path for repeated eager ops; 0 restores the
    # per-call jax.vjp re-trace (kill switch for debugging)
    "FLAGS_eager_dispatch_cache": True,
    "FLAGS_eager_dispatch_cache_size": 1024,   # LRU bound (entries)
    # -- chaos / robustness testing (consumed by utils/fault_injection):
    # deterministic fault schedule, e.g. "ckpt.write_shard:crash@2" —
    # empty = disarmed (fault_point() sites are a single bool check)
    "FLAGS_fault_inject": "",
    # -- distributed watchdog (consumed by distributed/watchdog.py):
    # seconds a collective may stall before the watchdog fires
    "FLAGS_comm_timeout": 1800.0,
    # -- runtime telemetry (consumed by observability/*): arming bool for
    # the metrics registry + span ring (disarmed sites are a single bool
    # check, same discipline as FLAGS_fault_inject), the background
    # Prometheus /metrics HTTP port (0 = off), the crash flight-recorder
    # JSONL path (empty = off), and the span ring bound
    "FLAGS_metrics": False,
    "FLAGS_metrics_port": 0,
    "FLAGS_flight_recorder": "",
    "FLAGS_span_ring_size": 512,
    # federation (consumed by observability/federation.py): path of this
    # process's atomically-rewritten registry-snapshot JSON (empty =
    # off; the launch supervisor sets it per child so the master can
    # merge one job-level /metrics), and the rewrite interval in seconds
    "FLAGS_metrics_snapshot": "",
    "FLAGS_metrics_snapshot_interval": 2.0,
    # request tracing (consumed by inference/serving.py +
    # observability/reqtrace.py): per-request event timelines and the
    # exact tail-latency attribution ledger (sum(buckets) == wall); ON
    # by default — =0 restores the pre-trace tick loop bitwise. The sink
    # is an append-only JSONL path (empty = in-memory store only); the
    # replica supervisor sets it per child so a SIGKILLed replica's
    # traces survive for the router's fleet-scope /v1/trace lookup
    "FLAGS_request_trace": True,
    "FLAGS_request_trace_sink": "",
    # lockdep-style lock-order witness (consumed by
    # observability/lockwitness.py): wraps threading.Lock/RLock
    # construction to report order inversions (potential deadlocks that
    # never fired), held-too-long and blocked-under-lock events through
    # the metrics registry + flight recorder. Default off: the wrappers
    # are never even installed (zero overhead); armed by the chaos
    # suite and the threaded tier-1 witness tests
    "FLAGS_lock_witness": False,
    # -- input pipeline (consumed by io/prefetch.py + io DataLoader):
    # device-side double-buffered batch staging via jax.device_put; false
    # restores the synchronous un-staged loader path (the debugging kill
    # switch — e.g. to localize a worker-thread fault to one batch)
    "FLAGS_dataloader_prefetch": True,
    # -- autotune (consumed by kernels/autotune.sweeps_enabled) --------
    "FLAGS_use_autotune": True,
    # kernel-route kill switch (an on-chip ablation lever; analog of
    # the reference's cudnn/flash deterministic+enable toggles).
    # Default FALSE: the only two on-chip measurements bracket the
    # route — r2 (XLA CE) 23,126 tok/s/chip vs r4 (fused CE on,
    # UNTUNED — its autotune sweep died mid-run) 19,011. Until the
    # attribution session proves the Pallas CE faster, the measured
    # configuration is the default; FLAGS_use_fused_ce=1 opts in
    # (ROADMAP.md S2).
    "FLAGS_use_fused_ce": False,       # Pallas blockwise CE vs XLA CE
    # -- serving (consumed by inference/serving.py): ragged paged
    # attention + chunked-prefill continuous batching; 0 is the kill
    # switch restoring the bucketed-prefill engine exactly
    "FLAGS_ragged_attention": True,
    # SLO resilience layer over the serving engine: priority/deadline
    # scheduling, admission control + shedding, adaptive degradation,
    # per-request fault isolation. 0 is the kill switch restoring the
    # FIFO scheduler exactly (same admission order, same preemption
    # victims, same compiled step signatures)
    "FLAGS_serving_slo": True,
    # self-speculative decoding (chunked-prefill regime, greedy only):
    # an n-gram prompt-lookup drafter proposes up to
    # FLAGS_speculative_draft_tokens continuation tokens per decode
    # slot, packed as q_len=k+1 verification rows into the SAME ragged
    # step (and the same max_chunk_tokens row budget, so the compiled
    # shape never changes); greedy argmax verification accepts the
    # longest agreeing prefix and rolls rejected KV back exactly.
    # FLAGS_speculative=0 is the kill switch: no drafting, single-token
    # decode rows, outputs AND the per-tick scheduling trace bitwise
    # the pre-speculation engine
    "FLAGS_speculative": True,
    "FLAGS_speculative_draft_tokens": 4,
    # prefix caching over the KV page pool (chunked-prefill regime
    # only): a content-hash index of fully-written prompt pages with
    # refcounted sharing, so a repeated system-prompt/few-shot prefix
    # is prefilled once and later admissions attach the cached pages.
    # 0 is the kill switch: no index, every page refcount-1, the engine
    # is token-identical AND allocation-identical to the uncached one
    "FLAGS_prefix_cache": True,
    # serving fleet (consumed by inference/fleet.py): N supervised
    # serve replicas behind the cache-affinity failover router
    # (`python -m paddle_tpu.inference.fleet`). 0 is the kill switch:
    # the fleet CLI collapses to a direct single-process
    # `inference.serve` run — byte-identical wire behavior, no router
    "FLAGS_serving_fleet": True,
    # -- quantized collectives (consumed by distributed/collective.py +
    # the jit.TrainStep/ShardingPlan grad-sync seam): armed capability
    # for the blockwise int8/fp8 communication path — quantization still
    # needs an explicit opt-in at the call site (all_reduce(quantized=)
    # or ShardingPlan(grad_sync=)); 0 is the kill switch restoring the
    # exact psum/GSPMD paths bitwise even for opted-in callers. The
    # block knob sets the absmax-scale granularity (elements per f32
    # scale on the wire).
    "FLAGS_quant_collectives": True,
    "FLAGS_quant_collectives_block": 256,
    # -- ZeRO sharded optimizer update (consumed by jit.TrainStep +
    # ShardingPlan(zero=)): armed capability for the explicit
    # reduce-scatter -> per-shard update -> all-gather weight-update
    # path (arxiv 2004.13336). Like FLAGS_quant_collectives it gates at
    # TrainStep BUILD time, so 0 is a kill switch that compiles the
    # exact pre-ZeRO replicated paths bitwise even for opted-in plans.
    "FLAGS_zero": True,
    "FLAGS_cudnn_exhaustive_search": False,     # alias: force sweeps
    # -- numerics (consumed in _apply_flag -> jax matmul precision) ----
    "FLAGS_gemm_use_half_precision_compute_type": True,
    # -- profiling / logging (consumed by jit.TrainStep) ---------------
    "FLAGS_benchmark": False,          # print per-step wall time
    # -- executor/memory behavior (consumed by jit.TrainStep) ----------
    "FLAGS_max_inplace_grad_add": 0,   # >0 enables buffer donation
    "FLAGS_eager_delete_tensor_gb": 0.0,  # <0 disables donation
    # -- allocator knobs: mapped onto XLA client env at set time; only
    # effective before backend init (documented XLA seam) --------------
    "FLAGS_fraction_of_gpu_memory_to_use": 0.92,
    "FLAGS_allocator_strategy": "auto_growth",
    "FLAGS_gpu_memory_limit_mb": 0,
    # -- API-compat registry (accepted + queryable; the machinery they
    # steer is XLA-internal on TPU) -------------------------------------
    "FLAGS_conv_workspace_size_limit": 512,
    "FLAGS_cudnn_batchnorm_spatial_persistent": False,
    "FLAGS_enable_cublas_tensor_op_math": True,
    "FLAGS_use_system_allocator": False,
    "FLAGS_use_pinned_memory": True,
    "FLAGS_init_allocated_mem": False,
    "FLAGS_initial_cpu_memory_in_mb": 500,
    "FLAGS_memory_fraction_of_eager_deletion": 1.0,
    "FLAGS_fast_eager_deletion_mode": True,
    "FLAGS_use_mkldnn": False,
    "FLAGS_enable_pir_api": True,
    "FLAGS_new_executor_serial_run": False,
    "FLAGS_low_precision_op_list": 0,
    "FLAGS_print_model_stats": False,
    "FLAGS_sync_nccl_allreduce": True,
    "FLAGS_fuse_parameter_memory_size": -1,
    "FLAGS_rpc_deadline": 180000,
    "FLAGS_apply_pass_to_program": False,
}


def _apply_flag(key, value):
    """Side effects of flags that steer global backends (the reference
    applies these in phi::SetFlag handlers)."""
    if key in ("FLAGS_cudnn_deterministic", "FLAGS_cpu_deterministic"):
        # NOTE: XLA_FLAGS is read at backend INIT — setting this after
        # the first jax computation affects only later-spawned backends
        # (same limitation as the reference's cudnn flag after ctx init)
        flags = os.environ.get("XLA_FLAGS", "")
        tok = "--xla_gpu_deterministic_ops=true"
        if value and tok not in flags:
            os.environ["XLA_FLAGS"] = (flags + " " + tok).strip()
        elif not value and tok in flags:
            os.environ["XLA_FLAGS"] = flags.replace(tok, "").strip()
    elif key == "FLAGS_gemm_use_half_precision_compute_type":
        try:
            jax.config.update("jax_default_matmul_precision",
                              "default" if value else "highest")
        except Exception:
            pass
    elif key == "FLAGS_fraction_of_gpu_memory_to_use":
        os.environ["XLA_PYTHON_CLIENT_MEM_FRACTION"] = str(value)
    elif key == "FLAGS_allocator_strategy":
        # auto_growth -> on-demand allocation; naive_best_fit -> XLA
        # preallocation (only effective before backend init)
        os.environ["XLA_PYTHON_CLIENT_PREALLOCATE"] = (
            "false" if value == "auto_growth" else "true")
    elif key == "FLAGS_check_nan_inf_level":
        _flags["FLAGS_check_nan_inf_warn_only"] = bool(int(value) >= 1)
    elif key == "FLAGS_fault_inject":
        from ..utils import fault_injection
        fault_injection.configure(value if isinstance(value, str) else None)
    elif key == "FLAGS_metrics":
        from .. import observability
        observability.enable(value not in _FALSY)
    elif key == "FLAGS_metrics_port":
        from ..observability import export as _oexp
        _oexp.serve_metrics(int(value or 0))
    elif key == "FLAGS_flight_recorder":
        from ..observability import export as _oexp
        if value:
            _oexp.install_flight_recorder(str(value))
        else:
            _oexp.uninstall_flight_recorder()
    elif key == "FLAGS_span_ring_size":
        from ..observability import spans as _ospans
        _ospans.set_ring_size(int(value))
    elif key == "FLAGS_metrics_snapshot":
        from ..observability import federation as _ofed
        if value:
            _ofed.start_publisher(str(value))
        else:
            _ofed.stop_publisher(final=False)
    elif key == "FLAGS_metrics_snapshot_interval":
        from ..observability import federation as _ofed
        if _ofed._publisher is not None:
            _ofed._publisher.interval = max(0.05, float(value))
    elif key == "FLAGS_lock_witness":
        from ..observability import lockwitness
        lockwitness.enable(value not in _FALSY)
    elif key == "FLAGS_request_trace_sink":
        from ..observability import reqtrace as _ortrace
        _ortrace.set_sink(str(value) if value else None)
    elif key == "FLAGS_eager_dispatch_cache_size":
        from ..autograd import tape  # late: tape imports this module
        tape._dispatch_cache.resize(int(value))
    elif key == "FLAGS_eager_dispatch_cache" and value in _FALSY:
        # disabling also drops the cached executables (debugging hygiene)
        from ..autograd import tape
        tape.clear_dispatch_cache()


def set_flags(flags: dict):
    for k, v in flags.items():
        _flags[k] = v
        _apply_flag(k, v)


def get_flags(keys):
    if isinstance(keys, str):
        keys = [keys]
    return {k: _flags.get(k) for k in keys}


def get_flag(key, default=None):
    env = os.environ.get(key)
    if env is not None:
        return env
    return _flags.get(key, default)


_FALSY = (False, None, 0, 0.0, "0", "false", "False", "", "off", "OFF")


def get_bool_flag(key, default=False) -> bool:
    """Boolean view of a flag: env-set flags arrive as STRINGS, so
    bool('0') would invert every kill switch — normalize here (single
    place; every boolean flag consumer must use this)."""
    v = get_flag(key, default)
    return v not in _FALSY
