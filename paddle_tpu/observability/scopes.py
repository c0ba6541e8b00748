"""The names the compiled train step carries, in ONE place.

`jax.named_scope` names land in every device operation's `op_name`
(XProf, `compiled.as_text()`); spans name what the host does while it
traces or dispatches. Both are read by name (`chipbench/components.json`,
`chipbench/scope_reduce.py`, PERF.md section 3), so the names live here
and nowhere else. Scopes are metadata of the compiled program: no flag,
no cost at run time, and no part of the compile-cache key.
"""
from __future__ import annotations

import contextlib
import threading

import jax

from . import spans as _spans

# jit.TrainStep: the statements of the step body, as scopes on the device
# and (first trace) as set-up spans `train_step.<phase>` on the host
PHASES = ("forward", "backward", "grad_sync", "optimizer")
# models: what a Layer's registered name does not say
COMPONENTS = ("embed", "layers", "norm", "attn/qkv", "attn/rope",
              "attn/core", "attn/out", "mlp", "head", "loss",
              # models/solar_open2: the gate of a gated softmax layer, the
              # parts of a gated delta-rule layer, and of an expert layer
              "attn/gate", "kda/proj", "kda/conv", "kda/gate", "kda/core",
              "kda/out", "moe/router", "moe/dispatch", "moe/experts",
              "moe/shared", "moe/combine",
              # models/granite_hybrid: the parts of a Mamba-2 layer (proj:
              # z | xBC | dt; dt: softplus, decay, running sums; norm: the
              # gate and the norm)
              "ssm/proj", "ssm/conv", "ssm/dt", "ssm/core", "ssm/norm",
              "ssm/out",
              # models/dots3_note: latent attention's projections and
              # latent norms are attn/qkv, its head gates attn/gate; the
              # core is named by its kind (both nest in attn/core); the
              # learned selection's indexer (projections, scores, its
              # loss and that loss's backward) and the top-k itself
              "attn/core/selected", "attn/core/window", "attn/index",
              "attn/select",
              # models/glm4_moe_lite: the dense-causal latent core (nests in
              # attn/core like the two above), and the multi-token-prediction
              # module after the last layer: the shifted ids' embedding, the
              # two norms and the [2H, H] product, its one expert layer
              # (whose inner attn/* and moe/* scopes stay as they are), its
              # pass over the shared head and its loss
              "attn/core/causal", "mtp/embed", "mtp/proj", "mtp/block",
              "mtp/head", "mtp/loss",
              # models/xing4_0 (`pieces.HyperConnection`): the n-stream
              # residual path of a half-layer: the three maps (norm, the
              # product with Phi, sigmoids, Sinkhorn), n streams -> the
              # branch's input, streams and branch -> n streams; the
              # embedding's expansion and the sum in front of a final norm
              "hc/map", "hc/pre", "hc/post", "hc/expand", "hc/reduce",
              # models/lfm2_moe: the parts of a gated short-convolution
              # layer (proj: the one [H, 3H] product into B | C | X; core:
              # B * X, the taps, C *; out: the output projection and the
              # residual add), and a head's own RMSNorm of q and k in front
              # of rotary (`pieces.qk_norm_rope`)
              "conv/proj", "conv/core", "conv/out", "attn/qk_norm")
# distributed/sharding: collectives the program itself issues
COLLECTIVES = ("tp/all_reduce", "tp/relayout")
# jit.TrainStep.__call__: TraceAnnotations, on the profiler's host plane
STEP_SPANS = ("train_step.call_args", "train_step.dispatch",
              "train_step.write_back")
# set-up events in spans.ring(): lower() and its parts, the trace count,
# what the armed remat policy keeps of a kernel's forward for its backward
# (kernels/flash_attention: name, bytes a call), how a splash call's
# backward is made (kernels/flash_attention `splash_backward`,
# `train_step.splash_backward`: form = "one_kernel" where dq, dk and dv
# come from the library's one kernel, "two_kernels" under a window or where
# one kv head's copies of dq alone would pass the stated bound; partials =
# the copies of dq that leave the one kernel, Sk // block_kv_dkv, summed
# after; partial_bytes = what the copies live at one time hold;
# block_kv_dkv = the dkv kernel's outer kv block; kv_heads_a_call = the kv
# heads one kernel call takes, fewer than the call's where all at once
# would pass the bound: the calls then go one after the other; once a
# traced call), the grid a tile-walking
# kernel of the learned selection was given (kernels/sparse_select_attention:
# kernel, grid_steps, live_tiles, heads_per_step, rows, keys; once a kernel
# a trace), how a delta-rule layer went over its heads (models/solar_open2
# `KDAttention.block`: groups, heads_per_group,
# hidden_width_products_in_group = the products inside a group's scan that
# have a hidden_size side, shared_columns = the columns of the one product
# a layer the groups share, stacked_out_bytes = the groups' outputs in
# front of the output projection; once a layer a trace), how the kernel
# calls of a sharded step were mapped (distributed/sharding
# `kernel_mesh_guard`: mapped = the shard_kernel calls that went into a
# shard_map, tracked = those of them that ran with variance tracking, the
# row-wise ones, unsummed = for each of those, in call order and joined by
# ",", the mesh axes ("+" between them) over which its backward sums no
# cotangent because no spec of the call is split over them; once a trace
# that maps a call), what the loss's multi-token-prediction module is
# (models/glm4_moe_lite `losses`: depth, loss_weight, positions = the rows
# of a sequence its loss counts, shares_embedding, shares_head, block_kind;
# once a trace), what the n-stream residual path is (models/xing4_0
# `losses`: n, iterations = Sinkhorn's, halves = the half-layers on such a
# path, stream_array_bytes = one [n, B, S, H] array, kept_one_stream_bytes
# = one [B, S, H] array, attention_half_keeps / ffn_half_keeps = what a
# half's taped operation keeps for the backward, in words; once a trace),
# how an expert layer moves its rows (nn/layer/moe
# `dropless_moe`: route = "kernel" where the scatter-adds of combine and of
# dispatch's transpose are kernels/row_moves' Pallas kernel, "xla" where
# they are the compiler's scatter; rows = the buffer's, hidden, row_bytes,
# tokens, tile = the targets a grid step holds, chunk = the ordered rows a
# visit; once an expert layer a trace), what a call of `Optimizer.prime()`
# that made anything made (optimizer/optimizer: programs = 1, or 0 where it
# ran inline under a trace; slots_made, slots_kept = the slots that were
# there and were left alone, parameters = those that got a slot; dur_s),
# the step's device memory, and what jax.monitoring reports of lowering,
# compiling and the cache.
# Memory, two events an operator reads with `step.lower(*batch).compile()`
# and then `observability.spans.ring()` (the runtime's `peak_bytes_in_use`
# is the process's high-water mark, not the step's: it never showed the
# step's temporaries): `train_step.memory`, once a compile of the lowered
# step: the compiler's bytes a device (argument_bytes, output_bytes,
# alias_bytes = the donated state, temp_bytes = the size of the region the
# temporaries live in, generated_code_bytes, sum_bytes = their sum with
# alias subtracted, which over-counts and refuses nothing; peak_bytes =
# the compiler's own peak, arguments + the program's fullest moment, what
# its refusal prints as "used X of Y" (the sum on a backend that gives
# none); bytes_limit where the runtime has one; devices);
# `train_step.residuals`, once a key a trace (scope = "<vocabulary
# scope>:<taped op>", the one it has where an op lacks the other) and a
# total under scope "*": what the forward keeps for the backward (bytes,
# arrays, trace; the total also state_bytes = the step's own inputs among
# it, and shapes: "global" under GSPMD, "shard" where the body runs under
# shard_map)
SETUP = ("train_step.lower", "train_step.call_args", "train_step.trace",
         "train_step.to_mlir", "train_step.traced", "train_step.kept",
         "train_step.splash_backward", "train_step.memory",
         "train_step.residuals",
         "dsa.grid", "kda.groups", "shard_kernel.calls", "mtp.module",
         "hc.streams", "moe.rows", "optimizer.prime", "xla.to_mlir",
         "xla.backend_compile", "xla.cache_hit", "xla.cache_miss")

_SCOPES = frozenset(COMPONENTS + COLLECTIVES)
_tl = threading.local()          # .open: scopes open on this thread


@contextlib.contextmanager
def scope(name: str):
    """`jax.named_scope(name)` for a name of the vocabulary."""
    if name not in _SCOPES:
        raise ValueError(f"scope {name!r} is not in observability.scopes")
    stack = getattr(_tl, "open", None)
    if stack is None:
        stack = _tl.open = []
    stack.append(name)
    try:
        with jax.named_scope(name):
            yield
    finally:
        stack.pop()


def carried():
    """The innermost scope open on this thread, or None. `jax.vjp` keeps
    only the names entered INSIDE the function it differentiates, so the
    tape (autograd.apply_op) re-enters this one inside every op it
    records: the op's backward operations then carry it too."""
    stack = getattr(_tl, "open", None)
    return stack[-1] if stack else None


@contextlib.contextmanager
def phase(name: str, executable: str):
    """One statement of the step body: the device operations it traces
    carry `name`, and the host seconds the tracing took are the set-up
    span `train_step.<name>` (Python tracing time IS what lower() is
    made of)."""
    if name not in PHASES:
        raise ValueError(f"phase {name!r} is not in observability.scopes")
    with _spans.setup_span("train_step." + name, executable=executable), \
            jax.named_scope(name):        # not carried: see carried()
        yield
