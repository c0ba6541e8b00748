"""DistTensor / placements / reshard → JAX shardings
(ref: phi/core/distributed/auto_parallel/placement_types.h Shard/Replicate/
Partial; python/paddle/distributed/auto_parallel/api.py:124 shard_tensor,
:302 reshard; reshard functions phi/.../reshard/*).

TPU-native: a placement list maps 1:1 onto a PartitionSpec; `shard_tensor`
is `jax.device_put(NamedSharding)`; `reshard` is another device_put — XLA
emits exactly the r_to_s / s_to_r / p_to_r collective the reference
implements by hand per case. SPMD rules (phi/infermeta/spmd_rules/) are
GSPMD's propagation pass — nothing to reimplement.
"""
from __future__ import annotations

import contextlib
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..observability import scopes as _scopes
from ..observability import spans as _spans
from ..tensor import Parameter, Tensor


class Placement:
    pass


class Replicate(Placement):
    def __repr__(self):
        return "Replicate()"

    def __eq__(self, o):
        return isinstance(o, Replicate)

    def __hash__(self):
        return hash("replicate")


class Shard(Placement):
    def __init__(self, dim: int):
        self.dim = dim

    def get_dim(self):
        return self.dim

    def __repr__(self):
        return f"Shard(dim={self.dim})"

    def __eq__(self, o):
        return isinstance(o, Shard) and o.dim == self.dim

    def __hash__(self):
        return hash(("shard", self.dim))


class Partial(Placement):
    """Pending-reduction placement. GSPMD tracks partial sums internally;
    user-facing Partial materializes on reshard."""

    def __init__(self, reduce_type="sum"):
        self.reduce_type = reduce_type

    def __repr__(self):
        return f"Partial({self.reduce_type})"

    def __eq__(self, o):
        return isinstance(o, Partial) and o.reduce_type == self.reduce_type

    def __hash__(self):
        return hash(("partial", self.reduce_type))


class ProcessMesh:
    """ref: python/paddle/distributed/auto_parallel/process_mesh.py.
    Thin wrapper producing a jax Mesh over the same shape/dim_names."""

    def __init__(self, mesh=None, dim_names=None, shape=None, process_ids=None):
        if mesh is not None:
            arr = np.asarray(mesh)
            self.shape = list(arr.shape)
            self.process_ids = arr.ravel().tolist()
        else:
            self.shape = list(shape)
            self.process_ids = (list(process_ids) if process_ids is not None
                                else list(range(int(np.prod(self.shape)))))
        self.dim_names = (list(dim_names) if dim_names is not None
                          else [f"d{i}" for i in range(len(self.shape))])
        devs = np.asarray(jax.devices())
        n = int(np.prod(self.shape))
        assert n <= devs.size, (
            f"ProcessMesh wants {n} devices, only {devs.size} present")
        self._jax_mesh = Mesh(devs[:n].reshape(self.shape),
                              tuple(self.dim_names))

    @property
    def mesh(self):
        return np.asarray(self.process_ids).reshape(self.shape)

    @property
    def jax_mesh(self) -> Mesh:
        return self._jax_mesh

    @property
    def ndim(self):
        return len(self.shape)

    def get_dim_size(self, name):
        return self.shape[self.dim_names.index(name)]

    def __eq__(self, o):
        return (isinstance(o, ProcessMesh) and o.shape == self.shape
                and o.dim_names == self.dim_names)

    def __repr__(self):
        return f"ProcessMesh(shape={self.shape}, dim_names={self.dim_names})"


def _as_jax_mesh(mesh):
    if isinstance(mesh, ProcessMesh):
        return mesh.jax_mesh
    return mesh


def to_placements(placements, mesh, ndim) -> P:
    """placement-per-mesh-dim list -> PartitionSpec over tensor dims."""
    jm = _as_jax_mesh(mesh)
    axis_names = list(jm.axis_names)
    spec: List[Any] = [None] * ndim
    for mesh_dim, pl in enumerate(placements):
        if isinstance(pl, Shard):
            d = pl.dim
            if spec[d] is None:
                spec[d] = axis_names[mesh_dim]
            elif isinstance(spec[d], tuple):
                spec[d] = spec[d] + (axis_names[mesh_dim],)
            else:
                spec[d] = (spec[d], axis_names[mesh_dim])
    return P(*spec)


def placements_from_spec(spec: P, mesh, ndim):
    jm = _as_jax_mesh(mesh)
    axis_names = list(jm.axis_names)
    placements = [Replicate() for _ in axis_names]
    for d, entry in enumerate(tuple(spec) + (None,) * (ndim - len(tuple(spec)))):
        if entry is None:
            continue
        entries = entry if isinstance(entry, tuple) else (entry,)
        for a in entries:
            placements[axis_names.index(a)] = Shard(d)
    return placements


def shard_tensor(x, mesh, placements, dtype=None, stop_gradient=None):
    """ref: api.py:124 — place `x` with NamedSharding (GSPMD does layout)."""
    t = x if isinstance(x, Tensor) else Tensor(jnp.asarray(x))
    jm = _as_jax_mesh(mesh)
    spec = to_placements(placements, mesh, t.ndim)
    sharding = NamedSharding(jm, spec)
    data = jax.device_put(t.data, sharding)
    out = (Parameter(data, name=t.name) if isinstance(t, Parameter)
           else Tensor(data, stop_gradient=t.stop_gradient, name=t.name))
    if stop_gradient is not None:
        out.stop_gradient = stop_gradient
    out.pspec = spec
    if isinstance(x, Tensor):
        # in-place flavor used by shard-and-keep-module-reference patterns
        x.data = data
        x.pspec = spec
    return out


def reshard(x, mesh, placements):
    """ref: api.py:302 + phi reshard function table — one device_put."""
    jm = _as_jax_mesh(mesh)
    has_partial = any(isinstance(p, Partial) for p in placements)
    spec = to_placements(placements, mesh, x.ndim)
    data = x.data if isinstance(x, Tensor) else jnp.asarray(x)
    if has_partial:
        raise NotImplementedError(
            "explicit Partial targets are internal to compiled programs; "
            "reshard to Shard/Replicate instead")
    out_data = jax.device_put(data, NamedSharding(jm, spec))
    out = Tensor(out_data, stop_gradient=getattr(x, "stop_gradient", True))
    out.pspec = spec
    return out


def dtensor_from_fn(fn, mesh, placements, *args, **kwargs):
    t = fn(*args, **kwargs)
    return shard_tensor(t, mesh, placements)


def data_axes_for(dim_size: int, mesh=None) -> tuple:
    """Mesh axes that carry the batch dim of activations (dp + the ZeRO
    sharding axis, which is data-parallel for activations), greedily
    restricted to axes whose running product divides `dim_size` —
    sharding constraints applied EAGERLY (outside jit) and jit
    in_shardings hard-require divisibility. Used to FULLY pin activation
    layouts at resharding boundaries: a partial constraint (batch dim
    None) lets GSPMD invent a different layout in the checkpointed
    backward and fall into 'involuntary full rematerialization' at the
    boundary collective."""
    from .topology import get_mesh
    mesh = mesh if mesh is not None else get_mesh()
    if mesh is None:
        return ()
    axes, prod = [], 1
    for a in ("dp", "sharding"):
        if a in mesh.axis_names and mesh.shape[a] > 1 \
                and dim_size % (prod * mesh.shape[a]) == 0:
            axes.append(a)
            prod *= mesh.shape[a]
    return tuple(axes)


def with_partial_annotation(x, spec: P):
    """with_sharding_constraint inside compiled programs.

    Routed through the tape (differentiable identity) — constructing a
    fresh Tensor here would sever the autograd graph and silently zero the
    gradients of everything upstream (r2 fix).
    """
    from jax.lax import with_sharding_constraint
    from .topology import get_mesh
    mesh = get_mesh()
    if mesh is None:
        return x
    if isinstance(x, Tensor):
        from ..autograd.tape import apply_op
        return apply_op(
            lambda a: with_sharding_constraint(a, NamedSharding(mesh, spec)),
            x, name="sharding_constraint")
    return with_sharding_constraint(x, NamedSharding(mesh, spec))


# Pallas (Mosaic) kernels cannot be partitioned by GSPMD: under a mesh a
# kernel call has to sit inside shard_map, each device running it on its
# own slice. compile_train_step arms its mesh here while the step traces;
# the models' kernel call sites go through shard_kernel. Beside each armed
# mesh: what its shard_kernel calls left unsummed, one entry a mapped call
# (None for a call without variance tracking), for the set-up event.
_kernel_meshes: List[tuple] = []


@contextlib.contextmanager
def kernel_mesh_guard(mesh: Mesh):
    """Arm `mesh` for the kernel call sites traced inside; on the way out
    one set-up event `shard_kernel.calls` (observability/scopes.py) says
    how the calls of this trace were mapped."""
    calls: list = []
    _kernel_meshes.append((mesh, calls))
    try:
        yield
    finally:
        _kernel_meshes.pop()
        if calls:
            tracked = [c for c in calls if c is not None]
            _spans.setup_event(
                "shard_kernel.calls", mapped=len(calls),
                tracked=len(tracked),
                unsummed=",".join("+".join(c) for c in tracked))


def _spec_axes(spec) -> tuple:
    """The names a PartitionSpec holds, flattened."""
    return tuple(a for e in spec if e is not None
                 for a in (e if isinstance(e, tuple) else (e,)))


def tp_all_reduce():
    """Scope `tp/all_reduce` while a step traces over a mesh with an mp
    axis, no scope otherwise: for the call sites whose tensor-parallel
    all-reduce is not in the program's own text. A row-parallel matmul's
    output is all-reduced in the forward pass and a column-parallel
    matmul's input gradient in the backward pass, both put in by the
    partitioner under the matmul's op_name. Of shard_kernel's own sums
    (`psum`) the ones over mp are swiglu's: the gradient of its
    activation, which enters replicated over mp and meets a different
    slice of the weight on each device. A row-wise call (the norms) sums
    nothing over mp: its copies there are equal."""
    mesh = _kernel_meshes[-1][0] if _kernel_meshes else None
    if mesh is None or "mp" not in mesh.axis_names or mesh.shape["mp"] < 2:
        return contextlib.nullcontext()
    return _scopes.scope("tp/all_reduce")


def shard_kernel(fn, in_specs, out_specs, batch: int, heads: int = 1):
    """`fn`, or `fn` inside shard_map over the mesh of the sharded step
    being traced (none armed, or one device: `fn` itself).

    Specs are PartitionSpecs over two ROLES, resolved against the mesh:
    "data" — the batch dim, split over the plan's dp and sharding axes
    as far as their running product divides `batch` (data_axes_for);
    "mp" — the heads / intermediate dim, split over mp when it divides
    `heads`. Whatever a role does not resolve to is replicated, so the
    kernel always gets whole rows and whole heads. Weights enter
    replicated over the data axes: shard_map gathers the FSDP shards on
    the way in and sums the weight gradient over them on the way out.

    What the backward sums. A call whose specs name "mp" (splash,
    swiglu) runs without variance tracking: shard_map's transpose sums
    the cotangent of every operand over every mesh axis its spec does
    not name, and for these calls each such sum is real (swiglu's da
    over mp, dw over the data axes). A call whose specs name no "mp"
    (the norms, the fused cross-entropy) is row-wise: across the axes no
    spec of it resolves to, every device holds the same rows and computes
    the same cotangents, and summing those copies (then halving) is an
    all-reduce of an activation that changes nothing. Such a call runs
    with variance tracking on and its replicated operands cast to
    varying over the axes the call IS split over, so JAX's own transpose
    sums a weight's gradient over exactly those axes and nothing over
    the rest. The kernels it reaches give their `pallas_call` out_shapes
    the operand's `vma` (kernels/rms_norm, fused_norm_residual,
    cross_entropy)."""
    mesh, calls = _kernel_meshes[-1] if _kernel_meshes else (None, None)
    if mesh is None or mesh.size == 1:
        return fn
    data = data_axes_for(batch, mesh)
    roles = {"data": data if len(data) > 1 else (data[0] if data else None),
             "mp": ("mp" if "mp" in mesh.axis_names and mesh.shape["mp"] > 1
                    and heads % mesh.shape["mp"] == 0 else None)}

    def resolve(spec):
        return P(*[roles[e] if e is not None else None for e in spec])

    out_tuple = (out_specs,) if isinstance(out_specs, P) else tuple(out_specs)
    ins = tuple(resolve(sp) for sp in in_specs)
    outs = tuple(resolve(sp) for sp in out_tuple)
    row_wise = not any("mp" in _spec_axes(sp)
                       for sp in (*in_specs, *out_tuple))
    body, unsummed = fn, None
    if row_wise:
        named = {a for sp in ins + outs for a in _spec_axes(sp)}
        split = tuple(n for n in mesh.axis_names if n in named)
        unsummed = tuple(n for n in mesh.axis_names if n not in named)
        casts = [tuple(n for n in split if n not in _spec_axes(sp))
                 for sp in ins]

        def body(*args):
            return fn(*[jax.lax.pcast(a, c, to="varying") if c else a
                        for a, c in zip(args, casts)])

    calls.append(unsummed)
    mapped = jax.shard_map(
        body, mesh=mesh, in_specs=ins,
        out_specs=outs[0] if isinstance(out_specs, P) else outs,
        check_vma=row_wise)

    def run(*args):
        with tp_all_reduce():
            return mapped(*args)

    return run


class ShardingPlan:
    """Placement policy consumed by jit.TrainStep: decides the NamedSharding
    of every model/optimizer array before compilation.

    This is the TPU-native form of fleet's sharding stages (SURVEY §2.5):
      stage 1/2 -> optimizer state (+grads) sharded on `sharding` axis
      stage 3   -> parameters sharded too (FSDP)
    plus tensor-parallel PartitionSpecs attached by mpu layers (p.pspec).
    """

    def __init__(self, mesh: Mesh, stage: int = 0, param_rules=None,
                 data_axes=("dp", "sharding"), shard_min_size: int = 2 ** 14,
                 grad_sync=None, grad_sync_block=None,
                 grad_sync_error_feedback: bool = False, zero: int = 0):
        self.mesh = mesh
        self.stage = stage
        self.param_rules = param_rules or {}
        self.pspecs: Dict[str, P] = {}  # model-annotated TP layouts (p.pspec)
        self._requested_data_axes = tuple(data_axes)  # pre-filter (remesh)
        self.data_axes = tuple(a for a in data_axes if a in mesh.axis_names
                               and mesh.shape[a] > 1) or tuple(
                                   a for a in data_axes if a in mesh.axis_names)
        self.shard_min_size = shard_min_size
        # quantized gradient sync (ISSUE 8, EQuARX): "int8"/"fp8" routes
        # the data-parallel grad mean through the blockwise-quantized
        # shard_map chain in collective.py instead of the implicit GSPMD
        # psum; None (default) keeps today's path. Armed only when
        # FLAGS_quant_collectives != 0 (evaluated at TrainStep build —
        # the kill switch restores the GSPMD path bitwise).
        self.grad_sync = grad_sync
        self.grad_sync_block = grad_sync_block
        self.grad_sync_error_feedback = bool(grad_sync_error_feedback)
        # explicit ZeRO sharded weight update (arxiv 2004.13336):
        # zero=1 shards optimizer state across the DP axis (grads still
        # all-reduced), zero=2 additionally reduce-scatters grads so the
        # full reduced gradient never materializes. Composes WITH
        # grad_sync (the quantized chain becomes the rs wire path);
        # armed only when FLAGS_zero != 0 (evaluated at TrainStep build
        # — the kill switch restores the replicated paths bitwise).
        if zero not in (0, 1, 2):
            raise ValueError(f"ShardingPlan(zero={zero!r}): ZeRO mode must "
                             f"be 0 (off), 1, or 2")
        self.zero = int(zero)
        if stage != 0 and (grad_sync is not None or self.zero):
            knobs = " and ".join(
                k for k, on in ((f"grad_sync={grad_sync!r}",
                                 grad_sync is not None),
                                (f"zero={zero}", bool(self.zero))) if on)
            raise ValueError(
                f"ShardingPlan(stage={stage}) GSPMD state/param sharding "
                f"does not compose with {knobs}: the explicit shard_map "
                f"paths (grad_sync= quantized sync, zero= ZeRO sharded "
                f"update) require fully replicated parameters/optimizer "
                f"state (stage=0) — pick ONE sharding story per plan")

    def remesh(self, mesh: Mesh) -> "ShardingPlan":
        """Re-derive this plan over a DIFFERENT (usually smaller) mesh —
        the degraded-world path of coordinated elastic recovery
        (ISSUE 6): when a rank is abandoned and survivors re-form at the
        smaller world size, the same stage/rules/annotations are
        re-applied over the shrunk mesh. Axis names absent from (or
        trivial on) the new mesh fall out of every spec through the
        existing `_valid_axes`/`data_axes` filtering; a re-`materialize`
        (or the next TrainStep compile, which keys its cache on shapes
        and tree structure) then places arrays in the new layout.
        Returns a NEW plan; the original keeps serving the old mesh."""
        plan = ShardingPlan(mesh, stage=self.stage,
                            param_rules=dict(self.param_rules),
                            data_axes=self._requested_data_axes,
                            shard_min_size=self.shard_min_size,
                            grad_sync=self.grad_sync,
                            grad_sync_block=self.grad_sync_block,
                            grad_sync_error_feedback=self
                            .grad_sync_error_feedback,
                            zero=self.zero)
        plan.pspecs = dict(self.pspecs)
        if hasattr(self, "_pid_to_name"):
            plan._pid_to_name = dict(self._pid_to_name)
        return plan

    def attach_model(self, model):
        """Collect per-parameter PartitionSpec annotations (TP layouts set by
        mpu/model layers via p.pspec) and the id->name map used to mirror
        parameter layouts onto their optimizer moments."""
        self._pid_to_name = {}
        for name, p in model.state_dict().items():
            self._pid_to_name[id(p)] = name
            if getattr(p, "pspec", None) is not None:
                self.pspecs[name] = p.pspec
        return self

    # -- spec decisions -----------------------------------------------------
    def _fsdp_axis(self):
        return "sharding" if "sharding" in self.mesh.axis_names else None

    def _valid_axes(self, spec_entry):
        """Drop axis names absent from this mesh (model annotated mp but the
        mesh has no mp axis, etc.)."""
        if spec_entry is None:
            return None
        entries = spec_entry if isinstance(spec_entry, tuple) else (spec_entry,)
        kept = tuple(a for a in entries if a in self.mesh.axis_names
                     and self.mesh.shape[a] > 1)
        if not kept:
            return None
        return kept if len(kept) > 1 else kept[0]

    def param_spec(self, name: str, arr) -> P:
        for pat, spec in self.param_rules.items():
            if pat in name:
                return spec
        annotated = self.pspecs.get(name)
        base = ([self._valid_axes(e) for e in
                 tuple(annotated) + (None,) * (arr.ndim - len(tuple(annotated)))]
                if annotated is not None else [None] * arr.ndim)
        ax = self._fsdp_axis()
        if self.stage >= 3 and ax and self.mesh.shape[ax] > 1 and arr.ndim >= 1:
            # FSDP-shard largest still-unsharded dim (ZeRO-3 partitioning),
            # composed with any TP annotation
            used = {a for e in base if e is not None
                    for a in (e if isinstance(e, tuple) else (e,))}
            if ax not in used:
                order = sorted(range(arr.ndim), key=lambda i: -arr.shape[i])
                for d in order:
                    if base[d] is not None:
                        continue
                    if arr.shape[d] % self.mesh.shape[ax] == 0 and \
                            arr.size >= self.shard_min_size:
                        base[d] = ax
                        break
        return P(*base)

    def opt_spec(self, key, arr, param_specs: Dict[str, P]) -> P:
        """Moments mirror their parameter's layout (id-keyed optimizer state,
        ref DygraphShardingOptimizer partitioning); extra FSDP-sharding of
        moments is what stage>=1 (ZeRO-1/2) means here."""
        if arr.ndim == 0:
            return P()
        # keyed by id(param) on the host, by parameter name where the
        # state crosses a jit boundary (jit.TrainStep._state_keys)
        pid = key[0] if isinstance(key, tuple) else None
        pname = pid if isinstance(pid, str) else getattr(
            self, "_pid_to_name", {}).get(pid)
        if pname is not None and pname in param_specs:
            pspec = param_specs[pname]
            if len(tuple(pspec)) == arr.ndim or self.stage >= 3:
                base = [self._valid_axes(e) for e in
                        tuple(pspec) + (None,) * (arr.ndim - len(tuple(pspec)))]
            else:
                base = [None] * arr.ndim
        else:
            base = [None] * arr.ndim
        ax = self._fsdp_axis()
        if self.stage >= 1 and ax and self.mesh.shape[ax] > 1:
            used = {a for e in base if e is not None
                    for a in (e if isinstance(e, tuple) else (e,))}
            if ax not in used:
                order = sorted(range(arr.ndim), key=lambda i: -arr.shape[i])
                for d in order:
                    if base[d] is not None:
                        continue
                    if arr.shape[d] % self.mesh.shape[ax] == 0 and \
                            arr.size >= self.shard_min_size:
                        base[d] = ax
                        break
        return P(*base)

    def batch_spec(self, arr) -> P:
        if arr.ndim == 0 or not self.data_axes:
            return P()
        return P(self.data_axes if len(self.data_axes) > 1
                 else self.data_axes[0])

    def reshard_batch(self, tree):
        """Reshard COMMITTED jax.Array leaves of a collated batch onto
        this plan's batch shardings — the belt both sharded step paths
        (jit.TrainStep.__call__, Engine._compiled_forward) wear before
        calling an executable compiled with explicit batch in_shardings.

        A DataLoader prefetcher may hand over batches committed to a
        sharding that is not this plan's (the active-plan registration
        is latest-wins: a later unsharded TrainStep clears it, or
        staging started before this plan existed); pjit refuses
        committed args whose sharding differs from in_shardings. A
        matching commit is a no-op; numpy/uncommitted leaves are left
        for jit to place (on a multi-process mesh device_put of local
        data would fail where jit's replicated placement succeeds),
        and a failed reshard falls through to jit for the real error."""
        def leaf(a):
            if isinstance(a, jax.Array):
                sh = NamedSharding(self.mesh, self.batch_spec(a))
                if a.sharding != sh:
                    try:
                        return jax.device_put(a, sh)
                    except Exception:
                        return a
            return a
        return jax.tree_util.tree_map(leaf, tree)

    # -- multi-host entry ----------------------------------------------------
    def materialize(self, model, optimizer=None):
        """Place every model array (and primed optimizer state) as a
        GLOBAL jax.Array in its planned sharding. Required before
        TrainStep on a multi-PROCESS mesh: eagerly created params are
        committed to one local device, and jit cannot implicitly
        reshard a single-device array onto devices other processes own.
        device_put from host numpy (same value on every process, as all
        ranks seed identically) is the documented multi-host path.
        Harmless on single-process meshes (it just places arrays).
        Ref: fleet sharding init broadcast (group_sharded stage init)."""
        from ..tensor import Parameter

        def _already_global(a):
            # a re-materialize (second prepare(), or after training) sees
            # global arrays spanning other processes' devices; np.asarray
            # on those raises — they are already placed, leave them be
            return isinstance(a, jax.Array) and not a.is_fully_addressable

        self.attach_model(model)
        p_specs = {}
        for name, t in model.state_dict().items():
            is_param = isinstance(t, Parameter) and not t.stop_gradient
            if _already_global(t.data):
                if is_param:
                    p_specs[name] = self.param_spec(
                        name, np.empty(t.data.shape))
                continue
            arr = np.asarray(t.data)
            spec = self.param_spec(name, arr) if is_param else P()
            t.data = jax.device_put(arr, NamedSharding(self.mesh, spec))
            if is_param:
                p_specs[name] = spec
        if optimizer is not None:
            if hasattr(optimizer, "prime"):
                optimizer.prime()
            for k, v in list(optimizer._state.items()):
                if _already_global(v):
                    continue
                arr = np.asarray(v)
                optimizer._state[k] = jax.device_put(
                    arr, NamedSharding(self.mesh,
                                       self.opt_spec(k, arr, p_specs)))
            for k, v in list(getattr(optimizer, "_master_weights",
                                     {}).items()):
                if _already_global(v):
                    continue
                arr = np.asarray(v)
                pname = getattr(self, "_pid_to_name", {}).get(k, "")
                spec = (p_specs.get(pname)
                        or self.param_spec(pname, arr))
                optimizer._master_weights[k] = jax.device_put(
                    arr, NamedSharding(self.mesh, spec))
        return self

    # -- TrainStep hook ------------------------------------------------------
    def compile_train_step(self, pure, donate):
        mesh = self.mesh
        untraced = pure

        def pure(*args):
            # the models' Pallas call sites read the mesh while this
            # traces, and wrap themselves in shard_map (shard_kernel)
            with kernel_mesh_guard(mesh):
                return untraced(*args)

        def _master_spec(self, k, v, p_specs):
            pname = k if isinstance(k, str) else getattr(
                self, "_pid_to_name", {}).get(k, "")
            if pname in p_specs and len(tuple(p_specs[pname])) <= v.ndim:
                return p_specs[pname]
            return self.param_spec(pname, v)

        def compiled_factory(params, buffers, opt_state, master,
                             scaler_state, step_i, lr, key, batch):
            p_specs = {k: self.param_spec(k, v) for k, v in params.items()}
            in_shardings = (
                {k: NamedSharding(mesh, p_specs[k]) for k in params},
                {k: NamedSharding(mesh, P()) for k in buffers},
                {k: NamedSharding(mesh, self.opt_spec(k, v, p_specs))
                 for k, v in opt_state.items()},
                {k: NamedSharding(mesh, _master_spec(self, k, v, p_specs))
                 for k, v in master.items()},
                {k: NamedSharding(mesh, P()) for k in scaler_state},
                NamedSharding(mesh, P()),
                NamedSharding(mesh, P()),
                NamedSharding(mesh, P()),
                jax.tree_util.tree_map(
                    lambda a: NamedSharding(mesh, self.batch_spec(a)), batch),
            )
            # optimizer state / master weights are created lazily INSIDE the
            # first step; only then can the output tree be wider than the
            # input tree — shape-infer it abstractly to get out_shardings.
            # In steady state (both populated) skip the extra trace.
            # fast path only when BOTH lazily-created dicts are populated
            # (a restored opt_state with masters still pending would make
            # the output tree wider than the inputs)
            if opt_state and master:
                out_shardings = (NamedSharding(mesh, P()),) + \
                    in_shardings[:5]
            else:
                out_abs = jax.eval_shape(pure, params, buffers, opt_state,
                                         master, scaler_state, step_i, lr,
                                         key, batch)
                _, p_abs, b_abs, os_abs, mw_abs, sc_abs = out_abs
                out_shardings = (
                    NamedSharding(mesh, P()),
                    {k: NamedSharding(mesh, p_specs[k]) for k in p_abs},
                    {k: NamedSharding(mesh, P()) for k in b_abs},
                    {k: NamedSharding(mesh, self.opt_spec(k, v, p_specs))
                     for k, v in os_abs.items()},
                    {k: NamedSharding(mesh, _master_spec(self, k, v, p_specs))
                     for k, v in mw_abs.items()},
                    {k: NamedSharding(mesh, P()) for k in sc_abs},
                )
            return jax.jit(pure, in_shardings=in_shardings,
                           out_shardings=out_shardings,
                           donate_argnums=donate)

        cache = {}

        def jitted(params, buffers, opt_state, master, scaler_state,
                   step_i, lr, key, batch):
            struct = jax.tree_util.tree_structure(
                (params, buffers, opt_state, master, scaler_state, batch))
            shapes = tuple(
                (a.shape, str(a.dtype)) for a in
                jax.tree_util.tree_leaves((params, opt_state, batch)))
            sig = (struct, shapes)
            if sig not in cache:
                cache[sig] = compiled_factory(params, buffers, opt_state,
                                              master, scaler_state, step_i,
                                              lr, key, batch)
            return cache[sig]

        def run(*args):
            # place inputs (no-op if already placed)
            return jitted(*args)(*args)

        run.lower = lambda *args: jitted(*args).lower(*args)
        return run

    # -- quantized grad-sync TrainStep hook (ISSUE 8) -----------------------
    def quant_sync_axis(self):
        """(axis_name, size) of the single data-parallel mesh axis the
        explicit shard_map paths (quantized grad sync, ZeRO update)
        reduce over; raises when the plan has no (or more than one)
        non-trivial data axis — the chain's all_to_all/all_gather
        decomposition is built per axis."""
        axes = [a for a in self.data_axes if self.mesh.shape[a] > 1]
        if len(axes) != 1:
            raise ValueError(
                f"the explicit data-parallel shard_map paths (grad_sync=/"
                f"zero=) need exactly one data-parallel "
                f"mesh axis of size > 1, plan has {axes or 'none'} "
                f"(mesh {dict(self.mesh.shape)})")
        return axes[0], int(self.mesh.shape[axes[0]])

    # -- ZeRO sharded-update TrainStep hooks (arxiv 2004.13336) -------------
    def zero_armed(self) -> bool:
        """True when this plan opted into ZeRO AND the FLAGS_zero kill
        switch is up — the single arming predicate shared by TrainStep's
        build and the checkpoint layout conversion."""
        from ..framework import core as _core
        return bool(self.zero) and _core.get_bool_flag("FLAGS_zero", True)

    def zero_wire_config(self):
        """The CommQuantConfig the ZeRO grad reduce-scatter puts on the
        wire, or None for the exact psum_scatter path. Quantization
        needs BOTH the plan's grad_sync opt-in and the quant kill
        switch up (same arming as the pure grad_sync path)."""
        from ..framework import core as _core
        if self.grad_sync is None or \
                not _core.get_bool_flag("FLAGS_quant_collectives", True):
            return None
        from ..quantization import comm as _qcomm
        return _qcomm.resolve_config(self.grad_sync, self.grad_sync_block,
                                     self.grad_sync_error_feedback)

    def zero_block(self) -> int:
        """Block size of the flat shard layout: the quant block when the
        wire is quantized (payloads, EF residuals, and param/state
        shards must agree on one partitioning), else 1 (minimal
        padding)."""
        cfg = self.zero_wire_config()
        return cfg.block if cfg is not None else 1

    def zero_layout(self, numel: int):
        """(per_rank_shard, padded_total) of a numel-element tensor in
        this plan's flat ZeRO layout — quantization/comm.py's
        shard_sizes contract, padding at the tail."""
        from ..quantization import comm as _qcomm
        _axis, nranks = self.quant_sync_axis()
        return _qcomm.shard_sizes(int(numel), nranks, self.zero_block())

    def compile_quantized_train_step(self, pure_local, donate):
        """Compile the quantized-grad-sync step: `pure_local` is the
        PER-SHARD body (jit.TrainStep builds it — step_fn + backward +
        collective.grad_sync_all_reduce on every grad + update), wrapped
        here in shard_map over the plan's data axis so each shard sees
        its local batch slice and the explicit quantized chain replaces
        the implicit GSPMD psum. Params/optimizer state stay replicated
        (enforced); the error-feedback residual tree rides sharded on
        the sync axis (one per-rank residual slice each)."""
        mesh = self.mesh
        axis, _n = self.quant_sync_axis()
        repl = NamedSharding(mesh, P())

        def _check_replicated(params):
            for name in params:
                spec = self.param_spec(name, params[name])
                if any(e is not None for e in tuple(spec)):
                    raise ValueError(
                        f"quantized grad sync requires fully replicated "
                        f"parameters, but {name!r} has layout {spec} — "
                        f"drop the TP annotation/param_rules or disable "
                        f"grad_sync")

        def compiled_factory(params, buffers, opt_state, master,
                             scaler_state, step_i, lr, key, batch, ef):
            _check_replicated(params)
            batch_specs = jax.tree_util.tree_map(
                lambda a: P(axis) if getattr(a, "ndim", 0) else P(), batch)
            ef_specs = jax.tree_util.tree_map(lambda a: P(axis), ef)
            in_specs = (P(), P(), P(), P(), P(), P(), P(), P(),
                        batch_specs, ef_specs)
            out_specs = (P(), P(), P(), P(), P(), P(), ef_specs)
            fn = jax.shard_map(pure_local, mesh=mesh, in_specs=in_specs,
                               out_specs=out_specs, check_vma=False)
            batch_sh = jax.tree_util.tree_map(
                lambda s: NamedSharding(mesh, s), batch_specs)
            ef_sh = jax.tree_util.tree_map(
                lambda s: NamedSharding(mesh, s), ef_specs)
            in_shardings = (
                {k: repl for k in params}, {k: repl for k in buffers},
                {k: repl for k in opt_state}, {k: repl for k in master},
                {k: repl for k in scaler_state}, repl, repl, repl,
                batch_sh, ef_sh)
            # opt_state/master can widen inside the first step (lazily
            # created slots) — shape-infer the output tree abstractly,
            # same reasoning as compile_train_step
            out_abs = jax.eval_shape(fn, params, buffers, opt_state,
                                     master, scaler_state, step_i, lr,
                                     key, batch, ef)
            _, p_abs, b_abs, os_abs, mw_abs, sc_abs, _ef_abs = out_abs
            out_shardings = (
                repl, {k: repl for k in p_abs}, {k: repl for k in b_abs},
                {k: repl for k in os_abs}, {k: repl for k in mw_abs},
                {k: repl for k in sc_abs}, ef_sh)
            return jax.jit(fn, in_shardings=in_shardings,
                           out_shardings=out_shardings,
                           donate_argnums=donate)

        cache = {}

        def run(params, buffers, opt_state, master, scaler_state, step_i,
                lr, key, batch, ef):
            struct = jax.tree_util.tree_structure(
                (params, buffers, opt_state, master, scaler_state, batch,
                 ef))
            shapes = tuple(
                (a.shape, str(a.dtype)) for a in
                jax.tree_util.tree_leaves((params, opt_state, batch)))
            sig = (struct, shapes)
            if sig not in cache:
                cache[sig] = compiled_factory(params, buffers, opt_state,
                                              master, scaler_state, step_i,
                                              lr, key, batch, ef)
            return cache[sig](params, buffers, opt_state, master,
                              scaler_state, step_i, lr, key, batch, ef)

        return run

    def compile_zero_train_step(self, pure_local, donate):
        """Compile the ZeRO sharded-update step: `pure_local` is the
        PER-SHARD body (jit.TrainStep builds it — step_fn + backward +
        collective.zero_grad_reduce_scatter + per-shard optimizer
        update + collective.zero_param_all_gather), wrapped here in
        shard_map over the plan's data axis. Params stay replicated
        (enforced) but OPTIMIZER STATE rides sharded on the sync axis:
        each state slot is a flat (s*nranks,)-padded vector of which
        every rank materializes only its own (s,)-slice — the HBM win.
        The error-feedback residual tree (quantized wire only) rides
        sharded exactly as in the grad_sync path."""
        mesh = self.mesh
        axis, _n = self.quant_sync_axis()
        repl = NamedSharding(mesh, P())
        shax = NamedSharding(mesh, P(axis))

        def _check_replicated(params):
            for name in params:
                spec = self.param_spec(name, params[name])
                if any(e is not None for e in tuple(spec)):
                    raise ValueError(
                        f"the ZeRO sharded update requires fully "
                        f"replicated parameters, but {name!r} has layout "
                        f"{spec} — drop the TP annotation/param_rules or "
                        f"set zero=0")

        def compiled_factory(params, buffers, opt_state, master,
                             scaler_state, step_i, lr, key, batch, ef):
            _check_replicated(params)
            batch_specs = jax.tree_util.tree_map(
                lambda a: P(axis) if getattr(a, "ndim", 0) else P(), batch)
            ef_specs = jax.tree_util.tree_map(lambda a: P(axis), ef)
            os_specs = {k: P(axis) for k in opt_state}
            in_specs = (P(), P(), os_specs, P(), P(), P(), P(), P(),
                        batch_specs, ef_specs)
            # opt_state widens inside the first step (slots created
            # lazily PER-SHARD — priming would allocate the full-size
            # state the mode exists to avoid), so the out tree is only
            # known abstractly; P(axis) as a spec PREFIX covers every
            # slot the body creates
            out_specs = (P(), P(), P(), P(axis), P(), P(), ef_specs)
            fn = jax.shard_map(pure_local, mesh=mesh, in_specs=in_specs,
                               out_specs=out_specs, check_vma=False)
            batch_sh = jax.tree_util.tree_map(
                lambda s: NamedSharding(mesh, s), batch_specs)
            ef_sh = jax.tree_util.tree_map(
                lambda s: NamedSharding(mesh, s), ef_specs)
            in_shardings = (
                {k: repl for k in params}, {k: repl for k in buffers},
                {k: shax for k in opt_state}, {k: repl for k in master},
                {k: repl for k in scaler_state}, repl, repl, repl,
                batch_sh, ef_sh)
            out_abs = jax.eval_shape(fn, params, buffers, opt_state,
                                     master, scaler_state, step_i, lr,
                                     key, batch, ef)
            _, p_abs, b_abs, os_abs, mw_abs, sc_abs, _ef_abs = out_abs
            out_shardings = (
                repl, {k: repl for k in p_abs}, {k: repl for k in b_abs},
                {k: shax for k in os_abs}, {k: repl for k in mw_abs},
                {k: repl for k in sc_abs}, ef_sh)
            return jax.jit(fn, in_shardings=in_shardings,
                           out_shardings=out_shardings,
                           donate_argnums=donate)

        cache = {}

        def run(params, buffers, opt_state, master, scaler_state, step_i,
                lr, key, batch, ef):
            struct = jax.tree_util.tree_structure(
                (params, buffers, opt_state, master, scaler_state, batch,
                 ef))
            shapes = tuple(
                (a.shape, str(a.dtype)) for a in
                jax.tree_util.tree_leaves((params, opt_state, batch)))
            sig = (struct, shapes)
            if sig not in cache:
                cache[sig] = compiled_factory(params, buffers, opt_state,
                                              master, scaler_state, step_i,
                                              lr, key, batch, ef)
            return cache[sig](params, buffers, opt_state, master,
                              scaler_state, step_i, lr, key, batch, ef)

        return run


def convert_zero_opt_state(saved, optimizer, plan=None):
    """Re-layout a checkpointed optimizer state dict across ZeRO worlds.

    ZeRO state checkpoints as flat (s*nranks,)-padded vectors (padding
    at the TAIL — quantization/comm.py's shard_sizes contract), each
    rank persisting only its own slice through dist_ckpt v2; dist_ckpt's
    tiling verification reassembles them on load. The flat length is
    world-size dependent, so restoring onto a different world (or back
    onto a replicated/FLAGS_zero=0 run) needs this conversion:

      * strip the tail padding of each slot (``ravel()[:numel]`` is
        layout-invariant — replicated param-shaped state passes through
        unchanged),
      * re-pad/re-place for the TARGET: `plan` with an armed zero mode
        re-pads to the new world's layout and shards it on the plan's
        data axis; plan=None (or zero off/disarmed) reshapes back to
        the param's own shape for the replicated update paths.

    `saved` maps optimizer state_dict() keys ("{param_name}.{slot}") to
    Tensors/arrays; returns a same-keyed dict ready for
    optimizer.set_state_dict(). Non-tensor entries ("@step",
    "LR_Scheduler") pass through untouched."""
    from ..tensor import Tensor as _T
    to_zero = plan is not None and plan.zero_armed()
    if to_zero:
        axis, nranks = plan.quant_sync_axis()
        target_sh = NamedSharding(plan.mesh, P(axis))
    prefix_map = {}
    for i, p in enumerate(optimizer._parameter_list):
        prefix_map.setdefault(f"{p.name or i}.", p)
    out = {}
    for k, v in saved.items():
        p = None
        if isinstance(k, str):
            pos = k.find(".")
            while pos != -1 and p is None:
                p = prefix_map.get(k[:pos + 1])
                pos = k.find(".", pos + 1)
        if p is None:
            out[k] = v
            continue
        arr = np.asarray(v.data if isinstance(v, _T) else v)
        numel = int(p.data.size)
        flat = arr.ravel()[:numel]
        if to_zero:
            s, padded = plan.zero_layout(numel)
            out[k] = jax.device_put(
                np.pad(flat, (0, padded - numel)), target_sh)
        else:
            out[k] = jnp.asarray(flat.reshape(p.data.shape))
    return out
