"""The Pallas kernels of the main path, compiled for a DESCRIBED TPU v5e
at the widths the repo ships (llama_350m / llama_1b / llama_7b).

Interpret mode cannot see what the chip's compiler refuses: a block that
does not tile (8, 128), a kernel that wants more VMEM than it is scoped
to, a Mosaic call GSPMD is asked to partition. The TPU compiler is
installed without a chip, so these compile against
`topologies.get_topology_desc("v5e:2x2")` — nothing runs, no device is
touched. A compile that passes here is not a chip run.

Only one process may hold libtpu, so the topology is described inside a
module-scoped fixture (never at import, in a skipif or in a parametrize
argument) and every such test lives in this one file: under xdist only
the worker that is handed the file loads the library.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from paddle_tpu.kernels import block_attention as ba
from paddle_tpu.kernels import cross_entropy as ce
from paddle_tpu.kernels import flash_attention as fa
from paddle_tpu.kernels import fused_norm_residual as fnr
from paddle_tpu.kernels import gated_delta_rule as gdr
from paddle_tpu.kernels import grouped_matmul as gm
from paddle_tpu.kernels import hyper_connection as hc
from paddle_tpu.kernels import paged_attention as pa
from paddle_tpu.kernels import ragged_paged_attention as rpa
from paddle_tpu.kernels import rms_norm as rn
from paddle_tpu.kernels import row_moves as rows
from paddle_tpu.kernels import short_conv as sc
from paddle_tpu.kernels import sparse_select_attention as dsa
from paddle_tpu.kernels import ssd
from paddle_tpu.kernels import swiglu as sg

# (hidden, intermediate) of models/llama.py's shipped configs
WIDTHS = {"350m": (1024, 2816), "1b": (2048, 5504), "7b": (4096, 11008)}
DTYPES = {"bf16": jnp.bfloat16, "f32": jnp.float32}
ROWS = 4 * 2048


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _as_on_the_chip(monkeypatch):
    """Steer every kernel module's backend test to "TPU" (compiled route,
    interpret off), compile at the program's own matmul precision, and
    keep these compiles out of the persistent cache: an entry written
    for a described chip cannot be read back without one."""
    for mod in (ba, ce, dsa, fa, fnr, gdr, gm, hc, pa, rpa, rn, rows, sc, sg,
                ssd):
        monkeypatch.setattr(mod, "_on_tpu", lambda: True)
    from jax.experimental.compilation_cache import compilation_cache as cc
    cache_was = jax.config.jax_enable_compilation_cache
    precision_was = jax.config.jax_default_matmul_precision
    jax.config.update("jax_enable_compilation_cache", False)
    # conftest.py asks for "highest" so that CPU goldens are true f32;
    # the chip runs the program's own default (Mosaic has no fp32-
    # precision matmul of bf16 operands)
    jax.config.update("jax_default_matmul_precision", None)
    cc.reset_cache()
    yield
    jax.config.update("jax_default_matmul_precision", precision_was)
    jax.config.update("jax_enable_compilation_cache", cache_was)
    cc.reset_cache()


def _compile(fn, *args):
    """Compile for the described chip; the text must hold a Mosaic call."""
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "no Pallas kernel in the program"
    return text


def _sum32(tree):
    return sum(jnp.sum(x.astype(jnp.float32))
               for x in jax.tree_util.tree_leaves(tree))


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("width", WIDTHS)
def test_swiglu_fwd_and_grad(one_chip, width, dtype):
    H, M = WIDTHS[width]
    a = jax.ShapeDtypeStruct((ROWS, H), DTYPES[dtype], sharding=one_chip)
    w = jax.ShapeDtypeStruct((H, 2 * M), DTYPES[dtype], sharding=one_chip)
    _compile(sg.swiglu, a, w)
    text = _compile(jax.grad(lambda a_, w_: _sum32(sg.swiglu(a_, w_)),
                             argnums=(0, 1)), a, w)
    assert "swiglu_bwd_da" in text and "swiglu_bwd_dw" in text


# intermediate size a device of the benchmark's training cells (hidden
# 4096, 4096 rows a device): Yi-6B whole, and split over mp=2
CELL_MLPS = {"yi-6b-1chip": 11008, "yi-6b-4chip": 5504}


def _mosaic_calls(text, kernel):
    return sum('custom_call_target="tpu_custom_call"' in line
               and kernel in line for line in text.splitlines())


@pytest.mark.parametrize("cell", CELL_MLPS)
def test_swiglu_backward_at_the_cells_widths(one_chip, cell):
    """One layer's swiglu backward as the cells run it: g and u are
    recomputed in swiglu_bwd_da alone, which hands the [T, 2M] gate | up
    cotangent to the matmul kernel swiglu_bwd_dw; the weight gradient
    leaves that kernel in one piece; each kernel's blocks fit what it
    is scoped to (the compiler refuses a kernel that does not)."""
    T, H, M = 4096, 4096, CELL_MLPS[cell]
    a = jax.ShapeDtypeStruct((T, H), jnp.bfloat16, sharding=one_chip)
    w = jax.ShapeDtypeStruct((H, 2 * M), jnp.bfloat16, sharding=one_chip)
    compiled = jax.jit(jax.grad(
        lambda a_, w_: _sum32(sg.swiglu(a_, w_)), argnums=(0, 1))).lower(
            a, w).compile()
    text = compiled.as_text()
    assert _mosaic_calls(text, "swiglu_bwd_da") == 1
    assert _mosaic_calls(text, "swiglu_bwd_dw") == 1
    assert _mosaic_calls(text, "swiglu_fwd") == 0     # no third recompute
    assert "concatenate" not in text
    # what the backward adds to the device's memory is the cotangent
    # buffer between the two kernels, and nothing of its size beside it
    dgu_bytes = T * 2 * M * 2
    assert dgu_bytes <= compiled.memory_analysis().temp_size_in_bytes \
        < 1.1 * dgu_bytes
    for kernel in ("da", "dw"):
        bt, bc = sg._blocks(kernel, T, H, M, 2)
        assert (sg._vmem_bytes(kernel, bt, bc, H, 2)
                <= sg._VMEM_BLOCK_BUDGET < sg._VMEM_LIMIT)


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("width", WIDTHS)
def test_fused_add_rms_norm_fwd_and_grad(one_chip, width, dtype):
    H, _ = WIDTHS[width]
    x = jax.ShapeDtypeStruct((4, 2048, H), DTYPES[dtype], sharding=one_chip)
    w = jax.ShapeDtypeStruct((H,), jnp.float32, sharding=one_chip)
    _compile(fnr.fused_add_rms_norm, x, x, w)
    _compile(jax.grad(
        lambda x_, r_, w_: _sum32(fnr.fused_add_rms_norm(x_, r_, w_)),
        argnums=(0, 1, 2)), x, x, w)


@pytest.mark.parametrize("rows", [1, 20, 300])
def test_serving_row_counts_tile(one_chip, rows):
    # decode steps and prefill chunks hand the row-blocked kernels row
    # counts that are no multiple of 8
    H, M = WIDTHS["7b"]
    x = jax.ShapeDtypeStruct((rows, H), jnp.bfloat16, sharding=one_chip)
    w = jax.ShapeDtypeStruct((H, 2 * M), jnp.bfloat16, sharding=one_chip)
    g = jax.ShapeDtypeStruct((H,), jnp.float32, sharding=one_chip)
    _compile(sg.swiglu, x, w)
    _compile(rn.rms_norm, x, g)
    _compile(fnr.fused_add_rms_norm, x, x, g)


@pytest.mark.parametrize("kv_heads", [32, 8], ids=["mha", "gqa"])
def test_flash_attention_fwd_and_grad(one_chip, kv_heads):
    q = jax.ShapeDtypeStruct((1, 2048, 32, 128), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, 2048, kv_heads, 128), jnp.bfloat16,
                              sharding=one_chip)
    assert fa.supported(q.shape, kv.shape, True)

    def f(q_, k_, v_):
        return fa.flash_attention_bshd(q_, k_, v_, causal=True)

    _compile(f, q, kv, kv)
    _compile(jax.grad(lambda *a: _sum32(f(*a)), argnums=(0, 1, 2)),
             q, kv, kv)


# (q heads, kv heads, S, key width, value width) of ONE splash call of the
# cells' steps, and the backward `fa.splash_backward` routes it to
CELL_SPLASH_CALLS = {
    "glm": ((5, 5, 16384, 256, 256), ("one_kernel", 1024)),
    "solar": ((8, 1, 32768, 128, 128), ("one_kernel", 4096)),
    "xing": ((8, 8, 4096, 256, 128), ("one_kernel", 1024)),
    "yi-1chip": ((32, 4, 4096, 128, 128), ("one_kernel", 1024)),
    "yi-4chip-a-device": ((16, 2, 4096, 128, 128), ("one_kernel", 1024)),
    "granite": ((32, 8, 32768, 64, 64), ("one_kernel", 2048)),
}


@pytest.mark.parametrize("cell", CELL_SPLASH_CALLS)
def test_splash_backward_at_the_cells_call_shapes(one_chip, cell):
    """The routed backward of one call as each cell's step makes it
    (ISSUE 50): the outer kv block `_one_kernel_vmem_bytes` lets through
    is one Mosaic's scoped VMEM takes (the compiler refuses a kernel that
    asks for more: GLM's 256-wide keys and values fit 1024 and not 2048,
    Solar's one kv head fits 4096) and the one kernel is alone in the
    backward."""
    (Hq, Hk, S, D, Dv), (form, outer) = CELL_SPLASH_CALLS[cell]
    q = jax.ShapeDtypeStruct((1, S, Hq, D), jnp.bfloat16, sharding=one_chip)
    k = jax.ShapeDtypeStruct((1, S, Hk, D), jnp.bfloat16, sharding=one_chip)
    v = jax.ShapeDtypeStruct((1, S, Hk, Dv), jnp.bfloat16, sharding=one_chip)
    chosen = fa.splash_backward((1, Hq, S, D), Hk, S, Dv, jnp.bfloat16, True,
                                None)
    assert (chosen.form, chosen.block_kv_dkv) == (form, outer)
    text = _compile(jax.grad(lambda q_, k_, v_: _sum32(
        fa.flash_attention_bshd(q_, k_, v_, causal=True)),
        argnums=(0, 1, 2)), q, k, v)
    assert _mosaic_calls(text, "splash_mqa_dkv") == 1
    assert _mosaic_calls(text, "splash_mqa_dq") == (
        0 if form == "one_kernel" else 1)


def test_fused_cross_entropy_fwd_and_grad(one_chip):
    x = jax.ShapeDtypeStruct((2048, 32000), jnp.bfloat16, sharding=one_chip)
    y = jax.ShapeDtypeStruct((2048,), jnp.int32, sharding=one_chip)
    _compile(ce.fused_cross_entropy, x, y)
    _compile(jax.grad(lambda x_, y_: jnp.sum(
        ce.fused_cross_entropy(x_, y_))), x, y)


def _pool(one_chip, kvh=32, n_pages=129, page=16, d=128):
    return jax.ShapeDtypeStruct((kvh, n_pages, page, d), jnp.bfloat16,
                                sharding=one_chip)


def test_paged_decode_attention(one_chip):
    q = jax.ShapeDtypeStruct((4, 32, 128), jnp.bfloat16, sharding=one_chip)
    lens = jax.ShapeDtypeStruct((4,), jnp.int32, sharding=one_chip)
    table = jax.ShapeDtypeStruct((4, 16), jnp.int32, sharding=one_chip)
    pool = _pool(one_chip)
    assert pa.supported(q.shape, pool.shape)
    _compile(pa.paged_decode_attention, q, pool, pool, lens, table)


def test_ragged_paged_attention(one_chip):
    q = jax.ShapeDtypeStruct((64, 32, 128), jnp.bfloat16, sharding=one_chip)
    seq = jax.ShapeDtypeStruct((4,), jnp.int32, sharding=one_chip)
    table = jax.ShapeDtypeStruct((4, 16), jnp.int32, sharding=one_chip)
    pool = _pool(one_chip)
    text = _compile(rpa.ragged_paged_attention, q, pool, pool, seq, seq,
                    seq, table)
    assert "ragged_paged_attention" in text


def test_block_attention_stats(one_chip):
    q = jax.ShapeDtypeStruct((1, 512, 8, 128), jnp.bfloat16,
                             sharding=one_chip)
    bias = jax.ShapeDtypeStruct((1, 8, 512, 512), jnp.float32,
                                sharding=one_chip)
    _compile(lambda q_, k_, v_, b_: ba.block_attention_stats(
        q_, k_, v_, None, 0.125, b_, True), q, q, q, bias)


def test_rms_norm(one_chip):
    x = jax.ShapeDtypeStruct((4, 2048, 4096), jnp.bfloat16,
                             sharding=one_chip)
    w = jax.ShapeDtypeStruct((4096,), jnp.float32, sharding=one_chip)
    _compile(rn.rms_norm, x, w)
    # the backward is analytic jnp and needs no forward output: the
    # gradient program holds no kernel, it only has to compile
    jax.jit(jax.grad(lambda x_, w_: _sum32(rn.rms_norm(x_, w_)),
                     argnums=(0, 1))).lower(x, w).compile()


def test_sharded_operands_compile_through_shard_kernel(topo):
    """Batch- and head-sharded operands on a 2x2 mesh: plain jit refuses
    to partition a Mosaic call; the models' call sites wrap it in
    shard_map over the armed mesh (distributed/sharding.shard_kernel)."""
    from paddle_tpu.distributed.sharding import (kernel_mesh_guard,
                                                 shard_kernel)
    from paddle_tpu.models.llama import _swiglu
    mesh = Mesh(np.asarray(topo.devices).reshape(2, 2), ("sharding", "mp"))

    def arg(shape, dtype, spec):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))

    H, M = WIDTHS["7b"]
    q = arg((2, 2048, 32, 128), jnp.bfloat16,
            P("sharding", None, "mp", None))
    x = arg((2, 2048, H), jnp.bfloat16, P("sharding", None, None))
    g = arg((H,), jnp.float32, P(None))
    w = arg((H, 2 * M), jnp.bfloat16, P("sharding", "mp"))
    bshd, bsh = P("data", None, "mp", None), P("data", None, None)

    def attend(q_, k_, v_):
        return fa.flash_attention_bshd(q_, k_, v_, causal=True)

    with pytest.raises(NotImplementedError, match="shard_map"):
        jax.jit(attend).lower(q, q, q).compile()

    def step(q_, x_, g_, w_):
        with kernel_mesh_guard(mesh):
            o = shard_kernel(attend, (bshd,) * 3, bshd, batch=2,
                             heads=32)(q_, q_, q_)
            y, h = shard_kernel(fnr.fused_add_rms_norm, (bsh, bsh, P(None)),
                                (bsh, bsh), batch=2)(x_, x_, g_)
            return _sum32((o, h, _swiglu(y, w_)))

    text = _compile(jax.grad(step, argnums=(0, 1, 2, 3)), q, x, g, w)
    for kernel in ("flash_attention", "fused_add_rms_norm", "swiglu_bwd_da",
                   "swiglu_bwd_dw"):
        assert kernel in text, kernel


def _all_reduces(text, shape):
    """The all-reduce instructions of `text` with a result of `shape`
    (XLA may have combined it with others into one tuple-shaped
    instruction): (from shard_map's transpose, the instruction's line)."""
    found = []
    for line in text.splitlines():
        m = re.search(r"= (.*?) all-reduce(-start)?\(", line)
        if m and shape in m.group(1):
            found.append(("/shard_map/" in line, line.strip()))
    return found


@pytest.mark.parametrize("call", ["rms_norm", "fused_add_rms_norm",
                                  "fused_cross_entropy", "swiglu"])
def test_shard_kernel_sums_a_cotangent_only_where_its_call_is_split(
        topo, call):
    """The backward of each `shard_kernel` call form on sharding 2 x mp 2,
    in the chip compiler's text. A row-wise call (no "mp" in its specs)
    leaves the activation's cotangent as it is: over mp every device holds
    the same rows, and shard_map's transpose without variance tracking
    summed those copies, one all-reduce of a [1, 4096, 4096] activation a
    call. What stays is the column-parallel matmul's own (the
    partitioner's) and dw over the data axis. swiglu keeps its sum of da
    over mp: there the copies differ."""
    from paddle_tpu.distributed.sharding import (kernel_mesh_guard,
                                                 shard_kernel)
    from paddle_tpu.models.llama import _swiglu
    mesh = Mesh(np.asarray(topo.devices).reshape(2, 2), ("sharding", "mp"))

    def arg(shape, dtype, spec):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))

    H, M = WIDTHS["7b"]
    x = arg((2, 4096, H), jnp.bfloat16, P("sharding", None, None))
    g = arg((H,), jnp.float32, P(None))
    wc = arg((H, 2048), jnp.bfloat16, P("sharding", "mp"))   # column-parallel
    bsh = P("data", None, None)

    def norm(x_, g_, wc_):
        y = shard_kernel(lambda a, w: rn.rms_norm(a, w, 1e-6),
                         (bsh, P(None)), bsh, batch=2)(x_, g_)
        return _sum32(y @ wc_)

    def add_norm(x_, g_, wc_):
        y, h = shard_kernel(
            lambda r, d, w: fnr.fused_add_rms_norm(r, d, w, 1e-6),
            (bsh, bsh, P(None)), (bsh, bsh), batch=2)(x_, 2 * x_, g_)
        return _sum32(y @ wc_) + _sum32(h)

    def loss(logits, labels):
        return _sum32(shard_kernel(
            lambda l, y: ce.fused_cross_entropy(l, y, -100),
            (P("data", None), P("data")), P("data"),
            batch=logits.shape[0])(logits, labels))

    def mlp(x_, wgu):
        return _sum32(_swiglu(x_, wgu))

    fn, args, activation = {
        "rms_norm": (norm, (x, g, wc), "bf16[1,4096,4096]"),
        "fused_add_rms_norm": (add_norm, (x, g, wc), "bf16[1,4096,4096]"),
        "fused_cross_entropy": (
            loss, (arg((8192, 64000), jnp.bfloat16, P("sharding", None)),
                   arg((8192,), jnp.int32, P("sharding"))),
            "bf16[4096,64000]"),
        "swiglu": (mlp, (x, arg((H, 2 * M), jnp.bfloat16,
                                P("sharding", "mp"))), "bf16[1,4096,4096]"),
    }[call]

    def armed(*a):
        with kernel_mesh_guard(mesh):
            return fn(*a)

    argnums = (0,) if call == "fused_cross_entropy" else tuple(
        range(len(args)))
    text = _compile(jax.grad(armed, argnums=argnums), *args)
    found = _all_reduces(text, activation)
    mapped = [line for from_map, line in found if from_map]
    if call == "swiglu":
        (da,) = mapped                  # over mp: device pairs (0,1), (2,3)
        assert "replica_groups={{0,1},{2,3}}" in da, da
        assert len(found) == 1
    else:
        assert not mapped, mapped
        # the partitioner's, for the matmul's input gradient
        assert len(found) == (0 if call == "fused_cross_entropy" else 1)
    if call in ("rms_norm", "fused_add_rms_norm"):
        # dw: summed over the data axis (device pairs (0,2), (1,3)) alone
        (dw,) = [line for from_map, line in _all_reduces(text, "f32[4096]")
                 if from_map]
        assert "replica_groups={{0,2},{1,3}}" in dw, dw


@pytest.mark.parametrize("cell", ["yi-6b-1chip.pretrain",
                                  "yi-6b-4chip.pretrain"])
def test_train_step_names_its_device_operations(topo, cell):
    """The whole `TrainStep` of a benchmark cell at depth 2 (as
    `chipbench/rehearse_compile.py` builds it: abstract weights placed
    as the plan places them), for one described chip and under ZeRO-3 x
    TP for four: of what a device executes (matmuls, fusions, custom
    calls, collectives; not what sits inside a fusion) nearly everything
    that carries an op_name resolves, through `chipbench/components.json`,
    to a component the program named (`observability/scopes.py`), and
    backward and recomputed work is told apart."""
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, root)
    import paddle_tpu as paddle
    import paddle_tpu.optimizer as popt
    from chipbench import harness, scope_reduce, weights
    from chipbench import run as bench_run
    from chipbench.drivers import train

    _, _, cell_json, config, traffic = bench_run.load_cell(root, cell)
    config = dict(config, num_hidden_layers=2)
    devices = list(topo.devices[:config["chips"]])
    plan = train._plan(config, devices)
    model, shapes = weights.skeleton(weights.model_config(config))
    one = SingleDeviceSharding(devices[0]) if plan is None else None
    shard = ({k: one for k in shapes} if plan is None
             else harness.plan_shardings(plan, model, shapes))
    abstract = {k: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=shard[k])
                for k, s in shapes.items()}
    weights.install(model, abstract)
    opt = popt.AdamW(learning_rate=1e-3, parameters=model.parameters())
    for name, t in model.state_dict().items():   # what prime() would make
        for slot in ("moment1", "moment2"):
            opt._state[(id(t), slot)] = abstract[name]
    step = paddle.jit.TrainStep(model, opt, lambda i, l: model.loss(i, l),
                                shard=plan)
    step._build()
    x = paddle.to_tensor(np.zeros((1, 8), np.int32))
    x.data = jax.ShapeDtypeStruct(
        (cell_json["batch_size"], traffic["seq_len"]), np.int32, sharding=one)
    args = step._call_args((x, x))
    if one is not None:                      # host scalars -> the device
        args = tuple(jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype,
                                          sharding=one)
                     if isinstance(a, (np.ndarray, np.generic)) else a
                     for a in args)
    # through `TrainStep.lower()`'s wrapper (`lower()` itself wants real
    # arrays): its compile leaves the compiler's byte count a device
    from paddle_tpu.observability import spans
    spans.clear()
    compiled = paddle.jit._LoweredStep(step._compiled.lower(*args),
                                       step._exec_tag, devices).compile()
    text = compiled.as_text()
    (event,) = [ev for ev in spans.ring()
                if ev["name"] == "train_step.memory"]
    mem = {k: int(v) for k, v in event["attrs"].items() if v.isdigit()}
    assert mem["devices"] == len(devices) and "bytes_limit" not in mem
    assert mem["temp_bytes"] == \
        compiled.memory_analysis().temp_size_in_bytes > 0
    assert mem["sum_bytes"] == (
        mem["argument_bytes"] + mem["output_bytes"] - mem["alias_bytes"]
        + mem["temp_bytes"] + mem["generated_code_bytes"])
    # the TPU compiler's own peak: the arguments and the program's
    # fullest moment, under the sum (`temp_bytes` is a region's size)
    assert mem["peak_bytes"] == \
        compiled.memory_analysis().peak_memory_in_bytes
    assert mem["argument_bytes"] < mem["peak_bytes"] < mem["sum_bytes"]
    # the donated state comes back in its own buffers
    assert 0.99 * mem["argument_bytes"] < mem["alias_bytes"] <= \
        mem["argument_bytes"]
    if plan is None:
        # the scanned layer forward, recomputed and backward: splash
        # forward ONCE (the default remat policy keeps its out and
        # logsumexp for the backward: tests/test_kept_residuals.py), ONE
        # backward kernel (dq, dk and dv from the dkv kernel since
        # ISSUE 50: tests/test_splash_backward.py); swiglu forward, da,
        # dw; fused add+norm x 2; rms_norm x 3 (the last norm among
        # them). A change of route shows here before it shows on the chip
        assert _mosaic_calls(text, "") == 10, harness.kernels_in(text)
        assert _mosaic_calls(text, "splash_mqa_fwd") == 1
        assert _mosaic_calls(text, "splash_mqa_dkv") == 1
        assert _mosaic_calls(text, "splash_mqa_dq") == 0
    assert "tpu_custom_call" in text
    _, total, counts, missed = scope_reduce.text_coverage(text)
    named_missed = [m for m in missed if m[2]]
    carrying = total - (len(missed) - len(named_missed))
    assert carrying >= 100 and len(named_missed) <= 0.05 * carrying, (
        named_missed[:10])
    for component in ("attn/qkv", "attn/core", "attn/out", "mlp", "head",
                      "loss", "norm"):
        assert counts[(component, "forward")] > 0, component
        assert counts[(component, "backward")] > 0, component
    assert counts[("attn/core", "recomputed")] > 0
    assert counts[("optimizer", "update")] > 0
    assert counts[("layers", "backward")] > 0
    if plan is not None:
        for cls in ("tp_all_reduce", "tp_relayout", "zero3"):
            assert sum(n for (c, _), n in counts.items() if c == cls), cls
        # of shard_map's own sums under tp/all_reduce, the scanned layer
        # holds ONE of an activation: swiglu's da over mp. The norms' (two
        # more a layer, and the last norm's after the scan) were sums of
        # equal copies and are gone
        mapped = [line for from_map, line in _all_reduces(
            text, "bf16[1,%d,%d]" % (traffic["seq_len"],
                                     config["hidden_size"])) if from_map]
        assert len(mapped) == 1 and "/mlp/" in mapped[0] \
            and "tp/all_reduce" in mapped[0], mapped


# -- models/solar_open2.py at the shapes of solar-open2-250b-ep40.pretrain-32k

def test_solar_open2_kernels_at_the_cells_shapes(one_chip):
    """What one group of heads and one expert layer hand the compiler at
    32768 tokens and the published widths: the routed experts' grouped
    products (megablox gmm forward, gmm + tgmm backward) over the 16384-row
    buffer, the shared expert's swiglu at M = 1280, and splash attention
    over one KV head with its 8 query heads."""
    bf = jnp.bfloat16

    def sds(shape, dtype=bf):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    x, w = sds((16384, 4096)), sds((8, 4096, 2560))
    sizes = sds((8,), jnp.int32)
    assert gm.supported(16384, 4096, 2560)
    text = _compile(jax.grad(
        lambda x_, w_, s_: _sum32(gm.grouped_matmul(x_, w_, s_)),
        argnums=(0, 1)), x, w, sizes)
    assert _mosaic_calls(text, "gmm") >= 2 and _mosaic_calls(text, "tgmm") == 1
    a, wgu = sds((32768, 4096)), sds((4096, 2 * 1280))
    text = _compile(jax.grad(lambda a_, w_: _sum32(sg.swiglu(a_, w_)),
                             argnums=(0, 1)), a, wgu)
    assert "swiglu_bwd_da" in text and "swiglu_bwd_dw" in text
    q, kv = sds((1, 32768, 8, 128)), sds((1, 32768, 1, 128))
    assert fa.supported(q.shape, kv.shape, True)
    _compile(jax.grad(lambda q_, k_, v_: _sum32(fa.flash_attention_bshd(
        q_, k_, v_, causal=True)), argnums=(0, 1, 2)), q, kv, kv)


def test_gated_delta_rule_compiles_for_the_chip(one_chip):
    """The chunked operator at the cell's own length and group of heads:
    each pass is ONE Mosaic kernel that takes bf16 q, k, v and the float32
    decay as they come and does the pairwise sub-blocks, the solve and the
    walk over the chunks in VMEM. Nothing of it is left for XLA: no
    triangular solve, no loop, no concatenate, no float32 copy of an
    operand; under a `jax.checkpoint`, as the model calls it, the forward
    runs twice (once keeping what the backward reads)."""
    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    qkv = sds((1, 32768, 4, 128))
    g, beta = sds((1, 32768, 4, 128), jnp.float32), sds((1, 32768, 4),
                                                        jnp.float32)
    compiled = jax.jit(jax.grad(
        lambda *a: _sum32(gdr.chunk_gated_delta_rule(*a)),
        argnums=(0, 1, 2, 3, 4))).lower(qkv, qkv, qkv, g, beta).compile()
    text = compiled.as_text()
    assert "triangular-solve" not in text and "triangular_solve" not in text
    assert " while(" not in text and "concatenate" not in text
    assert _mosaic_calls(text, "kda_chunk_states_bwd") == 1
    assert _mosaic_calls(text, "kda_chunk_states") == 2    # and its _bwd
    # what the backward reads, a token a head: 1/64 of a chunk's starting
    # state, a row of A, P and the solve's inverse (64 wide, stored 128
    # wide), of U~ and W; beside it o, its cotangent and beta by columns,
    # and no [N, B, H, C, d] float32 copy of q, k, v, g
    kept = 32768 * 4 * (128 * 128 // 64 * 4 + 128 * (4 + 4 + 2)
                        + 128 * (4 + 2))
    assert compiled.memory_analysis().temp_size_in_bytes < 1.35 * kept

    remat = jax.jit(jax.grad(lambda *a: _sum32(jax.checkpoint(
        gdr.chunk_gated_delta_rule)(*a)), argnums=(0, 1, 2, 3, 4))).lower(
            qkv, qkv, qkv, g, beta).compile().as_text()
    assert _mosaic_calls(remat, "kda_chunk_states_bwd") == 1
    assert _mosaic_calls(remat, "kda_chunk_states") == 2


def test_kda_half_layer_compiles_for_the_chip(one_chip):
    """One delta-rule half layer as the Solar cell runs it ([1, 32768,
    4096], 64 heads x 128 in 16 groups, rank 128), forward and its own
    backward: two loops over the groups; the delta-rule forward kernel and
    the convolution's twice (forward, recomputed: the forward `jax.vjp`
    traces in the backward and never reads is gone), their backward once;
    the input norm once a pass and not once a group; and the program's
    temporaries within 0.8 GiB of what the per-group form took (ISSUE 37:
    2,952,275,456 bytes at commit 2593084, this compile there)."""
    from paddle_tpu.models.solar_open2 import KDAttention, SolarOpen2Config
    cfg = SolarOpen2Config(num_hidden_layers=1, gqa_layers=(), vocab_size=128)
    layer = KDAttention(cfg)

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = [sds((1, 32768, cfg.hidden_size)),
            sds((cfg.hidden_size,), jnp.float32)] + [
        sds(p.data.shape, p.data.dtype) for p in layer.weights()]

    def loss(x, *ws):
        y = layer.block(x, *ws).astype(jnp.float32)
        return jnp.sum(y * y)           # the backward reads the forward's y

    compiled = jax.jit(jax.grad(
        loss, argnums=tuple(range(len(args))))).lower(*args).compile()
    text = compiled.as_text()
    assert text.count(" while(") == 2
    assert _mosaic_calls(text, "kda_chunk_states_bwd") == 1
    assert _mosaic_calls(text, "kda_chunk_states") == 3     # and its _bwd
    assert _mosaic_calls(text, "kda_conv_fwd") == 2
    assert _mosaic_calls(text, "kda_conv_bwd") == 1
    assert _mosaic_calls(text, "rms_norm") == 2
    assert compiled.memory_analysis().temp_size_in_bytes < (
        2_952_275_456 + 0.8 * 2 ** 30)


def test_short_conv_compiles_for_the_chip(one_chip):
    """The delta-rule layer's convolution + SiLU + L2 norm at the cell's
    own shape (a group of 4 heads x 128, q | k | v side by side), as the
    model calls it, under a `jax.checkpoint` and in front of something
    that reads q, k, v again in its backward (here their squares): the
    forward kernel runs twice and the backward once, XLA is left no
    convolution and no padded copy, and nothing the size of a float32
    [T, C] exists: the temporaries are q, k, v and their cotangents in
    bf16 and dw's partial sums."""
    T, C, taps = 32768, 1536, 4
    pre = jax.ShapeDtypeStruct((1, T, C), jnp.bfloat16, sharding=one_chip)
    w = jax.ShapeDtypeStruct((taps, C), jnp.bfloat16, sharding=one_chip)
    compiled = jax.jit(jax.value_and_grad(
        lambda p, w_: _sum32(jax.checkpoint(lambda *a: [
            x * x for x in sc.conv_silu_l2norm(*a, 4)])(p, w_)),
        argnums=(0, 1))).lower(pre, w).compile()
    text = compiled.as_text()
    assert _mosaic_calls(text, "kda_conv_fwd") == 2
    assert _mosaic_calls(text, "kda_conv_bwd") == 1
    assert "convolution" not in text and " pad(" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < (
        2 * T * C * 2 + taps * 8 * C * 4) * 1.05 < T * C * 4 * 1.1



# -- models/granite_hybrid.py at granite-4.0-h-micro-pp4.pretrain-32k's shapes

def test_ssd_compiles_for_the_chip(one_chip):
    """The chunked state-space operator at the cell's own shape (64 heads
    of 64, a state of 128, chunks of 256, 32768 tokens), as the model
    calls it, under a `jax.checkpoint`: each pass is ONE Mosaic kernel
    (the backward makes the forward again, keeping each chunk's starting
    state; the first forward's output is not read here, so it is gone),
    nothing of it is left for XLA as a loop, and nothing the size of L
    (64 x 128 x 256 x 256 float32: 2.1 GB) exists."""
    T, H, P, N, Q = 32768, 64, 64, 128, 256

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = (sds((1, T, H, P)), sds((1, T, H), jnp.float32),
            sds((1, T, H), jnp.float32), sds((1, T, N)), sds((1, T, N)),
            sds((H,), jnp.float32))
    assert ssd._tiles_ok(H, P, N, Q)
    compiled = jax.jit(jax.grad(lambda *a: _sum32(jax.checkpoint(
        lambda *b: ssd.ssd_chunk_scan(*b, chunk=Q))(*a)),
        argnums=tuple(range(6)))).lower(*args).compile()
    text = compiled.as_text()
    assert " while(" not in text
    assert _mosaic_calls(text, "ssd_chunk_scan_bwd") == 1
    assert _mosaic_calls(text, "ssd_chunk_scan") == 2      # and its _bwd
    states = T // Q * N * H * P * 4
    assert compiled.memory_analysis().temp_size_in_bytes < (
        states + 6 * T * H * P * 2) < H * (T // Q) * Q * Q * 4


def test_ssm_conv_compiles_for_the_chip(one_chip):
    """The state-space layer's convolution + bias + SiLU over the 4352
    x | B | C channels at 32768 tokens, x, B and C handed out as three
    outputs, under a `jax.checkpoint`: the forward kernel twice, the
    backward once, no convolution and no padded copy left for XLA."""
    T, C = 32768, 4352
    pre = jax.ShapeDtypeStruct((1, T, C), jnp.bfloat16, sharding=one_chip)
    w = jax.ShapeDtypeStruct((4, C), jnp.bfloat16, sharding=one_chip)
    b = jax.ShapeDtypeStruct((C,), jnp.bfloat16, sharding=one_chip)
    text = _compile(jax.value_and_grad(
        lambda *a: _sum32(jax.checkpoint(lambda *c: [
            x * x for x in sc.conv_bias_silu(*c, (4096, 128, 128))])(*a)),
        argnums=(0, 1, 2)), pre, w, b)
    assert _mosaic_calls(text, "ssm_conv_fwd") == 2
    assert _mosaic_calls(text, "ssm_conv_bwd") == 1
    assert "convolution" not in text and " pad(" not in text


def test_gated_short_conv_compiles_for_the_chip(one_chip):
    """models/lfm2_moe.py at lfm2-8b-a1b-ep4.pretrain-4k-batch's shape: the
    gated short convolution (B | C | X of 2048 columns each, three taps,
    no bias, no activation) over four sequences of 4096 tokens, under a
    `jax.checkpoint` as the conv half has it and in front of something
    that reads y again in its backward: the forward kernel twice, the
    backward once, no convolution, no padded copy and no float32
    [T, 2048] product left for XLA."""
    B, T, C = 4, 4096, 2048
    bcx = jax.ShapeDtypeStruct((B, T, 3 * C), jnp.bfloat16,
                               sharding=one_chip)
    w = jax.ShapeDtypeStruct((3, C), jnp.bfloat16, sharding=one_chip)
    compiled = jax.jit(jax.value_and_grad(
        lambda a, w_: _sum32(jax.checkpoint(lambda *c: jnp.square(
            sc.gate_conv_gate(*c)))(a, w_)), argnums=(0, 1))).lower(
                bcx, w).compile()
    text = compiled.as_text()
    assert _mosaic_calls(text, "gate_conv_fwd") == 2
    assert _mosaic_calls(text, "gate_conv_bwd") == 1
    assert "convolution" not in text and " pad(" not in text
    # y and its cotangent in bf16, dw's partial sums: nothing float32 the
    # size of B * X
    assert compiled.memory_analysis().temp_size_in_bytes < (
        2 * B * T * C * 2 + B * 3 * 8 * C * 4) * 1.05 < B * T * C * 4 * 1.1


def test_granite_attention_and_tied_head_at_the_cells_shapes(one_chip):
    """Splash attention at head width 64 (32 query heads over 8 KV heads,
    32768 tokens, scores times 1/64), never run at a benchmark shape
    before this cell; and a block of the tied head + loss: 1024 rows
    against the 100352-row table as it is stored, no transposed copy of
    it."""
    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    q, kv = sds((1, 32768, 32, 64)), sds((1, 32768, 8, 64))
    assert fa.supported(q.shape, kv.shape, True)
    text = _compile(jax.grad(lambda q_, k_, v_: _sum32(
        fa.flash_attention_bshd(q_, k_, v_, causal=True, scale=0.015625)),
        argnums=(0, 1, 2)), q, kv, kv)
    assert "splash_mqa" in text
    from paddle_tpu.nn.functional.loss import _linear_cross_entropy
    h, table = sds((4096, 2048)), sds((100352, 2048))
    labels = sds((4096,), jnp.int32)
    compiled = jax.jit(jax.grad(
        lambda h_, t_, l_: _linear_cross_entropy(
            h_, t_, l_, 1024, -100, tied=True, logit_scale=0.125),
        argnums=(0, 1))).lower(h, table, labels).compile()
    # float32 logits of a block and their cotangent, the table's float32
    # gradient: no second [100352, 2048] beside them
    assert compiled.memory_analysis().temp_size_in_bytes < (
        4 * 1024 * 100352 * 4 + 100352 * 2048 * 4 * 1.5)


@pytest.mark.parametrize("rows,hidden,vocab", [
    (16384, 5120, 152064), (32768, 4096, 196608)])
def test_the_widest_heads_and_their_loss_hold_one_block_of_logits(
        one_chip, rows, hidden, vocab):
    """The blocked head + loss with its gradients made in the forward, at
    the two widest heads of the cells' models (dots3-note's and
    Solar-Open2's whole vocabularies, of which a cell holds an eighth;
    blocks of 2048 rows): the temporaries are a block's float32 logits,
    which become their own gradient in place. No second block and no
    table-sized array beside them: the table's gradient is summed in the
    output's own buffer."""
    from paddle_tpu.nn.functional.loss import _linear_cross_entropy

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(jax.value_and_grad(
        lambda h_, w_, l_: _linear_cross_entropy(h_, w_, l_, 2048, -100),
        argnums=(0, 1))).lower(
            sds((rows, hidden)), sds((hidden, vocab)),
            sds((rows,), jnp.int32)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < (
        1.5 * 2048 * vocab * 4)


def test_dots3_note_kernels_at_the_cells_shapes(one_chip):
    """The learned selection's kernels at the dots3-note cell's widths (64
    index heads of 128, a group of 16 heads of 128 | 64 | 128, an int8 mask
    shared by the heads) over 4096 tokens (the four tile-walking kernels over
    the cell's 16384), and the window layer's route:
    MHA through splash under a LocalMask of 513, keys 256 wide, values
    128 (PR 33). Mosaic reads an int8 tile, slices a lane out of the
    heads' weights and transposes a float32 tile here or nowhere."""
    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    S, J, D, H = 4096, 64, 128, 16
    f32 = jnp.float32
    text = _compile(dsa.index_scores, sds((S, J, D), f32), sds((S, D), f32),
                    sds((S, J), f32))
    assert "dsa_index_scores" in text
    # the operands' two bfloat16 halves are made by roundings XLA keeps
    # (a float32 -> bfloat16 -> float32 round trip is dropped inside a
    # program, and the low half with it: PR 33)
    assert text.count("reduce-precision") >= 4
    # the four that walk the masked tiles, at the cell's 16384 tokens: 512
    # rows and as many heads a step as `_heads_per_step` gives them have to
    # fit the VMEM limit the file sets (ISSUE 35)
    L = 16384
    heads = (sds((H, L, 128)), sds((H, L, 64)), sds((H, L, 128)),
             sds((L, 64)), sds((H, L, 128)))
    text = _compile(jax.grad(
        lambda *a: _sum32(dsa.selected_attention(*a, 192 ** -0.5)[0]),
        argnums=(0, 1, 2, 3, 4)), *heads, sds((L, L), jnp.int8))
    assert all(k in text for k in ("dsa_core_fwd", "dsa_core_bwd_dq",
                                   "dsa_core_bwd_dkv"))
    for acc in ((sds((L, L), f32),), ()):     # a later group's call, the first
        text = _compile(
            lambda qn, qr, kn, kr, lse, m, *acc: dsa.head_prob_sum(
                qn, qr, kn, kr, lse, m, 192 ** -0.5, *(acc or (None,))),
            *heads[:4], sds((H, L), f32), sds((L, L), jnp.int8), *acc)
        assert "dsa_head_probs" in text
    mask = sds((S, S), jnp.int8)
    text = _compile(jax.grad(
        lambda qi, ki, w, sc, m, lse, ps: dsa.indexer_loss(
            qi, ki, w, sc, m, lse, ps, 128), argnums=(0, 1, 2)),
        sds((S, J, D), f32), sds((S, D), f32), sds((S, J), f32),
        sds((S, S), f32), mask, sds((S,), f32), sds((S, S), f32))
    assert "dsa_index_bwd_dq" in text and "dsa_index_bwd_dk" in text
    compiled = jax.jit(lambda sc: dsa.select_top_k(sc, 2048)).lower(
        sds((S, S), f32)).compile()
    # a block of rows at a time: no second array of the scores' size
    assert compiled.memory_analysis().temp_size_in_bytes < S * S * 4
    q, k, v = sds((1, S, 16, 256)), sds((1, S, 16, 256)), sds((1, S, 16, 128))
    assert fa.supported(q.shape, k.shape, True, v_dim=128, window=513)
    text = _compile(jax.grad(lambda q_, k_, v_: _sum32(
        fa.flash_attention_bshd(q_, k_, v_, causal=True, window=513)),
        argnums=(0, 1, 2)), q, k, v)
    assert "splash_mqa_fwd" in text and "splash_mqa_dkv" in text


# (buffer rows, hidden, tokens) of the three cells that hold an expert layer
CELL_ROWS = {"dots3": (16384, 5120, 16384), "solar": (16384, 4096, 32768),
             "glm": (32768, 2048, 16384)}


def _no_scatter_of(text, shape):
    return not [line for line in text.splitlines() if " scatter(" in line
                and line.split("=")[1].lstrip().startswith(shape)]


@pytest.mark.parametrize("cell", CELL_ROWS)
def test_row_moves_at_the_cells_shapes(one_chip, cell):
    """`gather_rows` and `scatter_add_rows` forward and backward, inside a
    `jax.checkpoint` as `Dots3NoteDecoderLayer._experts` runs them, at each
    cell's buffer rows, width and tokens: every scatter-add is the Pallas
    kernel (its float32 tile, two chunks and two output blocks fit the
    VMEM limit the file sets) and the compiled text holds no scatter of a
    [tokens, hidden] array."""
    R, H, T = CELL_ROWS[cell]
    assert rows.route(R, T, H, jnp.bfloat16) == "kernel"

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    @jax.checkpoint
    def moves(x, w, idx, live):
        return rows.scatter_add_rows(rows.gather_rows(x, idx, live) * w,
                                     idx, live, T)

    text = _compile(jax.grad(
        lambda *a: jnp.sum(moves(*a).astype(jnp.float32) ** 2),
        argnums=(0, 1)), sds((T, H)), sds((R, 1)), sds((R,), jnp.int32),
        sds((), jnp.int32))
    assert _mosaic_calls(text, "moe_scatter_add_rows") >= 2
    assert _no_scatter_of(text, f"bf16[{T},{H}]")


def test_dots3_expert_half_layer_beside_the_form_before(one_chip):
    """The routed half of the dots3 cell's expert layer (16,384 tokens and
    buffer rows of 5120, 8 experts of 1536 held, top-8 of 256) under
    `jax.checkpoint`, forward and backward: two calls of the scatter-add
    kernel, no scatter of a [tokens, hidden] array, and the program's peak
    printed beside that of the form before (`tests/_moe_parent_rows.py`);
    the whole step's two peaks are in PERF.md section 6 (the cell has 0.43
    GiB of headroom)."""
    import sys
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import _moe_parent_rows as parent
    from paddle_tpu.nn.layer.moe import dropless_moe
    (R, H, T), M, E, k = CELL_ROWS["dots3"], 1536, 8, 8

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = (sds((T, H)), sds((H, 32 * E)), sds((E, H, 2 * M)),
            sds((E, M, H)))

    def half(layer):
        routed = jax.checkpoint(lambda x, *ws: x + layer(
            x, *ws, first_expert=0, top_k=k, rows=R)[0])
        return jax.jit(jax.grad(
            lambda *a: jnp.sum(routed(*a).astype(jnp.float32) ** 2),
            argnums=(0, 1, 2, 3))).lower(*args).compile()

    new, old = half(dropless_moe), half(parent.dropless_moe)
    assert _mosaic_calls(new.as_text(), "moe_scatter_add_rows") == 2
    assert _no_scatter_of(new.as_text(), f"bf16[{T},{H}]")
    assert not _no_scatter_of(old.as_text(), f"bf16[{T},{H}]")
    peak, before = (c.memory_analysis().peak_memory_in_bytes
                    for c in (new, old))
    print(f"dots3 expert half layer: peak {peak / 2**30:.3f} GiB, the form "
          f"before {before / 2**30:.3f}")
    # the ordered copy and its index lists, and nothing else of the
    # buffer's size, may be live beside what the form before held
    assert peak <= before + 1.1 * R * H * 2


def test_hyper_connection_mixing_at_the_cells_shape(one_chip):
    """The four-stream half-layer's mixing around a stand-in branch, forward
    and backward, at the xing4_0 cell's shape (two sequences of 4096 tokens,
    four streams of 3584 columns, bf16): `hc_pre_fwd` and `hc_post_fwd` are
    Mosaic calls whose blocks (all four streams of 256 rows x 512 columns,
    in and out, and the float32 maps) fit the kernel's VMEM, and the step
    keeps no float32 array of the streams' size."""
    B, n, S, C = 2, 4, 4096, 3584

    def sds(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def half(X, phi, scale, bias, w):
        h_pre, h_post, h_res = hc.maps(
            X, phi, scale, bias, eps=1e-6, iters=20, hc_eps=1e-6,
            clamp=(-30.0, 30.0))
        u = hc.pre(X, h_pre)
        return hc.post(X, jnp.tanh(u @ w), h_res, h_post)

    text = _compile(jax.grad(
        lambda *a: jnp.sum(half(*a).astype(jnp.float32) ** 2),
        argnums=(0, 1, 2, 3)), sds((n, B, S, C)), sds((n * C, 24)),
        sds((3,), jnp.float32), sds((24,), jnp.float32), sds((C, C)))
    assert _mosaic_calls(text, "hc_pre_fwd") == 1
    assert _mosaic_calls(text, "hc_post_fwd") == 1
    wide = f"f32[{B},{n},{S},{C}]"
    assert not [ln for ln in text.splitlines()
                if ln.split("=")[0].strip().startswith(("ROOT", "%"))
                and f" = {wide}" in ln and " fusion(" not in ln], wide
