#!/usr/bin/env python3
"""Compile a cell's whole program for a DESCRIBED v5e:2x2, by hand:

    JAX_PLATFORMS=cpu python3 chipbench/rehearse_compile.py <cell> [key=value ...]

Nothing runs and no chip is needed: the TPU compiler refuses here what it
would refuse on the chip (a block that does not tile, a kernel out of
VMEM, a program that does not fit), and `memory_analysis()` gives the
bytes a device holds. It is how the depths and `total_pages` in the
configuration files were found; `key=value` overrides a key of the
configuration (`num_hidden_layers=28`), of its `engine` group
(`engine.total_pages=7000`) or of the cell (`cell.batch_size=2`) for that
search. `REHEARSE=reference` compiles the plain reference's own programs
for the cell's devices instead (does the check fit the chip?). A compile
that passes is not a chip run and gives no time.

Not a test: only one process may hold libtpu, and the repository's one
test file that describes the topology is `tests/test_tpu_compile.py`.
The program asks `jax.default_backend()` whether to take its Pallas
routes; this script answers for it (every `kernels.*._on_tpu`), as that
test file does.
"""
from __future__ import annotations

import importlib
import os
import pkgutil
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)


def _steer_kernels_to_tpu():
    import paddle_tpu.kernels as K
    for m in pkgutil.iter_modules(K.__path__):
        mod = importlib.import_module(f"paddle_tpu.kernels.{m.name}")
        if hasattr(mod, "_on_tpu"):
            mod._on_tpu = lambda: True
        if hasattr(mod, "on_tpu"):
            mod.on_tpu = lambda: True


def _report(name, compiled, t0):
    from chipbench import harness
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    text = compiled.as_text()
    kernels = sorted({n.split("/")[-2] if "/" in n else n
                      for n in harness.kernels_in(text)})
    colls = {c: text.count(f" {c}(") + text.count(f" {c}-start(")
             for c in ("all-gather", "all-reduce", "reduce-scatter",
                       "all-to-all", "collective-permute")}
    print(f"{name}: compiled in {time.perf_counter() - t0:.1f} s; per "
          f"device: arguments {mem.argument_size_in_bytes / 2**30:.2f} GiB, "
          f"temporaries {mem.temp_size_in_bytes / 2**30:.2f} GiB, outputs "
          f"{mem.output_size_in_bytes / 2**30:.2f} GiB, aliased "
          f"{mem.alias_size_in_bytes / 2**30:.2f} GiB -> "
          f"{total / 2**30:.2f} GiB of 15.75")
    print(f"{name}: kernels {kernels}")
    print(f"{name}: collectives {colls}", flush=True)


def rehearse_train(config, traffic, cell, topo):
    import jax
    import numpy as np
    from jax.sharding import SingleDeviceSharding

    import paddle_tpu as paddle
    import paddle_tpu.optimizer as popt
    from chipbench import harness, weights
    from chipbench.drivers import train

    chips = config["chips"]
    devices = list(topo.devices[:chips])
    plan = train._plan(config, devices)
    cfg = weights.model_config(config)
    model, shapes = weights.skeleton(cfg)
    if plan is None:
        one = SingleDeviceSharding(devices[0])
        shard = {k: one for k in shapes}
        batch_sh = scalar_sh = one
    else:
        shard = harness.plan_shardings(plan, model, shapes)
        batch_sh = scalar_sh = None          # the plan's jit places them
    abstract = {k: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=shard[k])
                for k, s in shapes.items()}
    weights.install(model, abstract)
    tr = config["trainer"]
    opt = popt.AdamW(learning_rate=tr["learning_rate"], beta1=tr["beta1"],
                     beta2=tr["beta2"], epsilon=tr["epsilon"],
                     parameters=model.parameters(),
                     weight_decay=tr["weight_decay"])
    for name, t in model.state_dict().items():   # what prime() would make
        for slot in ("moment1", "moment2"):
            opt._state[(id(t), slot)] = abstract[name]
    step = paddle.jit.TrainStep(model, opt, lambda i, l: model.loss(i, l),
                                shard=plan)
    step._build()
    B, S = cell["batch_size"], traffic["seq_len"]
    x = paddle.to_tensor(np.zeros((1, 8), np.int32))
    x.data = jax.ShapeDtypeStruct((B, S), np.int32, sharding=batch_sh)
    t0 = time.perf_counter()
    args = step._call_args((x, x))
    if scalar_sh is not None:                # host scalars -> the device
        args = tuple(jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype,
                                          sharding=scalar_sh)
                     if isinstance(a, (np.ndarray, np.generic)) else a
                     for a in args)
    lowered = step._compiled.lower(*args)
    print(f"train step: lowered in {time.perf_counter() - t0:.1f} s",
          flush=True)
    _report(f"train step depth={cfg.num_hidden_layers} B={B} S={S} "
            f"chips={chips}", lowered.compile(), t0)


def rehearse_reference(config, traffic, cell, topo):
    """The reference's own programs (one layer forward, one layer's VJP,
    head + loss) on the cell's devices with the weights placed as the
    plan places them: do they compile, and what does a device hold."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from jax.sharding import SingleDeviceSharding

    from chipbench import harness, reference, weights
    from chipbench.drivers import train

    devices = list(topo.devices[:config["chips"]])
    plan = train._plan(config, devices)
    model, shapes = weights.skeleton(weights.model_config(config))
    if plan is None:
        rep = SingleDeviceSharding(devices[0])
        shard = {k: rep for k in shapes}
    else:
        rep = NamedSharding(plan.mesh, P())
        shard = harness.plan_shardings(plan, model, shapes)
    B, S, H = cell["batch_size"], traffic["seq_len"], config["hidden_size"]
    a = reference.arch(config)
    w = {k: jax.ShapeDtypeStruct(shapes[n].shape, shapes[n].dtype,
                                 sharding=shard[n])
         for k, n in reference.layer_names(0).items()}
    x = jax.ShapeDtypeStruct((B, S, H), np.float32, sharding=rep)
    ids = jax.ShapeDtypeStruct((B, S), np.int32, sharding=rep)

    def sds(name):
        return jax.ShapeDtypeStruct(shapes[name].shape, shapes[name].dtype,
                                    sharding=shard[name])

    for name, lowered in (
            ("reference layer forward",
             reference._layer_fwd.lower(w, x, a=a, mode=None)),
            ("reference layer VJP",
             reference._layer_bwd.lower(w, x, x, a=a, mode=None)),
            ("reference head + loss",
             reference._head_loss.lower(sds("model.norm.weight"),
                                        sds("lm_head"), x, ids, eps=a[3],
                                        mode=None))):
        t0 = time.perf_counter()
        _report(f"{name} B={B} S={S} chips={config['chips']}",
                lowered.compile(), t0)


def rehearse_serve(config, traffic, cell, topo):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import SingleDeviceSharding

    from chipbench import weights
    from paddle_tpu.inference import ContinuousBatchingEngine

    one = SingleDeviceSharding(topo.devices[0])
    cfg = weights.model_config(config)
    model, shapes = weights.skeleton(cfg)
    # the engine's constructor allocates its pool: give it a one-page
    # pool and describe the real one in the call's shapes
    weights.install(model, {k: jnp.zeros((1,) * len(s.shape), s.dtype)
                            for k, s in shapes.items()})
    eng_cfg = {k: v for k, v in config["engine"].items()
               if not k.endswith("_how")}
    pages = eng_cfg.pop("total_pages")
    eng = ContinuousBatchingEngine(model, total_pages=2, **eng_cfg)
    eng._donate = True                        # as on the chip
    fn = eng._ragged_fn()

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    state = {k: sds(s.shape, s.dtype) for k, s in shapes.items()}
    T, B = eng._T_pack, eng.B
    pool = sds((cfg.num_hidden_layers, cfg.kv_heads, pages, eng.page,
                cfg.head_dim), jnp.bfloat16)
    i32 = np.int32
    t0 = time.perf_counter()
    lowered = fn.lower(
        state, sds((T,), i32), pool, pool, sds((T,), i32), sds((T,), i32),
        sds((T,), i32), sds((B, eng.ppmax), i32), sds((B,), i32),
        sds((B,), i32), sds((B,), i32), sds((B,), bool), sds((B,), bool),
        jax.eval_shape(lambda: jax.random.key(0)))
    _report(f"ragged step rows={T} slots={B} pages={pages} "
            f"(pool 2 x {np.prod(pool.shape) * 2 / 2**30:.2f} GiB)",
            lowered.compile(), t0)


def main(argv):
    from jax.experimental import topologies

    from chipbench import run
    manifest, entry, cell, config, traffic = run.load_cell(ROOT, argv[0])
    for kv in argv[1:]:
        key, value = kv.split("=", 1)
        target = config
        if key.startswith("cell."):
            target, key = cell, key[5:]
        while "." in key:
            head, key = key.split(".", 1)
            target = target[head]
        target[key] = type(target[key])(value) if target.get(key) is not None \
            else int(value)
    _steer_kernels_to_tpu()
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    if os.environ.get("REHEARSE") == "reference":
        return rehearse_reference(config, traffic, cell, topo)
    {"train": rehearse_train, "serve": rehearse_serve}[traffic["kind"]](
        config, traffic, cell, topo)


if __name__ == "__main__":
    main(sys.argv[1:])
