#!/usr/bin/env python3
"""Compile a `pretrain` cell's whole step for a DESCRIBED v5e, by hand:

    JAX_PLATFORMS=cpu python3 chipbench/rehearse_pretrain.py <cell> [key=value ...]

`rehearse_compile.py` for the `pretrain` traffic kind (that file knows
`train` and `serve` by name): nothing runs and no chip is needed, the TPU
compiler refuses here what it would refuse on the chip, and
`memory_analysis()` gives the bytes a device holds (it over-counts: the
`solar-open2-250b-ep40` step reads 14.59 GiB here and peaks at 10.38e9
bytes on the chip). `key=value` overrides a key of the configuration
(`num_hidden_layers=1`, `gqa_layers=9`), of its `program` group
(`program.kda_head_group=8`) or the sequence (`seq=4096`).
`REHEARSE=reference` compiles the plain reference's four half-layer VJPs
instead (`REHEARSE_MODE=fp8` in the control's precision): does the check
fit beside the stored state? A compile that passes is not a chip run and
gives no time.
"""
from __future__ import annotations

import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)


def rehearse_step(config, seq, batch, one, parts):
    import jax
    import numpy as np

    import paddle_tpu as paddle
    import paddle_tpu.optimizer as popt
    from chipbench import rehearse_compile, weights

    program = parts[0]
    cfg = program.model_config(config)
    model, shapes = program.skeleton(cfg)
    abstract = {k: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one)
                for k, s in shapes.items()}
    weights.install(model, abstract)
    tr = config["trainer"]
    opt = popt.AdamW(learning_rate=tr["learning_rate"], beta1=tr["beta1"],
                     beta2=tr["beta2"], epsilon=tr["epsilon"],
                     parameters=model.parameters(),
                     weight_decay=tr["weight_decay"])
    names = {id(t): k for k, t in model.state_dict().items()}
    for p in model.parameters():               # what prime() would make
        for slot in ("moment1", "moment2"):
            opt._state[(id(p), slot)] = abstract[names[id(p)]]
    n = sum(int(np.prod(abstract[names[id(p)]].shape))
            for p in model.parameters())
    print(f"parameters: {n / 1e6:.2f} M", flush=True)
    step = paddle.jit.TrainStep(model, opt, lambda i, l: model.loss(i, l))
    step._build()
    x = paddle.to_tensor(np.zeros((1, 8), np.int32))
    x.data = jax.ShapeDtypeStruct((batch, seq), np.int32, sharding=one)
    t0 = time.perf_counter()
    args = tuple(jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype,
                                      sharding=one)
                 if isinstance(a, (np.ndarray, np.generic)) else a
                 for a in step._call_args((x, x)))
    lowered = step._compiled.lower(*args)
    print(f"train step: lowered in {time.perf_counter() - t0:.1f} s",
          flush=True)
    rehearse_compile._report(
        f"train step depth={cfg.num_hidden_layers} B={batch} S={seq}",
        lowered.compile(), t0)


def rehearse_reference(config, seq, batch, one, parts):
    import jax
    import numpy as np

    from chipbench import rehearse_compile
    program, ref = parts[0], parts[1]
    _, shapes = program.skeleton(program.model_config(config))
    a, held = ref.arch(config), ref.held_of(config)
    cap = ref.expert_cap(a, held, seq)
    mode = os.environ.get("REHEARSE_MODE") or None
    x = jax.ShapeDtypeStruct((batch, seq, config["hidden_size"]), np.float32,
                             sharding=one)
    seen = set()
    with jax.default_matmul_precision("highest"):
        for i in range(config["num_hidden_layers"]):
            kind = ref.layer_kind(a, i)
            if kind in seen:
                continue
            seen.add(kind)
            w = {k: jax.ShapeDtypeStruct(shapes[n].shape, shapes[n].dtype,
                                         sharding=one)
                 for k, n in ref.layer_names(a, i).items()}
            mixer = {k: v for k, v in w.items()
                     if k == "ln1" or k.startswith("mixer.")}
            mlp = {k: v for k, v in w.items()
                   if k == "ln2" or k.startswith("mlp.")}
            for name, lower in (
                    (f"reference {kind} mixer VJP", lambda: ref._mixer_bwd.lower(
                        mixer, x, x, kind=kind, a=a, mode=mode)),
                    (f"reference expert VJP (layer {i})",
                     lambda: ref._expert_bwd.lower(
                         mlp, x, x, a=a, held=held, mode=mode, cap=cap))):
                t0 = time.perf_counter()
                rehearse_compile._report(f"{name} B={batch} S={seq}",
                                         lower().compile(), t0)


def main(argv):
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from chipbench import rehearse_compile, run
    from chipbench.drivers import pretrain
    _, _, cell, config, traffic = run.load_cell(ROOT, argv[0])
    seq = traffic["seq_len"]
    for kv in argv[1:]:
        key, value = kv.split("=", 1)
        if key == "seq":
            seq = int(value)
        elif key == "gqa_layers":
            config[key] = [int(t) for t in value.split(",") if t]
        elif key.startswith("program."):
            config.setdefault("program", {})[key[8:]] = int(value)
        else:
            config[key] = int(value)
    rehearse_compile._steer_kernels_to_tpu()
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    which = (rehearse_reference if os.environ.get("REHEARSE") == "reference"
             else rehearse_step)
    which(config, seq, cell["batch_size"], one, pretrain.parts(config))


if __name__ == "__main__":
    main(sys.argv[1:])
