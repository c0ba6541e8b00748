"""One splash call's backward in its two forms, at a cell's call shape.

The library's backward is either two kernels (dkv, then dq: each makes a
tile's scores and probabilities) or one (`use_fused_bwd_kernel`: dq leaves
the kernel as Sk // block_kv_dkv copies of q and is summed after). This
script times forward + backward of ONE call (the vmap over kv heads that
`kernels/flash_attention._splash_gqa` makes) for the two-kernel form and
for the one-kernel form at each outer kv block, and prints a JSON line a
form. It is how `flash_attention._ONE_KERNEL_*` were chosen (PERF.md,
section 6, PR 50).

On the chip:   python3 benchmarks/splash_backward_forms.py glm solar
Without one:   JAX_PLATFORMS=cpu python3 benchmarks/splash_backward_forms.py --compile-only glm
               (compiles each form for a described v5e: says which outer
               blocks Mosaic's scoped VMEM refuses; no time)
"""
from __future__ import annotations

import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp

# name: (kv heads a call, q heads a kv head, S, key width, value width, mask)
SHAPES = {
    "glm": (5, 1, 16384, 256, 256, "causal"),
    "solar": (1, 8, 32768, 128, 128, "causal"),
    "granite": (8, 4, 32768, 64, 64, "causal"),
    "xing": (8, 1, 4096, 256, 128, "causal"),
    "yi": (4, 8, 4096, 128, 128, "causal"),
    "dots3_window": (16, 1, 16384, 256, 128, "window513"),
    # with --interpret, on the CPU: the script's own rehearsal
    "tiny": (2, 2, 1024, 128, 128, "causal"),
}
OUTER = (512, 1024, 2048, 4096, 8192)


def _kernel(shape, outer, compute=512, bq=512, interpret=False):
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk, splash_attention_mask as sm)
    hk, group, S, _, _, mask = shape
    if mask == "causal":
        one = sm.CausalMask((S, S))
    else:
        one = sm.LocalMask((S, S), (int(mask[6:]) - 1, 0), 0)
    if outer is None:
        bs = sk.BlockSizes(block_q=bq, block_kv=compute,
                           block_kv_compute=compute, block_q_dkv=bq,
                           block_kv_dkv=compute,
                           block_kv_dkv_compute=compute, block_q_dq=bq,
                           block_kv_dq=compute)
    else:
        bs = sk.BlockSizes(block_q=bq, block_kv=compute,
                           block_kv_compute=compute, block_q_dkv=bq,
                           block_kv_dkv=outer,
                           block_kv_dkv_compute=compute,
                           use_fused_bwd_kernel=True)
    kernel = sk.make_splash_mqa_single_device(
        sm.MultiHeadMask([one] * group), block_sizes=bs,
        interpret=interpret)
    return jax.vmap(kernel, in_axes=(0, 0, 0, None))


def _loss_and_grads(shape, outer, interpret=False):
    run = _kernel(shape, outer, interpret=interpret)

    def loss(q, k, v, do):
        return jnp.sum(run(q, k, v, None).astype(jnp.float32)
                       * do.astype(jnp.float32))

    return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))


def _args(shape, abstract, sharding=None):
    hk, group, S, dk, dv, _ = shape
    shapes = [(hk, group, S, dk), (hk, S, dk), (hk, S, dv), (hk, group, S, dv)]
    if abstract:
        return [jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=sharding)
                for s in shapes]
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    return [(jax.random.normal(k, s, jnp.float32) * 0.5).astype(jnp.bfloat16)
            for k, s in zip(keys, shapes)]


def _device_ms(compiled, args, name, outer, n=3):
    """Device self milliseconds a call by operation, from a profiler trace
    of n calls (the reader is the benchmark's: chipbench/trace_reduce)."""
    import shutil
    import tempfile
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "chipbench"))
    import trace_reduce
    d = tempfile.mkdtemp(prefix=f"splash_{name}_{outer}_")
    try:
        jax.profiler.start_trace(d)
        for _ in range(n):
            jax.block_until_ready(compiled(*args))
        jax.profiler.stop_trace()
        trace = trace_reduce.load(d, span_names=())
        ops = {}
        for events in trace["device"].values():
            for op, sec in trace_reduce.self_times(events).items():
                base = trace_reduce.base_name(op)
                ops[base] = ops.get(base, 0.0) + sec * 1e3 / n
        return {k: round(v, 3) for k, v in sorted(
            ops.items(), key=lambda kv: -kv[1]) if v >= 0.01}
    except Exception as e:  # a timing without its split is still a reading
        return {"error": f"{type(e).__name__}: {e}"[:200]}
    finally:
        shutil.rmtree(d, ignore_errors=True)


def main(argv):
    compile_only = "--compile-only" in argv
    interpret = "--interpret" in argv
    names = [a for a in argv if not a.startswith("--")] or ["glm", "solar"]
    sharding = None
    if compile_only:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        sharding = SingleDeviceSharding(topo.devices[0])
        jax.config.update("jax_enable_compilation_cache", False)
    out = []
    for name in names:
        shape = SHAPES[name]
        S = shape[2]
        args = _args(shape, compile_only, sharding)
        ref = None
        for outer in (None,) + tuple(o for o in OUTER if o <= S):
            row = {"shape": name, "outer": outer,
                   "form": "two_kernels" if outer is None else "one_kernel",
                   "copies": None if outer is None else S // outer}
            try:
                fn = _loss_and_grads(shape, outer, interpret)
                t0 = time.time()
                compiled = fn.lower(*args).compile()
                row["compile_s"] = round(time.time() - t0, 2)
                mem = compiled.memory_analysis()
                row["temp_bytes"] = int(mem.temp_size_in_bytes)
                if not compile_only:
                    grads = compiled(*args)
                    jax.block_until_ready(grads)
                    n = 5
                    t0 = time.perf_counter()
                    for _ in range(n):
                        grads = compiled(*args)
                    jax.block_until_ready(grads)
                    row["fwd_bwd_ms"] = round(
                        (time.perf_counter() - t0) / n * 1e3, 3)
                    row["device_ms"] = _device_ms(compiled, args, name, outer)
                    g32 = [g.astype(jnp.float32) for g in grads]
                    if ref is None:
                        ref = g32
                    else:
                        row["max_gap_to_two_kernels"] = [
                            float(jnp.max(jnp.abs(a - b))
                                  / jnp.max(jnp.abs(b)))
                            for a, b in zip(g32, ref)]
                row["ok"] = True
            except Exception as e:  # the compiler's refusal is the reading
                row["ok"] = False
                row["error"] = str(e).strip().splitlines()[-1][:300] \
                    if str(e).strip() else type(e).__name__
            print(json.dumps(row), flush=True)
            out.append(row)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/splash_backward_forms.jsonl", "a") as f:
        for row in out:
            f.write(json.dumps(row) + "\n")


if __name__ == "__main__":
    main(sys.argv[1:])
