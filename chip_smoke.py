#!/usr/bin/env python3
"""Start the two main paths on the chip, once, and check what comes out.

    python chip_smoke.py              # one TPU chip: train phase, serve phase
    python chip_smoke.py --chips 4    # four chips: the sharded train step only

Everything runs in this one process (a chip belongs to one process).
The model is LLaMA at the full `llama_7b` widths, bf16, depth cut to what
one 16 GB chip holds with AdamW state; weights and data come from --seed.

  train   `paddle.jit.TrainStep` + AdamW on one repeated batch: the
          compiled step must hold the flash-attention, SwiGLU and fused
          add+RMSNorm kernels, the loss must be finite and must fall.
  serve   `ContinuousBatchingEngine` over requests of different prompt
          lengths: every request finishes, greedy tokens are checked
          against `model.generate`, the compiled step must hold the paged
          attention kernel.
  4 chips the same train step under ShardingPlan(stage=3) over
          sharding=2 x mp=2 against the same steps on one of the chips:
          loss trajectories agree, state is spread, kernels are in.

Without a TPU it says so and exits 1. Any failed check raises. The last
line of a passing run is {"ok": true, "device": {...}}; readings on the
lines before it are smoke readings, not benchmark numbers.
"""
from __future__ import annotations

import argparse
import gc
import json
import re
import sys
import time

# depth (of llama_7b's 32 layers) that fits one v5e chip: parameters,
# gradients and AdamW moments in bf16 plus the activations of one
# 2048-token sequence. Found with compiled.memory_analysis() against a
# described v5e (PERF.md, "Cells"); scan_layers is on, so depth changes
# the memory, not the program.
DEPTH = 4
SEQ = 2048
BATCH = 1
TRAIN_STEPS = 5
LR = 3e-4
# four-chip phase, per step: |sharded - single| <= ATOL + RTOL * |single|
# (numpy.allclose's form). On one repeated batch the loss falls from ~11
# to under 0.1 within three steps: RTOL judges the first steps, ATOL the
# tail. The first four-chip run differed by at most 4.3e-4 at any step
# (PERF.md, PR 22), a tenth of what the tail is allowed
TRAJ_RTOL = 2e-2
TRAJ_ATOL = 5e-3
# serve phase: where the engine's greedy token differs from
# model.generate's, the reference logits of the two must lie within this
# share of the largest logit (4 bf16 ulps): a bf16 near-tie that two
# kernels may break differently, not a wrong answer
TIE_RTOL = 2.0 ** -5

TRAIN_KERNELS = {
    "flash attention": ("flash_attention", "splash"),
    "swiglu fwd": ("swiglu_fwd",),
    "swiglu bwd": ("swiglu_bwd_da",),
    "swiglu bwd dw": ("swiglu_bwd_dw",),
    "fused add+rms_norm": ("fused_add_rms_norm",),
}
SERVE_KERNELS = {
    "paged attention": ("ragged_paged_attention", "paged_attention"),
    "swiglu fwd": ("swiglu_fwd",),
}


def smoke_config(depth=DEPTH, seq=SEQ):
    from paddle_tpu.models.llama import llama_7b
    cfg = llama_7b()
    cfg.num_hidden_layers = depth
    cfg.max_position_embeddings = max(cfg.max_position_embeddings, seq)
    return cfg


def kernels_in(compiled_text):
    """op_name of every Mosaic kernel in a compiled program's text."""
    return [m.group(1) for m in re.finditer(
        r'custom_call_target="tpu_custom_call"[^\n]*?op_name="([^"]*)"',
        compiled_text)]


def require_kernels(compiled_text, wanted, where):
    names = kernels_in(compiled_text)
    missing = [k for k, marks in wanted.items()
               if not any(m in n for n in names for m in marks)]
    if missing:
        raise AssertionError(
            f"{where}: compiled program holds no {missing} kernel "
            f"(tpu_custom_calls found: {sorted(set(names))})")
    return len(names)


def _batch(cfg, seed, batch, seq):
    import numpy as np
    rng = np.random.default_rng(seed)
    return rng.integers(0, cfg.vocab_size, (batch, seq)).astype(np.int32)


def _train(cfg, seed, ids, steps, shard=None, check_kernels=True,
           where="train"):
    """Build model + AdamW + TrainStep from `seed`, compile, take `steps`
    steps on `ids`. Returns (readings, model, optimizer)."""
    import jax

    import paddle_tpu as paddle
    import paddle_tpu.optimizer as popt
    from paddle_tpu.models.llama import LlamaForCausalLM

    paddle.seed(seed)
    model = LlamaForCausalLM(cfg)
    opt = popt.AdamW(learning_rate=LR, parameters=model.parameters(),
                     weight_decay=0.1)
    step = paddle.jit.TrainStep(model, opt,
                                lambda i, l: model.loss(i, l), shard=shard)
    x = paddle.to_tensor(ids)
    t0 = time.perf_counter()
    lowered = step.lower(x, x)       # Python tracing: no cache shortens it
    lower_s = time.perf_counter() - t0
    compiled = lowered.compile()     # XLA + Mosaic: what the cache keeps
    compile_s = time.perf_counter() - t0 - lower_s
    n_kernels = None
    if check_kernels:
        n_kernels = require_kernels(compiled.as_text(), TRAIN_KERNELS, where)
    mem = compiled.memory_analysis()
    losses, step_s = [], []
    for _ in range(steps):
        t0 = time.perf_counter()
        loss = step(x, x)
        jax.block_until_ready(loss.data)
        step_s.append(time.perf_counter() - t0)
        losses.append(float(loss.numpy()))
    import numpy as np
    if not np.isfinite(losses).all():
        raise AssertionError(f"{where}: loss not finite: {losses}")
    if not losses[-1] < losses[0]:
        raise AssertionError(f"{where}: loss did not fall: {losses}")
    readings = {
        "losses": losses, "lower_s": lower_s, "compile_s": compile_s,
        # the first call finds the executable compiled above; the steps
        # after it are warm
        "first_step_s": step_s[0], "warm_step_s": step_s[1:],
        "n_kernels": n_kernels,
        "program_bytes": None if mem is None else int(
            mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes),
    }
    return readings, model, opt


def _release():
    """Give a finished phase's device memory back before the next phase
    needs it: whatever of its model, optimizer state and loaded program
    only reference cycles or jit's caches still hold."""
    import jax
    jax.clear_caches()
    gc.collect()


def _peak_bytes(device):
    stats = device.memory_stats()
    return None if not stats else stats.get("peak_bytes_in_use")


def train_phase(cfg, seed, batch=BATCH, seq=SEQ, steps=TRAIN_STEPS,
                check_kernels=True):
    import jax
    r, _, _ = _train(cfg, seed, _batch(cfg, seed, batch, seq), steps,
                     check_kernels=check_kernels)
    r["peak_bytes_in_use"] = _peak_bytes(jax.devices()[0])
    print(f"train: depth={cfg.num_hidden_layers} batch={batch} seq={seq} "
          f"lower_s={r['lower_s']:.2f} compile_s={r['compile_s']:.2f} "
          f"first_step_s={r['first_step_s']:.2f} "
          f"warm_step_s={[round(s, 4) for s in r['warm_step_s']]} "
          f"program_bytes={r['program_bytes']} "
          f"peak_bytes_in_use={r['peak_bytes_in_use']} "
          f"kernels={r['n_kernels']}")
    print("train: loss " + " ".join(f"{v:.4f}" for v in r["losses"]))
    return r


class _FirstCall:
    """A jitted function that keeps the shapes of its first call, so the
    program that ran can be lowered again and read."""

    def __init__(self, fn):
        self.fn, self.shapes = fn, None

    def __call__(self, *args):
        if self.shapes is None:
            import jax
            self.shapes = jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), args)
        return self.fn(*args)

    def compiled_text(self):
        return self.fn.lower(*self.shapes).compile().as_text()


def _reference_logits(model, rows, pad_to):
    """Teacher-forced logits [n, pad_to, V] of the training forward for
    right-padded token rows (causal: padding changes nothing before it)."""
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.framework import core
    ids = np.zeros((len(rows), pad_to), np.int32)
    for i, r in enumerate(rows):
        ids[i, :len(r)] = r
    with core.no_grad_guard():
        return np.asarray(model(paddle.to_tensor(ids)).numpy(), np.float32)


def serve_phase(cfg, seed, prompt_lens=(5, 17, 33, 64, 100), new_tokens=8,
                max_batch=4, max_seq=256, buckets=(32, 64, 128),
                check_kernels=True):
    import jax
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.inference import (ContinuousBatchingEngine,
                                      GenerationRequest)
    from paddle_tpu.models.llama import LlamaForCausalLM

    paddle.seed(seed)
    model = LlamaForCausalLM(cfg)
    model.eval()
    rng = np.random.default_rng(seed)
    prompts = [[int(t) for t in rng.integers(1, cfg.vocab_size, n)]
               for n in prompt_lens]

    # the page pool at half the dense equivalent: admission then gates on
    # free pages, as it does in a deployment
    pages = (max_batch * (max_seq // 16)) // 2 + 1
    eng = ContinuousBatchingEngine(model, max_batch=max_batch,
                                   max_seq=max_seq, prefill_buckets=buckets,
                                   total_pages=pages)
    programs = {}
    if eng._ragged:
        programs["ragged step"] = eng._compiled_ragged = _FirstCall(
            eng._ragged_fn())
    else:
        programs["decode step"] = eng._compiled_decode = _FirstCall(
            eng._decode_fn())
    reqs = [GenerationRequest(p, max_new_tokens=new_tokens) for p in prompts]
    t0 = time.perf_counter()
    for r in reqs:
        eng.add_request(r)
    finished = eng.run()
    serve_s = time.perf_counter() - t0
    if len(finished) != len(reqs) or any(
            len(r.output) != new_tokens for r in reqs):
        raise AssertionError(
            f"serve: {len(finished)}/{len(reqs)} requests finished, "
            f"output lengths {[len(r.output) for r in reqs]}")
    n_kernels = None
    if check_kernels:
        for name, prog in programs.items():
            n_kernels = require_kernels(prog.compiled_text(), SERVE_KERNELS,
                                        f"serve ({name})")

    # greedy tokens against model.generate, request by request
    ref = [[int(t) for t in np.asarray(model.generate(
        np.asarray([p], np.int32), max_new_tokens=new_tokens).numpy())[0]]
        for p in prompts]
    diverged = [i for i, r in enumerate(reqs) if list(r.output) != ref[i]]
    ties = []
    if diverged:
        # the engine's rows teacher-forced through the training forward:
        # from the first differing position on, every engine token must
        # lie within the near-tie margin of that position's best logit
        pad = -(-(max(prompt_lens) + new_tokens) // 128) * 128
        logits = _reference_logits(
            model, [prompts[i] + list(reqs[i].output) for i in diverged],
            pad)
        for row, i in enumerate(diverged):
            out, n0 = list(reqs[i].output), len(prompts[i])
            first = next(j for j in range(new_tokens) if out[j] != ref[i][j])
            for j in range(first, new_tokens):
                lg = logits[row, n0 + j - 1]
                gap = float(lg.max() - lg[out[j]])
                if gap > TIE_RTOL * float(np.abs(lg).max()):
                    raise AssertionError(
                        f"serve: request {i} token {j}: engine chose "
                        f"{out[j]} (generate: {ref[i][j]}), {gap:.4f} below "
                        f"the reference's best logit {float(lg.max()):.4f} "
                        f"— not a near-tie")
            ties.append((i, first))
    tokens = sum(len(r.output) for r in reqs)
    print(f"serve: depth={cfg.num_hidden_layers} requests={len(reqs)} "
          f"prompt_lens={list(prompt_lens)} tokens={tokens} "
          f"seconds={serve_s:.2f} (compilation included) "
          f"preemptions={eng.preemptions} kernels={n_kernels} "
          f"peak_bytes_in_use={_peak_bytes(jax.devices()[0])}")
    print(f"serve: {len(reqs) - len(diverged)}/{len(reqs)} requests "
          f"token-identical to model.generate"
          + (f"; (request, token) {ties} parted at a bf16 near-tie of the "
             f"reference logits (within {TIE_RTOL:.4f} of the largest) and "
             f"stayed greedy under it" if ties else ""))
    return {"tokens": tokens, "seconds": serve_s, "diverged": diverged}


def _device_bytes(arrays):
    """Bytes each device holds of a collection of jax Arrays."""
    held = {}
    for a in arrays:
        for s in a.addressable_shards:
            held[s.device.id] = held.get(s.device.id, 0) + s.data.nbytes
    return held


def sharded_phase(cfg, seed, devices, batch=2, seq=SEQ, steps=TRAIN_STEPS,
                  check_kernels=True, rtol=TRAJ_RTOL, atol=TRAJ_ATOL):
    """The ZeRO-3 + TP train step on four devices against the same steps
    on the first of them."""
    import numpy as np

    from paddle_tpu.distributed.sharding import ShardingPlan
    from paddle_tpu.distributed.topology import HybridCommunicateGroup

    hcg = HybridCommunicateGroup(dp_degree=1, sharding_degree=2, mp_degree=2,
                                 devices=list(devices[:4]))
    ids = _batch(cfg, seed, batch, seq)
    r4, model, opt = _train(cfg, seed, ids, steps,
                            shard=ShardingPlan(hcg.mesh, stage=3),
                            check_kernels=check_kernels,
                            where="sharded train")
    state = [p.data for p in model.parameters()] + list(opt._state.values())
    held = _device_bytes(state)
    total = sum(a.nbytes for a in state)
    if len(held) != 4 or max(held.values()) > 0.5 * total:
        raise AssertionError(
            f"sharded train: parameters and optimizer state are not "
            f"spread: {held} of {total} bytes")
    print(f"sharded train: mesh sharding=2 x mp=2 "
          f"depth={cfg.num_hidden_layers} batch={batch} seq={seq} "
          f"lower_s={r4['lower_s']:.2f} compile_s={r4['compile_s']:.2f} "
          f"warm_step_s={[round(s, 4) for s in r4['warm_step_s']]} "
          f"kernels={r4['n_kernels']} "
          f"program_bytes_per_device={r4['program_bytes']}")
    print(f"sharded train: state bytes per device {held} of {total} "
          f"unsharded")
    del model, opt, state
    _release()                       # the first chip needs the room back
    r1, _, _ = _train(cfg, seed, ids, steps, check_kernels=check_kernels,
                      where="single-device train")
    a, b = np.asarray(r4["losses"]), np.asarray(r1["losses"])
    # worst step's difference as a share of what that step is allowed
    drift = float(np.max(np.abs(a - b) / (atol + rtol * np.abs(b))))
    print("sharded train: loss " + " ".join(f"{v:.4f}" for v in a))
    print("single device: loss " + " ".join(f"{v:.4f}" for v in b))
    print(f"sharded train: |difference| per step "
          f"{[float(f'{d:.2e}') for d in np.abs(a - b)]}, worst is "
          f"{drift:.2f} of its tolerance ({atol:.0e} + {rtol:.0e}*|single|); "
          f"single-device "
          f"warm_step_s={[round(s, 4) for s in r1['warm_step_s']]}")
    if drift > 1:
        raise AssertionError(
            f"sharded train: trajectory leaves the single-device run's "
            f"tolerance ({atol:.0e} + {rtol:.0e}*|single|) by a factor "
            f"{drift:.2f}")
    return {"sharded": r4, "single": r1, "drift": drift, "held": held}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded train step and the "
                         "single-device run it is compared with")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    devs = jax.devices()
    d0 = devs[0]
    if d0.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX reports platform "
              f"{d0.platform!r} ({len(devs)} device(s)). Nothing was run.",
              file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, JAX reports {len(devs)}", file=sys.stderr)
        return 1
    print(f"device: platform={d0.platform} kind={d0.device_kind} "
          f"count={len(devs)}")

    from paddle_tpu.framework.compile_cache import use_compile_cache
    print(f"compile cache: {use_compile_cache()}")

    cfg = smoke_config()
    print(f"model: llama_7b widths hidden={cfg.hidden_size} "
          f"intermediate={cfg.intermediate_size} "
          f"heads={cfg.num_attention_heads}x{cfg.head_dim} "
          f"vocab={cfg.vocab_size} dtype={cfg.dtype} "
          f"depth={cfg.num_hidden_layers} of 32 seed={args.seed}")
    if args.chips == 4:
        sharded_phase(cfg, args.seed, devs)
    else:
        train_phase(cfg, args.seed)
        _release()                   # the trainer's state, off the chip
        serve_phase(cfg, args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": args.chips}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
