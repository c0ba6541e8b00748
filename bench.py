#!/usr/bin/env python
"""Training benchmark of ONE model on the TPU this process finds.

Prints the device it ran on, then ONE JSON line:
  {"metric": ..., "value": N, "unit": "tokens/s/chip", "vs_baseline": N}

vs_baseline is MFU relative to the A100+NCCL parity target (BASELINE.json):
A100 LLaMA pretraining lands at ~50% MFU with a tuned Megatron-style stack,
so vs_baseline = our_MFU / 0.50 (>= 1.0 means we beat the baseline).

It needs a TPU: on any other platform it says so and exits 1 — a number
from a CPU is not a device metric. A device_kind without a listed peak
(observability/goodput.py) is an error. It writes no file. Any failure
is a non-zero exit.

Env knobs: BENCH_MODEL (tiny|350m|1b|7b for LLaMA — BASELINE config 3 —
plus bert|ernie|resnet50|unet for BASELINE configs 2/4/1/5),
BENCH_BATCH, BENCH_SEQ, BENCH_IMG, BENCH_STEPS, BENCH_REMAT,
BENCH_FUSE_QKV_MLP.
"""
from __future__ import annotations

import json
import os
import sys


def _peak_flops(device) -> float:
    from paddle_tpu.observability.goodput import peak_for_device_kind
    peak = peak_for_device_kind(device.device_kind)
    if peak is None:
        raise SystemExit(
            f"bench: no peak FLOP/s is listed for device_kind "
            f"{device.device_kind!r} (observability/goodput.py _PEAK); "
            f"MFU cannot be computed")
    return peak


def _time_steps(step, args, steps):
    """Warmup until the jit cache stops growing, then time `steps`.
    The timed loop runs with the goodput ledger armed, so every BENCH
    artifact carries the step-time decomposition (productive vs badput
    buckets + the ledger's own MFU reading) next to tokens/s."""
    import time as _time

    from paddle_tpu import observability as _obs
    from paddle_tpu.observability import goodput as _goodput
    prev_cache = -1
    warmup = 0
    while warmup < 6:
        loss = step(*args)
        warmup += 1
        cache = getattr(step._compiled, "_cache_size", lambda: None)()
        if cache is not None and cache == prev_cache and warmup >= 3:
            break
        prev_cache = cache
    float(loss.numpy())
    restore = _obs.arm()
    # one armed warmup step OUTSIDE the timed loop: the first armed call
    # pays the one-off cost_analysis lowering for the MFU gauge
    loss = step(*args)
    float(loss.numpy())
    _goodput.reset()
    _goodput.open_window()
    t0 = _time.perf_counter()
    for _ in range(steps):
        loss = step(*args)
    last = float(loss.numpy())
    dt = _time.perf_counter() - t0
    # under async dispatch the per-step windows measure DISPATCH wall;
    # the final blocking pull drains the queued device work — close one
    # more window over it so the drain reads as device-execute time
    # instead of vanishing from the attribution
    _goodput.step_boundary()
    gp = _goodput.summary()
    restore()
    n_compiles = (getattr(step._compiled, "_cache_size",
                          lambda: None)() or 0) - (prev_cache or 0)
    goodput = {
        "productive_seconds": round(gp["productive_seconds"], 4),
        "badput_seconds": {k: round(v, 4)
                           for k, v in gp["badput_seconds"].items()},
        "productive_fraction": round(gp["productive_fraction"], 4),
        "attributed_fraction": round(gp["wall_seconds"] / dt, 4)
                               if dt else 0.0,
        "mfu": round(gp["mfu"], 4),
    }
    return dt, last, n_compiles, goodput


def _measured_fwd_flops(model, *example):
    """XLA's own flop count of the model forward (used where no closed
    formula exists — ResNet/UNet); train step ~ 3x forward."""
    import jax

    from paddle_tpu.framework import core
    from paddle_tpu.tensor import Tensor

    state = {k: t.data for k, t in model.state_dict().items()}

    def fwd(state, *xs):
        with model.use_state(state), core.no_grad_guard():
            out = model(*[Tensor(x) for x in xs])
            return out.data if isinstance(out, Tensor) else out[0].data

    try:
        ca = jax.jit(fwd).lower(state, *example).cost_analysis() or {}
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        return float(ca.get("flops", 0.0) or 0.0)
    except Exception:
        return 0.0


def _bench_other(size, devs):
    """BASELINE.md configs 1/2/4/5 (ResNet-50 / BERT / ERNIE / UNet);
    config 3 (LLaMA) is the default path in main()."""
    import numpy as np

    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    import paddle_tpu.optimizer as popt

    rng = np.random.default_rng(0)
    paddle.seed(0)
    steps = int(os.environ.get("BENCH_STEPS", 8))

    if size in ("bert", "ernie"):
        if size == "bert":
            from paddle_tpu.models.bert import (BertForMaskedLM as ctor,
                                                bert_base)
            cfg = bert_base()
        else:
            from paddle_tpu.models.ernie import (
                ErnieForPretraining as ctor, ernie_base)
            cfg = ernie_base()
        model = ctor(cfg)
        B = int(os.environ.get("BENCH_BATCH", 16))
        S = int(os.environ.get("BENCH_SEQ", 512))
        ids = paddle.to_tensor(
            rng.integers(0, cfg.vocab_size, (B, S)).astype(np.int32))
        step_fn = lambda i, l: model.loss(i, l)
        args = (ids, ids)
        items = B * S
        unit = "tokens/s/chip"
        n_params = sum(int(np.prod(t.shape)) for t in model.parameters())
        flops_per_step = (6 * n_params + 12 * cfg.num_hidden_layers
                          * cfg.hidden_size * S) * items
    elif size == "resnet50":
        from paddle_tpu.vision.models import resnet50
        model = resnet50(num_classes=1000)
        B = int(os.environ.get("BENCH_BATCH", 64))
        HW = int(os.environ.get("BENCH_IMG", 224))
        img = paddle.to_tensor(
            rng.standard_normal((B, 3, HW, HW)).astype(np.float32))
        lbl = paddle.to_tensor(rng.integers(0, 1000, (B,)).astype(np.int32))
        step_fn = lambda x, y: nn.functional.cross_entropy(model(x), y)
        args = (img, lbl)
        items = B
        unit = "images/s/chip"
        flops_per_step = 3.0 * _measured_fwd_flops(model, img.data)
    elif size == "unet":
        from paddle_tpu.models.unet import UNet2DConditionModel
        model = UNet2DConditionModel(
            block_out_channels=(128, 256, 512, 512),
            cross_attention_dim=512, sample_size=32)
        cfgm = model.cfg
        B = int(os.environ.get("BENCH_BATCH", 8))
        sz = cfgm.sample_size
        x = paddle.to_tensor(rng.standard_normal(
            (B, cfgm.in_channels, sz, sz)).astype(np.float32))
        t = paddle.to_tensor(rng.integers(0, 1000, (B,)).astype(np.int32))
        ctx = paddle.to_tensor(rng.standard_normal(
            (B, 16, cfgm.cross_attention_dim)).astype(np.float32))
        noise = paddle.to_tensor(rng.standard_normal(
            x.shape).astype(np.float32))
        step_fn = lambda x, t, c, n: nn.functional.mse_loss(
            model(x, t, c), n)
        args = (x, t, ctx, noise)
        items = B
        unit = "images/s/chip"
        flops_per_step = 3.0 * _measured_fwd_flops(
            model, x.data, t.data, ctx.data)
    else:
        raise ValueError(f"unknown BENCH_MODEL {size}")

    opt = popt.AdamW(learning_rate=1e-4, parameters=model.parameters(),
                     weight_decay=0.01)
    step = paddle.jit.TrainStep(model, opt, step_fn)
    dt, last, n_compiles, goodput = _time_steps(step, args, steps)

    n_chips = len(devs)
    rate = items * steps / dt / n_chips
    peak = _peak_flops(devs[0])
    mfu = flops_per_step * steps / dt / n_chips / peak
    print(json.dumps({
        "metric": f"{size}_train_{unit.replace('/s/chip', '')}_per_sec_per_chip",
        "value": round(rate, 2), "unit": unit,
        "vs_baseline": round(mfu / 0.50, 4),
        "extra": {"mfu": round(mfu, 4), "loss": round(last, 4),
                  "steps": steps, "n_chips": n_chips,
                  "compiles_in_timed_loop": n_compiles,
                  "goodput": goodput,
                  "device": getattr(devs[0], "device_kind",
                                    devs[0].platform)},
    }))


def main():
    import numpy as np

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(
            f"bench: needs a TPU; JAX reports platform "
            f"{devs[0].platform!r}. A CPU timing is not a device metric: "
            f"nothing was run.")
    print(f"bench: device platform={devs[0].platform} "
          f"kind={devs[0].device_kind} count={len(devs)}", file=sys.stderr)
    _peak_flops(devs[0])             # an unlisted device fails before work

    import paddle_tpu as paddle
    import paddle_tpu.optimizer as popt
    from paddle_tpu.framework.compile_cache import use_compile_cache
    from paddle_tpu.models import llama as L

    use_compile_cache()
    kind = devs[0].device_kind.lower().replace(" ", "")
    small_hbm = ("lite" in kind) or ("v5e" in kind)  # v5e: 16 GB HBM
    default_model = "350m" if small_hbm else "1b"
    size = os.environ.get("BENCH_MODEL", default_model)
    if size in ("bert", "ernie", "resnet50", "unet"):
        # BASELINE.md configs 1/2/4/5 — measurement harness parity
        _bench_other(size, devs)
        return
    # remat trades ~1/3 extra forward FLOPs for activation memory; models
    # that fit without it should skip it (BENCH_REMAT=1 forces it on)
    remat_default = size == "7b"
    remat = bool(int(os.environ.get("BENCH_REMAT", int(remat_default))))
    # BENCH_FUSE_QKV_MLP=0 reverts to separate qkv/gate/up matmuls (the
    # layout A/B lever)
    fuse = bool(int(os.environ.get("BENCH_FUSE_QKV_MLP", "1")))

    def _kernel_routes(cfg, batch, seq):
        """What actually RAN: the kernels' own eligibility gates at the
        bench shapes (flag AND backend AND shape), not raw flags."""
        from paddle_tpu.kernels import cross_entropy as _ce
        from paddle_tpu.kernels import flash_attention as _fa
        qkv = (batch, seq, cfg.num_attention_heads, cfg.head_dim)
        kv = (batch, seq, cfg.kv_heads, cfg.head_dim)
        return {
            "fused_ce": bool(_ce.supported(cfg.vocab_size)),
            "flash_attention": bool(_fa.supported(qkv, kv, True)),
            "fused_qkv_mlp": bool(fuse),
        }
    cfg = {"tiny": L.llama_tiny, "350m": L.llama_350m,
           "1b": L.llama_1b, "7b": L.llama_7b}[size](
        use_recompute=remat, fuse_attention_qkv=fuse, fuse_mlp=fuse)
    # batch must divide evenly over the sharding axis (= all chips)
    batch = int(os.environ.get("BENCH_BATCH", max(4, len(devs))))
    batch = max(batch, len(devs))
    seq = int(os.environ.get("BENCH_SEQ", 2048))
    steps = int(os.environ.get("BENCH_STEPS", 8))
    cfg.max_position_embeddings = max(cfg.max_position_embeddings, seq)

    paddle.seed(0)
    model = L.LlamaForCausalLM(cfg)
    opt = popt.AdamW(learning_rate=3e-4, parameters=model.parameters(),
                     weight_decay=0.1)

    def step_fn(ids, labels):
        return model.loss(ids, labels)

    shard = None
    if len(devs) > 1:
        from paddle_tpu.distributed.sharding import ShardingPlan
        from paddle_tpu.distributed.topology import HybridCommunicateGroup
        hcg = HybridCommunicateGroup(dp_degree=1, sharding_degree=len(devs))
        shard = ShardingPlan(hcg.mesh, stage=3)
    step = paddle.jit.TrainStep(model, opt, step_fn, shard=shard)

    rng = np.random.default_rng(0)
    ids = paddle.to_tensor(
        rng.integers(0, cfg.vocab_size, (batch, seq)).astype(np.int32))

    # warmup-until-cache-stable + timing shared with _bench_other: the
    # state tree widens twice (moments, then master weights), each
    # widening = a recompile; the timed loop must see zero compiles
    dt, last, n_compiles_timed, goodput = _time_steps(step, (ids, ids),
                                                      steps)

    n_chips = len(devs)
    tokens = batch * seq * steps
    tok_per_sec_chip = tokens / dt / n_chips

    n_params = sum(int(np.prod(t.shape)) for t in model.parameters())
    # PaLM-appendix accounting: 6N per token + attention 12*L*d_model*S
    flops_per_token = 6 * n_params + 12 * cfg.num_hidden_layers * \
        cfg.hidden_size * seq
    peak = _peak_flops(devs[0])
    mfu = tok_per_sec_chip * flops_per_token / peak
    vs_baseline = mfu / 0.50

    print(json.dumps({
        "metric": f"llama_{size}_train_tokens_per_sec_per_chip",
        "value": round(tok_per_sec_chip, 2),
        "unit": "tokens/s/chip",
        "vs_baseline": round(vs_baseline, 4),
        "extra": {
            "mfu": round(mfu, 4), "loss": round(last, 4),
            "batch": batch, "seq": seq, "steps": steps,
            "n_params": n_params, "n_chips": n_chips,
            "compiles_in_timed_loop": n_compiles_timed,
            "goodput": goodput,
            "device": getattr(devs[0], "device_kind", devs[0].platform),
            # self-describing kernel routes: the record says which ran
            "kernel_routes": _kernel_routes(cfg, batch, seq),
        },
    }))


if __name__ == "__main__":
    main()
