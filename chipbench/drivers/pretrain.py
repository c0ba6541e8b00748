"""Driver of the `pretrain` traffic kind: `paddle.jit.TrainStep` + AdamW on
packed token batches, for any architecture that brings three files named
by its configuration's `model_type`:

    chipbench/program_<model_type>.py    model_config, skeleton, generator,
                                         counters
    chipbench/reference_<model_type>.py  train_steps (the plain reference)
    chipbench/costs_<model_type>.py      train_flops_per_token

The run is `drivers/train.py`'s: ONE compiled step built from the seed,
driven through its first `check_steps` steps by the same call the window
uses, the window, then the reference following the same steps once the
program's state is freed. `first_steps`, `compare` and the window's shape
are that driver's own. On top of its three gaps, `correct` holds what the
compiled step counted: pairs an expert layer found no row for.

Set-up also compiles the reference's programs (`precompile`, from shapes
alone, on a thread of its own beside the step's own compile, joined
before the window opens): in a checkout's first run they would otherwise
compile after the window, one after another, for longer than the window
lasts.
"""
from __future__ import annotations

import importlib
import threading
import time

import numpy as np

from chipbench import harness, weights
from chipbench.drivers import train as dense


def parts(cfg_json):
    """(program, reference, costs) modules of a configuration."""
    kind = cfg_json["model_type"]
    return tuple(importlib.import_module(f"chipbench.{part}_{kind}")
                 for part in ("program", "reference", "costs"))


def build(ctx):
    """The compiled step with its state, from the seed (what
    `drivers.train.build` returns, for `first_steps`), and under
    "reference_warm" the thread that compiles the reference's programs
    beside the step's own compile, for the caller to join."""
    import jax

    import paddle_tpu as paddle
    import paddle_tpu.optimizer as popt

    cfg_json, tr = ctx.config, ctx.config["trainer"]
    program, reference = parts(cfg_json)[:2]
    cfg = program.model_config(cfg_json)
    model, shapes = program.skeleton(cfg)
    make_state = program.generator(shapes)
    weights.install(model, make_state(ctx.seed))
    paddle.seed(ctx.seed % (2 ** 31 - 1))
    opt = popt.AdamW(learning_rate=tr["learning_rate"],
                     beta1=tr["beta1"], beta2=tr["beta2"],
                     epsilon=tr["epsilon"], parameters=model.parameters(),
                     weight_decay=tr["weight_decay"])
    step = paddle.jit.TrainStep(model, opt, lambda i, l: model.loss(i, l))
    B, S = ctx.cell["batch_size"], ctx.traffic["seq_len"]
    ids = weights.token_batches(ctx.seed, cfg.vocab_size,
                                ctx.traffic["distinct_batches"], B, S)
    feed = [paddle.to_tensor(b) for b in ids]
    t0 = time.perf_counter()
    lowered = step.lower(feed[0], feed[0])    # Python tracing, never cached
    lower_s = time.perf_counter() - t0
    # after lower(): its set-up spans count the compiles that end while
    # they are open (`lower_inner_compile_s`)
    warm = threading.Thread(target=reference.precompile, daemon=True,
                            args=(shapes, cfg_json, B, S))
    warm.start()
    compiled = lowered.compile()              # XLA + Mosaic, cached
    compile_s = time.perf_counter() - t0 - lower_s
    n_kernels = None
    if ctx.on_chip:
        n_kernels = harness.require_kernels(
            compiled.as_text(), cfg_json["kernels"], ctx.workload)
    print(f"pretrain: model_type={cfg_json['model_type']} "
          f"depth={cfg.num_hidden_layers} batch={B} seq={S} "
          f"lower_s={lower_s:.3f} compile_s={compile_s:.3f} "
          f"kernels={n_kernels}", flush=True)
    del lowered, compiled

    def call(i):
        """One step on staged batch i: the window's own call and feed."""
        x = feed[i % len(feed)]
        with jax.profiler.TraceAnnotation("train_step"):
            loss = step(x, x)
            jax.block_until_ready(loss.data)
        return loss.data

    return {"model": model, "opt": opt, "step": step, "call": call,
            "make_state": make_state, "shardings": None, "ids": ids,
            "lower_s": lower_s, "compile_s": compile_s, "cfg": cfg,
            "reference_warm": warm}


def counted(ctx, counters):
    """The rows of `correct` that come from the step's own counters."""
    return [harness.compared(
        "moe_dropped_pairs", counters["dropped_pairs"], 0,
        f"pairs that found no row in an expert layer's buffer, all steps; "
        f"rows per held expert in the last step, by layer: "
        f"{counters['expert_tokens']}")]


def control(ctx, controls=True, faults=False):
    """Sound, control and fault readings of one seed, for setting the
    limits (`chipbench/control.py`, `drivers.train.control_sides`)."""
    reference = parts(ctx.config)[1]
    sut = build(ctx)
    got = dense.first_steps(ctx, sut)
    make_state, ids = sut["make_state"], sut["ids"]
    sut["reference_warm"].join()
    sut.clear()
    n = dense._check_steps(ctx)

    def follow(mode=None, trainer=ctx.config["trainer"]):
        harness.release()      # the run before's loaded programs hold memory
        return reference.train_steps(lambda: make_state(ctx.seed), ids[:n],
                                     ctx.config, trainer, mode=mode)

    return dense.control_sides(ctx, got, follow, controls, faults)


def run(ctx):
    program, reference, costs = parts(ctx.config)
    sut = build(ctx)
    got = dense.first_steps(ctx, sut)
    B, S = ctx.cell["batch_size"], ctx.traffic["seq_len"]
    n_check = dense._check_steps(ctx)
    t_join = time.perf_counter()
    sut["reference_warm"].join()       # nothing compiles inside the window
    print(f"pretrain: waited {time.perf_counter() - t_join:.2f} s more for "
          f"the reference's programs", flush=True)

    # -- the window: the same object, the same call
    losses, ends, n, traced = [], [], 0, None
    trace_after = 2                    # steady steps before the trace
    traced_s = traced_n = 0            # the profiler's bracket, kept out
    t0 = time.perf_counter()           # of the traced run's own rate
    setup_s = t0 - ctx.t_start
    while True:
        if ctx.trace and n == trace_after:
            t_in = time.perf_counter()
            with harness.device_trace(ctx) as traced:
                for _ in range(ctx.traffic["trace_steps"]):
                    losses.append(sut["call"](n_check + n))
                    n += 1
            traced_s, traced_n = time.perf_counter() - t_in, n - trace_after
        losses.append(sut["call"](n_check + n))
        n += 1
        elapsed = time.perf_counter() - t0
        ends.append(elapsed)
        if elapsed >= ctx.seconds:
            break
    peak = harness.peak_bytes(ctx.devices[:ctx.chips])
    window_losses = [float(x) for x in losses]
    finite = bool(np.isfinite(window_losses).all())
    tokens_per_s_chip = ((n - traced_n) * B * S / (elapsed - traced_s)
                         / ctx.chips)
    counters = program.counters(sut["model"])     # outside every timing
    print(f"pretrain: steps={n} window_s={elapsed:.4f} "
          f"step_s={elapsed / n:.5f} untraced steps ended at "
          f"{[round(e, 3) for e in ends]} first_losses={got['losses']} "
          f"window_loss_first={window_losses[0]:.4f} "
          f"last={window_losses[-1]:.4f} peak_bytes={peak} "
          f"counters={counters}", flush=True)

    # -- the check: free the program, then follow the same steps plainly
    make_state, ids, lower_s = sut["make_state"], sut["ids"], sut["lower_s"]
    sut.clear()
    harness.release()
    t1 = time.perf_counter()
    ref = reference.train_steps(lambda: make_state(ctx.seed), ids[:n_check],
                                ctx.config, ctx.config["trainer"])
    rows = dense.compare(ctx, got, ref) + counted(ctx, counters)
    rows.append(harness.compared("window_losses_not_finite",
                                 0 if finite else 1, 0, ""))
    print(f"check: reference followed {n_check} steps in "
          f"{time.perf_counter() - t1:.2f} s; most rows an expert was sent "
          f"{ref['expert_rows']}", flush=True)

    run_data = {
        "kind": "train", "trace": traced, "chips": ctx.chips,
        "tokens_per_s_chip": tokens_per_s_chip, "lower_s": lower_s,
        "flops_per_token": costs.train_flops_per_token(ctx.config, S),
        "peaks": ctx.peaks, "config": ctx.config, "batch_size": B,
        "seq_len": S, "steps_traced": ctx.traffic["trace_steps"],
        "counters": counters,
    }
    return {
        "end_to_end": {"train_tokens_per_s_chip": tokens_per_s_chip,
                       "setup_s": setup_s},
        "attempted": n, "failed": 0 if finite else n,
        "compared": rows, "peak_bytes": peak, "run": run_data,
    }
