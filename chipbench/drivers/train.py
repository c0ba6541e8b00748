"""Driver of the `train` traffic kind: `paddle.jit.TrainStep` + AdamW on
packed token batches (a copy of what `chip_smoke.py`'s `_train` and
`sharded_phase` proved on the chip in PR 22, cut to one object).

Set-up builds ONE compiled step with its state, drives it from the seed
through its first `check_steps` steps (every batch different) through
the same call the window uses, and hands that object to the window. What
those steps left behind (losses, the first gradient's norm per leaf read
from AdamW's first moment, the parameters' change per leaf) is compared
with `reference.train_steps` once the window has closed and the
program's state is freed.
"""
from __future__ import annotations

import time

import numpy as np

from chipbench import costs, harness, reference, weights


def _plan(cfg_json, devices):
    """ShardingPlan of the configuration's mesh, or None on one chip."""
    mesh = cfg_json.get("mesh")
    if not mesh:
        return None
    from paddle_tpu.distributed.sharding import ShardingPlan
    from paddle_tpu.distributed.topology import HybridCommunicateGroup
    hcg = HybridCommunicateGroup(
        dp_degree=mesh.get("dp", 1), sharding_degree=mesh.get("sharding", 1),
        mp_degree=mesh.get("mp", 1),
        devices=list(devices[:cfg_json["chips"]]))
    return ShardingPlan(hcg.mesh, stage=mesh["stage"])


def _check_steps(ctx):
    """Steps the check follows: the mix's, or the cell's own where the
    reference would otherwise outlast the window."""
    return ctx.cell.get("check_steps", ctx.traffic["check_steps"])


def _spread(shardings):
    """Reference activations kept for the backward are spread over the
    cell's devices along the sequence (identity on one chip)."""
    if not shardings:
        return lambda x: x
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh = next(iter(shardings.values())).mesh
    axes = tuple(a for a in mesh.axis_names if mesh.shape[a] > 1)
    return lambda x: jax.device_put(x, NamedSharding(mesh,
                                                     P(None, axes, None)))


def _leaf_norms():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def norms(tree, scale):
        return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
                * scale for k, v in tree.items()}

    @jax.jit
    def diff_norms(a, b):
        return {k: jnp.sqrt(jnp.sum(jnp.square(
            a[k].astype(jnp.float32) - b[k].astype(jnp.float32))))
            for k in a}

    return norms, diff_norms


def build(ctx):
    """The compiled step with its state, from the seed. Returns a dict
    the rest of the driver (and the tests, which break it) works on."""
    import jax

    import paddle_tpu as paddle
    import paddle_tpu.optimizer as popt

    cfg_json, tr = ctx.config, ctx.config["trainer"]
    cfg = weights.model_config(cfg_json)
    plan = _plan(cfg_json, ctx.devices)
    model, shapes = weights.skeleton(cfg)
    shardings = None
    if plan is not None:
        shardings = harness.plan_shardings(plan, model, shapes)
    make_state = weights.generator(shapes, shardings)
    weights.install(model, make_state(ctx.seed))
    paddle.seed(ctx.seed % (2 ** 31 - 1))
    opt = popt.AdamW(learning_rate=tr["learning_rate"],
                     beta1=tr["beta1"], beta2=tr["beta2"],
                     epsilon=tr["epsilon"], parameters=model.parameters(),
                     weight_decay=tr["weight_decay"])
    step = paddle.jit.TrainStep(model, opt, lambda i, l: model.loss(i, l),
                                shard=plan)
    B, S = ctx.cell["batch_size"], ctx.traffic["seq_len"]
    ids = weights.token_batches(ctx.seed, cfg.vocab_size,
                                ctx.traffic["distinct_batches"], B, S)
    feed = [paddle.to_tensor(b) for b in ids]
    t0 = time.perf_counter()
    lowered = step.lower(feed[0], feed[0])    # Python tracing, never cached
    lower_s = time.perf_counter() - t0
    compiled = lowered.compile()              # XLA + Mosaic, cached
    compile_s = time.perf_counter() - t0 - lower_s
    n_kernels = None
    if ctx.on_chip:
        n_kernels = harness.require_kernels(
            compiled.as_text(), cfg_json["kernels"], ctx.workload)
    print(f"train: depth={cfg.num_hidden_layers} batch={B} seq={S} "
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
            "make_state": make_state, "shardings": shardings, "ids": ids,
            "lower_s": lower_s, "compile_s": compile_s, "cfg": cfg}


def first_steps(ctx, sut):
    """Drive the step through its first steps and keep what the check
    compares: every loss, |g| per leaf at step 1 (AdamW's first moment
    after one step is (1 - beta1) g), |p_n - p_0| per leaf."""
    norms, diff_norms = _leaf_norms()
    tr = ctx.config["trainer"]
    model, opt = sut["model"], sut["opt"]
    losses, grad_norms = [], None
    for i in range(_check_steps(ctx)):
        losses.append(float(sut["call"](i)))
        if i == 0:
            names = {id(t): k for k, t in model.state_dict().items()}
            m1 = {names[pid]: v for (pid, slot), v in opt._state.items()
                  if slot == "moment1"}
            grad_norms = {k: float(v) for k, v in norms(
                m1, np.float32(1.0 / (1.0 - tr["beta1"]))).items()}
            del m1
    start = sut["make_state"](ctx.seed)
    now = {k: t.data for k, t in model.state_dict().items()}
    delta = {k: float(v) for k, v in diff_norms(now, start).items()}
    del start, now
    return {"losses": losses, "grad_norms": grad_norms,
            "delta_norms": delta}


def loss_rows(limit, got, ref):
    """`loss_gap` as one row under a number (the largest gap over the
    checked steps), or as one row a checked step, `loss_gap.step<n>`,
    under a list: each step held to a limit of its own."""
    gaps = [abs(a - b) for a, b in zip(got["losses"], ref["losses"])]
    note = f"program {got['losses']} reference {ref['losses']}"
    if not isinstance(limit, list):
        return [harness.compared("loss_gap", max(gaps), limit, note)]
    return [harness.compared(f"loss_gap.step{i}", gap, lim, note)
            for i, (gap, lim) in enumerate(zip(gaps, limit), start=1)]


def compare(ctx, got, ref):
    """The numbers `correct` rests on, each beside its limit."""
    lim = ctx.cell["correct"]["limits"]
    g_gap, g_leaf = reference.worst_leaf_gap(got["grad_norms"],
                                             ref["grad_norms"])
    d_gap, d_leaf = reference.worst_leaf_gap(got["delta_norms"],
                                             ref["delta_norms"])
    return loss_rows(lim["loss_gap"]["limit"], got, ref) + [
        harness.compared("first_grad_norm_gap", g_gap,
                         lim["first_grad_norm_gap"]["limit"], g_leaf),
        harness.compared("param_change_norm_gap", d_gap,
                         lim["param_change_norm_gap"]["limit"], d_leaf),
    ]


def control_rows(ctx, got, ref):
    """`compare`'s rows for `chipbench/control.py`, which reads each
    checked step's loss gap whatever form the limit has: under a number
    the steps' rows follow, each beside that number."""
    rows = compare(ctx, got, ref)
    limit = ctx.cell["correct"]["limits"]["loss_gap"]["limit"]
    if not isinstance(limit, list):
        rows += loss_rows([limit] * len(got["losses"]), got, ref)
    return rows


def control_sides(ctx, got, follow, controls=True, faults=False):
    """{"sound": rows, <mode>: rows, "fault:<name>": rows}: the program's
    first steps against `follow()`, the reference; then, in the program's
    place, the reference in each lower precision of `correct.controls`
    (`follow(mode=)`) and the reference under each of `correct.faults`,
    a gross fault written as the trainer settings it changes
    (`follow(trainer=)`)."""
    ref = follow()
    out = {"sound": control_rows(ctx, got, ref)}
    for mode in ctx.cell["correct"]["controls"] if controls else ():
        out[mode] = control_rows(ctx, follow(mode=mode), ref)
    wrong = ctx.cell["correct"].get("faults", {}) if faults else {}
    for name, changed in wrong.items():
        low = follow(trainer={**ctx.config["trainer"], **changed})
        out["fault:" + name] = control_rows(ctx, low, ref)
    return out


def control(ctx, controls=True, faults=False):
    """Sound, control and fault readings of one seed, for setting the
    limits (`control_sides`)."""
    sut = build(ctx)
    got = first_steps(ctx, sut)
    make_state, shardings, ids = (sut["make_state"], sut["shardings"],
                                  sut["ids"])
    sut.clear()
    harness.release()
    n = _check_steps(ctx)
    keep = _spread(shardings)

    def follow(mode=None, trainer=ctx.config["trainer"]):
        return reference.train_steps(lambda: make_state(ctx.seed), ids[:n],
                                     ctx.config, trainer, mode=mode,
                                     keep=keep)

    return control_sides(ctx, got, follow, controls, faults)


def run(ctx):
    sut = build(ctx)
    got = first_steps(ctx, sut)
    B, S = ctx.cell["batch_size"], ctx.traffic["seq_len"]
    n_check = _check_steps(ctx)

    # -- the window: the same object, the same call
    losses, n, traced = [], 0, None
    trace_after = 4                    # steady steps before the trace
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
        if elapsed >= ctx.seconds:
            break
    peak = harness.peak_bytes(ctx.devices[:ctx.chips])
    window_losses = [float(x) for x in losses]
    finite = bool(np.isfinite(window_losses).all())
    tokens_per_s_chip = ((n - traced_n) * B * S / (elapsed - traced_s)
                         / ctx.chips)
    print(f"train: steps={n} window_s={elapsed:.4f} "
          f"step_s={elapsed / n:.5f} first_losses={got['losses']} "
          f"window_loss_first={window_losses[0]:.4f} "
          f"last={window_losses[-1]:.4f} peak_bytes={peak}", flush=True)

    # -- the check: free the program, then follow the same steps plainly
    make_state, shardings, ids = (sut["make_state"], sut["shardings"],
                                  sut["ids"])
    lower_s = sut["lower_s"]
    sut.clear()
    harness.release()
    t1 = time.perf_counter()
    ref = reference.train_steps(
        lambda: make_state(ctx.seed),
        ids[:n_check], ctx.config, ctx.config["trainer"],
        keep=_spread(shardings))
    rows = compare(ctx, got, ref)
    rows.append(harness.compared("window_losses_not_finite",
                                 0 if finite else 1, 0, ""))
    print(f"check: reference followed {n_check} steps in "
          f"{time.perf_counter() - t1:.2f} s", flush=True)

    flops_tok = costs.train_flops_per_token(ctx.config, S)
    run_data = {
        "kind": "train", "trace": traced, "chips": ctx.chips,
        "tokens_per_s_chip": tokens_per_s_chip, "lower_s": lower_s,
        "flops_per_token": flops_tok, "peaks": ctx.peaks,
        "config": ctx.config, "batch_size": B, "seq_len": S,
        "steps_traced": ctx.traffic["trace_steps"],
    }
    return {
        "end_to_end": {"train_tokens_per_s_chip": tokens_per_s_chip,
                       "setup_s": setup_s},
        "attempted": n, "failed": 0 if finite else n,
        "compared": rows, "peak_bytes": peak, "run": run_data,
    }
