"""Driver of the `serve` traffic kind: one `ContinuousBatchingEngine` on
one chip under an open loop at the cell's fixed rate (the engine set-up
is a copy of what `chip_smoke.py`'s `serve_phase` proved in PR 22).

Each request is timed from when it was DUE, not from `add_request`. A
ramp fills the slots before the window; arrivals go on through a drain
after it, so requests due late in the window finish under the same load.
A request due in the window and unfinished after the drain is `failed`.
Once the window and the drain have closed and the engine is freed, a
seeded sample of the requests the window finished (the longest among
them) is teacher-forced through `reference.logits_at`: the widest gap by
which a served token's logit lies under the reference's best is the
number `correct` rests on.
"""
from __future__ import annotations

import time

import numpy as np

from chipbench import harness, reference, traffic, weights


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


def build(ctx, **engine_overrides):
    """Model from the seed and the engine around it, warmed: the one
    ragged step compiled, a few requests served and drained."""
    import paddle_tpu as paddle
    from paddle_tpu.inference import (ContinuousBatchingEngine,
                                      GenerationRequest)

    cfg_json = ctx.config
    cfg = weights.model_config(cfg_json)
    model, shapes = weights.skeleton(cfg)
    state = weights.generator(shapes)(ctx.seed)
    weights.install(model, state)
    model.eval()
    paddle.seed(ctx.seed % (2 ** 31 - 1))
    eng_cfg = {k: v for k, v in cfg_json["engine"].items()
               if not k.endswith("_how")}
    eng_cfg.update(engine_overrides)
    eng = ContinuousBatchingEngine(model, greedy=ctx.traffic["greedy"],
                                   seed=ctx.seed % (2 ** 31 - 1), **eng_cfg)
    prog = None
    if eng._ragged:
        prog = eng._compiled_ragged = _FirstCall(eng._ragged_fn())
    rng = np.random.default_rng([ctx.seed, 0xA11])
    t0 = time.perf_counter()
    for n in (40, 300, 9, 130):
        eng.add_request(GenerationRequest(
            [int(t) for t in rng.integers(1, cfg.vocab_size,
                                          min(n, eng.S // 2))],
            max_new_tokens=6))
    while eng.has_work:
        eng.step()
    warm_s = time.perf_counter() - t0
    n_kernels = None
    if ctx.on_chip and prog is not None:
        n_kernels = harness.require_kernels(
            prog.compiled_text(), cfg_json["kernels"], ctx.workload)
    print(f"serve: depth={cfg.num_hidden_layers} slots={eng.B} "
          f"packed_rows={eng._T_pack} pages={eng.pool.n_pages} "
          f"kv_pool_bytes={eng.kv_cache_bytes} ragged={eng._ragged} "
          f"speculative={eng._spec} warm_s={warm_s:.3f} "
          f"kernels={n_kernels}", flush=True)
    return {"engine": eng, "state": state, "cfg": cfg,
            "Request": GenerationRequest}


def drive(ctx, sut, rate, window_s):
    """The open loop: ramp, window, drain. Returns the readings."""
    import jax
    eng, Request = sut["engine"], sut["Request"]
    mix = ctx.traffic
    ramp, drain = float(mix["ramp_s"]), float(mix["drain_s"])
    plan = traffic.serve_requests(mix, rate, ramp + window_s + drain,
                                  sut["cfg"].vocab_size, ctx.seed)
    reqs = [Request([int(t) for t in toks], max_new_tokens=n_out)
            for _, toks, n_out in plan]
    due = [d for d, _, _ in plan]
    in_window = [ramp <= d < ramp + window_s for d in due]
    added, lags, ticks = 0, [], []
    traced, tracing = None, None
    trace_at = ramp + min(5.0, window_s / 3)
    marks = {}                    # "start"/"end" -> (clock, tokens so far)
    queue_mid = None

    def produced():
        return sum(len(r.output) for r in reqs[:added])

    t0 = time.perf_counter()
    while True:
        now = time.perf_counter() - t0
        if "start" not in marks and now >= ramp:
            marks["start"] = (now, produced())
        if queue_mid is None and now >= ramp + window_s / 2:
            queue_mid = len(eng.waiting)
        if "end" not in marks and now >= ramp + window_s:
            marks["end"] = (now, produced())
            marks["queue_end"] = len(eng.waiting)
        if ctx.trace and traced is None and now >= trace_at:
            tracing = harness.device_trace(ctx)
            traced = tracing.__enter__()
            traced["ticks"] = []
        if tracing is not None and now >= trace_at + mix["trace_s"]:
            tracing.__exit__(None, None, None)
            tracing = None
        while added < len(reqs) and due[added] <= now:
            with jax.profiler.TraceAnnotation("add_request"):
                eng.add_request(reqs[added])
            lags.append(time.perf_counter() - t0 - due[added])
            added += 1
        if "end" in marks:
            if now >= ramp + window_s + drain or all(
                    r.done for r, w in zip(reqs, in_window) if w):
                break
        if eng.has_work:
            before = None
            if tracing is not None:
                before = [(s.req, s.length) for s in eng.slots]
            t1 = time.perf_counter()
            with jax.profiler.TraceAnnotation("engine.step"):
                eng.step()
            ticks.append(time.perf_counter() - t1)
            if before is not None:
                traced["ticks"].append(
                    [(s.length - n0, s.length)
                     for s, (r0, n0) in zip(eng.slots, before)
                     if s.req is not None and s.req is r0
                     and s.length > n0])
        elif added < len(reqs):
            time.sleep(max(0.0, min(due[added] - now, 0.05)))
        else:
            break
    if tracing is not None:
        tracing.__exit__(None, None, None)
    t_end = time.perf_counter() - t0
    marks.setdefault("end", (t_end, produced()))
    return {"reqs": reqs, "due": due, "in_window": in_window, "t0": t0,
            "t_end": t_end, "marks": marks, "lags": lags, "ticks": ticks,
            "trace": traced, "queue_mid": queue_mid,
            "queue_end": marks.get("queue_end", len(eng.waiting)),
            "added": added}


def readings(d):
    """End-to-end numbers of one driven window."""
    t0 = d["t0"]
    ttft, tpot, failed, done = [], [], 0, []
    for r, due, w in zip(d["reqs"], d["due"], d["in_window"]):
        if not w:
            continue
        if not r.done or r.status != "served":
            failed += 1
            continue
        done.append(r)
        ttft.append((r.first_token_s - t0 - due) * 1e3)
        if len(r.output) > 1:
            tpot.append((r.finished_s - r.first_token_s)
                        / (len(r.output) - 1) * 1e3)
    # a failed request counts as the worst seen
    ttft += [max(ttft, default=d["t_end"] * 1e3)] * failed
    tpot += [max(tpot, default=d["t_end"] * 1e3)] * failed
    (s_t, s_n), (e_t, e_n) = d["marks"]["start"], d["marks"]["end"]
    return {"ttft_ms": ttft, "tpot_ms": tpot, "failed": failed,
            "attempted": sum(d["in_window"]), "finished": done,
            "tokens_per_s": (e_n - s_n) / (e_t - s_t),
            "window_s": e_t - s_t}


def sample_for_check(finished, k, seed):
    """k of the window's finished requests, drawn from the seed, and the
    longest of all."""
    if not finished:
        return []
    rng = np.random.default_rng([int(seed), 0xC4EC])
    longest = max(range(len(finished)), key=lambda i: len(
        finished[i].prompt) + len(finished[i].output))
    rest = [i for i in range(len(finished)) if i != longest]
    pick = list(rng.permutation(rest)[:k]) + [longest]
    return [finished[i] for i in pick]


def served_gaps(state, cfg_json, sample, mode=None):
    """For each sampled request the reference's logits at every position
    that produced a served token. Returns (widest gap of a served token
    under the reference's best logit, widest gap of the token a `mode`
    control would have put first, tokens compared)."""
    widest, widest_ctrl, n_tok = 0.0, 0.0, 0
    for r in sample:
        toks = list(r.prompt) + list(r.output)
        rows = np.arange(len(r.prompt) - 1, len(toks) - 1)
        lg = np.asarray(reference.logits_at(state, cfg_json, toks, rows))
        best = lg.max(axis=-1)
        gap = best - lg[np.arange(len(rows)), np.asarray(r.output)]
        widest = max(widest, float(gap.max()))
        n_tok += len(rows)
        if mode is not None:
            lo = np.asarray(reference.logits_at(state, cfg_json, toks, rows,
                                                mode=mode))
            first = lo.argmax(axis=-1)
            widest_ctrl = max(widest_ctrl, float(
                (best - lg[np.arange(len(rows)), first]).max()))
    return widest, widest_ctrl, n_tok


def control(ctx):
    """Sound and control readings of one seed, for setting the limit: a
    short window at the cell's own load, then the served tokens' widest
    gap beside the widest gap of the token each lower precision would
    have put first at the same positions."""
    sut = build(ctx)
    d = drive(ctx, sut, float(ctx.cell["rate_rps"]), ctx.seconds)
    r = readings(d)
    sample = sample_for_check(r["finished"], ctx.traffic["check_requests"],
                              ctx.seed)
    state = sut["state"]
    sut.clear()
    harness.release()
    lim = ctx.cell["correct"]["limits"]["served_logit_gap"]["limit"]
    out = {}
    for mode in ctx.cell["correct"]["controls"]:
        gap, low, n_tok = served_gaps(state, ctx.config, sample, mode=mode)
        out["sound"] = [harness.compared("served_logit_gap", gap, lim,
                                         f"{n_tok} tokens")]
        out[mode] = [harness.compared("served_logit_gap", low, lim,
                                      f"{n_tok} tokens")]
    return out


def sweep(ctx, rates):
    """Rows for `traffic.knee`: one engine, each rate driven for
    ctx.seconds after the last one's requests have drained."""
    sut = build(ctx)
    eng, rows = sut["engine"], []
    for rate in rates:
        d = drive(ctx, sut, rate, ctx.seconds)
        r = readings(d)
        arrived = r["attempted"]
        rows.append({
            "rate": rate, "arrived": arrived,
            "completed": arrived - r["failed"],
            "queue_mid": d["queue_mid"], "queue_end": d["queue_end"],
            "tokens_per_s": r["tokens_per_s"],
            "ttft_p95_ms": harness.percentile(r["ttft_ms"], 95),
            "tpot_p95_ms": harness.percentile(r["tpot_ms"], 95),
            "tick_ms": harness.percentile(d["ticks"], 50) * 1e3,
            "preemptions": eng.preemptions})
        print("sweep: " + str(rows[-1]), flush=True)
        t0 = time.perf_counter()
        while eng.has_work and time.perf_counter() - t0 < 60:
            eng.step()
    return rows


def run(ctx):
    sut = build(ctx)
    eng = sut["engine"]
    rate = float(ctx.cell["rate_rps"])
    setup_s = time.perf_counter() - ctx.t_start + float(
        ctx.traffic["ramp_s"])           # the ramp warms the slots: set-up
    d = drive(ctx, sut, rate, ctx.seconds)
    r = readings(d)
    peak = harness.peak_bytes(ctx.devices[:1])
    lag95 = harness.percentile(d["lags"], 95) * 1e3
    print(f"serve: rate={rate} arrived={d['added']} in_window="
          f"{r['attempted']} failed={r['failed']} window_s="
          f"{r['window_s']:.4f} ticks={len(d['ticks'])} "
          f"tick_ms_median={harness.percentile(d['ticks'], 50) * 1e3:.3f} "
          f"arrival_lag_p95_ms={lag95:.3f} queue_mid={d['queue_mid']} "
          f"queue_end={d['queue_end']} preemptions={eng.preemptions} "
          f"prefill_tokens={eng.prefill_tokens_total} "
          f"spec_drafted={eng.spec_drafted} free_pages={eng.pool.n_free} "
          f"ran_s={d['t_end']:.2f} peak_bytes={peak}", flush=True)

    ledgers = [dict(q.trace.buckets) for q in r["finished"]
               if getattr(q, "trace", None) is not None]
    sample = sample_for_check(r["finished"], ctx.traffic["check_requests"],
                              ctx.seed)
    state, cfg_json = sut["state"], ctx.config
    n_pages, n_free = eng.pool.n_pages, eng.pool.n_free
    sut.clear()
    del eng
    harness.release()
    t1 = time.perf_counter()
    gap, _, n_tok = served_gaps(state, cfg_json, sample)
    print(f"check: reference forward over {len(sample)} finished requests, "
          f"{n_tok} served tokens, in {time.perf_counter() - t1:.2f} s",
          flush=True)
    lim = ctx.cell["correct"]["limits"]
    rows = [
        harness.compared("served_logit_gap",
                         gap if sample else float("nan"),
                         lim["served_logit_gap"]["limit"],
                         f"over {n_tok} tokens of {len(sample)} requests"),
    ]
    ttft95 = harness.percentile(r["ttft_ms"], 95) if r["ttft_ms"] else None
    tpot95 = harness.percentile(r["tpot_ms"], 95) if r["tpot_ms"] else None
    e2e = {"serve_tokens_per_s": r["tokens_per_s"], "setup_s": setup_s}
    if ttft95 is not None:
        e2e["ttft_p95_ms"] = ttft95
    if tpot95 is not None:
        e2e["tpot_p95_ms"] = tpot95
    run_data = {
        "kind": "serve", "trace": d["trace"], "chips": 1,
        "tick_s": d["ticks"], "arrival_lag_s": d["lags"],
        "ledgers": ledgers, "peaks": ctx.peaks, "config": cfg_json,
        "pool": {"pages": n_pages, "free_at_end": n_free},
    }
    return {"end_to_end": e2e, "attempted": r["attempted"],
            "failed": r["failed"], "compared": rows, "peak_bytes": peak,
            "run": run_data}
