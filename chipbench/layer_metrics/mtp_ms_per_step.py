"""Device milliseconds a step spends in the multi-token-prediction module,
per device: self time of every operation whose op_name holds `mtp/embed`,
`mtp/proj`, `mtp/block`, `mtp/head` or `mtp/loss`, and of every operation
that runs INSIDE one that does (the block's head-group scan is a `while`
under the module's name; its body is lowered once for every layer of the
model, so the up-projections, the core's kernels and the output projection
in it carry no layer's name and are the module's by lying inside that
loop), forward, backward and recomputed. No components table: the
first-match rule would have to put the module's rows before the inner
names, and would still miss the loop's body. A Mosaic call that carries no
op_name and lies in no such loop (the module's grouped products and swiglu
kernels, where the profiler keeps no name for them) cannot be told from
the trunk's and is left out: the note says how much device time such calls
took in all. The note adds the last step's two losses (`main_loss`,
`mtp_loss`: buffers the compiled step writes beside the one it returns)."""
LAYER = "kernels"
UNIT = "ms"
MOVES = "train_tokens_per_s_chip"
SCOPES = ("mtp/embed", "mtp/proj", "mtp/block", "mtp/head", "mtp/loss")


def _scope_of(op_name):
    from chipbench import scope_reduce
    toks = scope_reduce.tokens(op_name)
    return next((s for s in SCOPES if scope_reduce._holds(toks, s)), None)


def module_self_ns(events):
    """({scope: self ns}, self ns of unnamed operations outside the module)
    of the nested events [[instruction, start, duration, op_name]] of one
    device line: an event is the module's under the scope its own op_name
    holds, or else under the scope of the nearest enclosing event that is
    the module's."""
    by, stray, stack = {}, 0, []       # stack of [end, self_ns, scope, named]

    def close(upto):
        nonlocal stray
        while stack and stack[-1][0] <= upto:
            _, self_ns, scope, named = stack.pop()
            if scope is not None:
                by[scope] = by.get(scope, 0) + self_ns
            elif not named:
                stray += self_ns

    for _, s, d, op in sorted(events, key=lambda e: (e[1], -e[2])):
        close(s)
        if stack:
            stack[-1][1] -= min(d, stack[-1][0] - s)
        scope = _scope_of(op) or (stack[-1][2] if stack else None)
        stack.append([s + d, d, scope, bool(op)])
    close(float("inf"))
    return by, stray


def compute(run):
    from chipbench import scope_reduce, trace_reduce
    if (run.get("config") or {}).get("model_type") != "glm4_moe_lite":
        return None
    red = scope_reduce.of_run(run)
    if not red or not red["has_op_names"]:
        return None
    tr = run["trace"]
    if "scope_loaded" not in tr:
        tr["scope_loaded"] = scope_reduce.load(tr["dir"])
    trace = tr["scope_loaded"]
    outer = [s for s in trace["spans"] if s[0] in trace_reduce.SPAN_NAMES]
    w0, w1 = outer[0][1], max(s + d for _, s, d in outer)
    total, stray = {}, 0
    for events in trace["device"].values():
        by, unnamed = module_self_ns(
            [(n, max(s, w0), min(s + d, w1) - max(s, w0), op)
             for n, s, d, op in events if s < w1 and s + d > w0])
        stray += unnamed
        for k, v in by.items():
            total[k] = total.get(k, 0) + v
    per = 1e-6 / len(trace["device"]) / run["steps_traced"]   # ns -> ms a step
    value = sum(total.values()) * per
    if value <= 0:
        return None
    c = run.get("counters") or {}
    return value, (
        "per device per step: " + " ".join(
            f"{k}={total.get(k, 0) * per:.3f}" for k in SCOPES)
        + f" | unnamed operations in no loop of the module: "
        f"{stray * per:.3f} (not counted) | last step: "
        f"main_loss={c.get('main_loss')} mtp_loss={c.get('mtp_loss')}")
