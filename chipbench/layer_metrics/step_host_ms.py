"""Host milliseconds a step spends outside the dispatch of the compiled
program: the spans `train_step.call_args` and `train_step.write_back`
that `TrainStep.__call__` puts on the profiler's host plane, mean per
traced step. The note names the device's idle gaps by the innermost
span that covers each (the program's three and chipbench's own)."""
LAYER = "compiled step"
UNIT = "ms"
MOVES = "train_tokens_per_s_chip"


def compute(run):
    from chipbench import scope_reduce
    red = scope_reduce.of_run(run)
    if not red or not red["host_span_s"]:
        return None
    host, steps = red["host_span_s"], run["steps_traced"]
    mean = {k: sum(v) * 1e3 / steps for k, v in host.items()}
    value = (mean.get("train_step.call_args", 0.0)
             + mean.get("train_step.write_back", 0.0))
    return value, (
        " ".join(f"{k}={v:.3f}" for k, v in sorted(mean.items()))
        + f" spans={ {k: len(v) for k, v in host.items()} }"
        + " | trace: idle by program span "
        + str([[k, round(v, 6)] for k, v in red["idle_by_span_s"]]))
