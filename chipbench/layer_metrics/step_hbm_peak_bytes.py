"""Bytes a device holds at the compiled step's fullest moment, by the
compiler's own count of the executable the run compiled
(`train_step.memory`, left by `TrainStep.lower().compile()`): the
arguments and what the program has live at its peak, the number the
compiler's refusal prints as "used X of Y". Bytes, not a share of the
chip: a count that turns out too high is a finding. The note gives the
five terms of `memory_analysis()` and their sum (which over-counts:
`temp_bytes` is a region's size, not what is live at once), the device's
limit and what is left under it, and the runtime's `peak_bytes_in_use`,
which is the process's and not the step's."""
LAYER = "compiled step"
UNIT = "bytes"
MOVES = "train_tokens_per_s_chip"


def compute(run):
    from chipbench import step_memory
    if run.get("kind") != "train":
        return None
    mem = step_memory.memory()
    if mem is None:
        return None
    limit = mem.get("bytes_limit")
    note = (" ".join(f"{k}={mem[k]}" for k in (
        "argument_bytes", "output_bytes", "alias_bytes", "temp_bytes",
        "generated_code_bytes", "sum_bytes", "bytes_limit", "devices")
        if k in mem)
        + (f" headroom={limit - mem['peak_bytes']}" if limit else "")
        + f" read_s={mem['dur_s']:.4f}"
        + f" | runtime peak_bytes_in_use="
          f"{step_memory.runtime_peak(run['chips'])}")
    return mem["peak_bytes"], note
