"""The compiled step's temporaries on a device (`train_step.memory`'s
`temp_bytes`, the compiler's size of the region that holds gradients,
what the forward keeps for the backward and kernel scratch): the number
a PR that keeps a value instead of recomputing it moves. It is a
region's size and over-counts what is live at once; the note gives the
program's bytes at the peak (`peak_bytes - argument_bytes`) beside it."""
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
    return mem["temp_bytes"], (
        f"program bytes at the peak="
        f"{mem['peak_bytes'] - mem['argument_bytes']} of "
        f"peak_bytes={mem['peak_bytes']} on {mem['devices']} device(s)")
