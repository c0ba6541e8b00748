"""Times the step's body was traced again after its first execution:
the program counts traces where only tracing runs (`pure()` in
jit.TrainStep) and keeps the count with its set-up events. A retrace
in the middle of a run is a recompile inside the window: it must be 0."""
LAYER = "compiled step"
UNIT = "count"
MOVES = "train_tokens_per_s_chip"


def compute(run):
    from chipbench import scope_reduce
    if run.get("kind") != "train":
        return None
    ph = scope_reduce.setup_phases()
    if ph is None:
        return None
    return ph["retraces"], f"traces in all: {ph['traces']}"
