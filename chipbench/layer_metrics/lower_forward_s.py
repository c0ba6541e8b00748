"""Host seconds of Python tracing the step's forward pass inside its first
`TrainStep.lower()`: the set-up span `train_step.forward`, less the
eager programs that compiled while it was open."""
LAYER = "compiled step"
UNIT = "s"
MOVES = "setup_s"


def compute(run):
    from chipbench import scope_reduce
    if run.get("kind") != "train":
        return None
    ph = scope_reduce.setup_phases()
    if ph is None or "forward" not in ph:
        return None
    return ph["forward"], scope_reduce.setup_note(ph, run)
