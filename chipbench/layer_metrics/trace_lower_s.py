"""Host seconds of `TrainStep.lower()`: Python tracing through the tape,
which no compile cache shortens."""
LAYER = "compiled step"
UNIT = "s"
MOVES = "setup_s"


def compute(run):
    return run.get("lower_s")
