"""Host seconds of backend compiles that fired INSIDE the step's first
`TrainStep.lower()`: small eager programs (constants, tables, masks
computed on the device while the step traces), not the step; each is
under the persistent cache's floor and compiles again in every process.
The note counts them and gives every other part of `train_step.lower`,
the uncovered rest included."""
LAYER = "compiled step"
UNIT = "s"
MOVES = "setup_s"


def compute(run):
    from chipbench import scope_reduce
    if run.get("kind") != "train":
        return None
    ph = scope_reduce.setup_phases()
    if ph is None or "inner_compile" not in ph:
        return None
    return ph["inner_compile"], scope_reduce.setup_note(ph, run)
