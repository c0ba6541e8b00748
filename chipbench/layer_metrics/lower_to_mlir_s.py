"""Host seconds jax took to turn the step's jaxpr into an MLIR module
inside its first `TrainStep.lower()`: jax.monitoring's
jaxpr_to_mlir_module_duration for the step itself, kept by the program
as the set-up event `train_step.to_mlir`."""
LAYER = "compiled step"
UNIT = "s"
MOVES = "setup_s"


def compute(run):
    from chipbench import scope_reduce
    if run.get("kind") != "train":
        return None
    ph = scope_reduce.setup_phases()
    if ph is None or "to_mlir" not in ph:
        return None
    return ph["to_mlir"], scope_reduce.setup_note(ph, run)
