"""Device milliseconds a step spends in the gated MLP, per device:
component `mlp` (the swiglu kernels and the down projection, forward,
backward and recomputed)."""
LAYER = "kernels"
UNIT = "ms"
MOVES = "train_tokens_per_s_chip"


def compute(run):
    from chipbench import scope_reduce
    return scope_reduce.ms_per_step(run, "mlp")
