"""Device milliseconds a step spends in the optimizer's update, per
device: phase `optimizer`, and `grad_sync`'s own compute where a plan
has one (its collectives are the sharding layer's)."""
LAYER = "compiled step"
UNIT = "ms"
MOVES = "train_tokens_per_s_chip"


def compute(run):
    from chipbench import scope_reduce
    return scope_reduce.ms_per_step(run, "optimizer")
