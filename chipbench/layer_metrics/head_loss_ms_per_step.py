"""Device milliseconds a step spends in the output head and the
cross-entropy, per device: components `head` and `loss`."""
LAYER = "kernels"
UNIT = "ms"
MOVES = "train_tokens_per_s_chip"


def compute(run):
    from chipbench import scope_reduce
    return scope_reduce.ms_per_step(run, "head_loss")
