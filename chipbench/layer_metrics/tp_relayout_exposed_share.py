"""Share of the traced window a device spends relaying out the gate|up
weight for the swiglu kernel and its gradient back: collectives and
copies under the scope `tp/relayout`."""
LAYER = "sharding"
UNIT = "%"
MOVES = "train_tokens_per_s_chip"


def compute(run):
    from chipbench import scope_reduce
    return scope_reduce.exposed_share(run, "tp_relayout")
