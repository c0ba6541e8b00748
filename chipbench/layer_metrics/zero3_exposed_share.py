"""Share of the traced window a device spends, with no compute on its ops
line, in the collectives ZeRO-3's partitioning put in: parameter
all-gathers (under the scope of the operation that consumes the
parameter) and gradient reduce-scatters / all-reduces of the backward
pass outside `tp/*` (components.json's `collectives` rows). The note
gives every class and the remainder (`other`)."""
LAYER = "sharding"
UNIT = "%"
MOVES = "train_tokens_per_s_chip"


def compute(run):
    from chipbench import scope_reduce
    return scope_reduce.exposed_share(run, "zero3")
