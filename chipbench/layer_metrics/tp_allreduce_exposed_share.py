"""Share of the traced window a device spends in tensor-parallel
all-reduces with no compute on its ops line: all-reduces under the scope
`tp/all_reduce` (shard_kernel's `psum`, and the all-reduces the
partitioner hangs on row- and column-parallel matmuls)."""
LAYER = "sharding"
UNIT = "%"
MOVES = "train_tokens_per_s_chip"


def compute(run):
    from chipbench import scope_reduce
    return scope_reduce.exposed_share(run, "tp_all_reduce")
