"""Device milliseconds a step spends in attention, per device: self time
of every operation whose op_name resolves to `attn/qkv`, `attn/rope`,
`attn/core` or `attn/out` (kernels and projections, forward, backward
and recomputed; collectives are the sharding layer's and not counted)."""
LAYER = "kernels"
UNIT = "ms"
MOVES = "train_tokens_per_s_chip"


def compute(run):
    from chipbench import scope_reduce
    return scope_reduce.ms_per_step(run, "attention")
