"""Device milliseconds a step spends in the Mamba-2 state-space layers'
mixers, per device: self time of every operation whose op_name resolves
to `ssm/proj`, `ssm/conv`, `ssm/dt`, `ssm/core`, `ssm/norm` or `ssm/out`
(forward, backward and recomputed), read through the group `ssm` of the
run's own `components_<model_type>.json` where it has one, else of
`components_granitemoehybrid.json`."""
LAYER = "kernels"
UNIT = "ms"
MOVES = "train_tokens_per_s_chip"
FIRST = "components_granitemoehybrid.json"


def compute(run):
    from chipbench import scope_tables
    return scope_tables.ms_per_step(
        run, scope_tables.table_of(run, "ssm", FIRST), "ssm")
