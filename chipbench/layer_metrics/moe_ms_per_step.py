"""Device milliseconds a step spends in the expert layers, per device:
self time of every operation whose op_name resolves to `moe/router`,
`moe/dispatch`, `moe/experts`, `moe/shared` or `moe/combine` (forward,
backward and recomputed), read through the group `moe` of the run's own
`components_<model_type>.json` where it has one, else of
`components_solar_open2.json`."""
LAYER = "kernels"
UNIT = "ms"
MOVES = "train_tokens_per_s_chip"
FIRST = "components_solar_open2.json"


def compute(run):
    from chipbench import scope_tables
    return scope_tables.ms_per_step(
        run, scope_tables.table_of(run, "moe", FIRST), "moe")
