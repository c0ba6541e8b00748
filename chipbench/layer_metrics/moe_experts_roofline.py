"""Share of its roofline the routed experts' grouped products reach in
training: the least time the chip could take for 18 x pairs x H x M
operations and the bytes of `moe_experts_train` (the run's own
`costs_<model_type>.py` where it has the function, else
costs_solar_open2's), with `pairs` the (token, expert) pairs the compiled
step COUNTED as computed here (its `expert_tokens` buffers, a layer at a
time), over ALL device time of component `moe/experts`, recomputation
included."""
LAYER = "kernels"
UNIT = "%"
MOVES = "train_tokens_per_s_chip"
FIRST = "solar_open2"


def compute(run):
    from chipbench import scope_tables
    counters = run.get("counters")
    if not counters or not counters.get("expert_tokens"):
        return None
    cs = scope_tables.costs_of(run, "moe_experts_train", "costs_" + FIRST)
    flops = byts = 0
    for layer in counters["expert_tokens"]:
        f, b = cs.moe_experts_train(run["config"], sum(layer))
        flops, byts = flops + f, byts + b
    steps = run["steps_traced"]
    return scope_tables.roofline(
        run, scope_tables.table_of(run, "moe_experts",
                                   f"components_{FIRST}.json"),
        "moe_experts", flops * steps, byts * steps,
        f"pairs a layer in the last step "
        f"{[sum(l) for l in counters['expert_tokens']]}")
