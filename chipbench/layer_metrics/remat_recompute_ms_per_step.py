"""Device milliseconds a step spends recomputing forward work in the
backward pass, per device: every operation jax marks
`rematted_computation`, any component (a forward Mosaic kernel inside
the backward `while` counts too)."""
LAYER = "compiled step"
UNIT = "ms"
MOVES = "train_tokens_per_s_chip"


def compute(run):
    from chipbench import scope_reduce
    red = scope_reduce.of_run(run)
    if not red or not red["has_op_names"]:
        return None
    by = {}
    for (component, direction), s in red["component_s"].items():
        if direction == "recomputed":
            by[component] = s * 1e3 / run["steps_traced"]
    if not by:
        return None
    return sum(by.values()), "by component: " + " ".join(
        f"{c}={v:.3f}" for c, v in sorted(by.items(), key=lambda kv: -kv[1]))
