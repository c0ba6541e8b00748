"""Share of the device's busy time in the traced window whose operation
resolves, through components.json, to a component the program names
(or, for a collective, to a class). What is left is printed by
instruction name. A trace in which no operation carries an op_name
reads 0: kernels told by their instruction names alone are not the
program's naming."""
LAYER = "compiled step"
UNIT = "%"
MOVES = "train_tokens_per_s_chip"


def compute(run):
    from chipbench import scope_reduce
    red = scope_reduce.of_run(run)
    if not red or red["busy_s"] <= 0:
        return None
    if not red["has_op_names"]:
        return 0.0, "no operation in the trace carries an op_name"
    steps = run["steps_traced"]
    groups = {g: scope_reduce.group_s(red, g) * 1e3 / steps
              for g in ("attention", "mlp", "head_loss", "optimizer")}
    grouped = {c for g in scope_reduce.rules()["groups"].values() for c in g}
    other = {}
    for (c, _), s in red["component_s"].items():
        if c not in grouped:
            other[c] = other.get(c, 0.0) + s * 1e3 / steps
    coll = sum(red["collective_s"].values()) * 1e3 / steps
    rest = red["unnamed_total_s"] * 1e3 / steps
    note = (f"busy_ms_per_step={red['busy_s'] * 1e3 / steps:.3f} = "
            + " + ".join(f"{g} {v:.3f}" for g, v in groups.items())
            + " + " + " + ".join(f"{c} {v:.3f}" for c, v in sorted(other.items()))
            + f" + collectives {coll:.3f} + unnamed {rest:.3f}"
            + " | unnamed: " + " ".join(
                f"{n}={s * 1e3 / steps:.3f}" for n, s in red["unnamed_s"]))
    return 100.0 * red["named_s"] / red["busy_s"], note
