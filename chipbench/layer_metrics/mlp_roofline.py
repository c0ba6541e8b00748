"""Share of its roofline the gated MLP reaches in training: the least
time the chip could take for the MLP the traced steps REQUIRE
(costs_components.mlp_train: forward + backward once a layer a step)
over ALL device time of component `mlp`, recomputation included. Says
which bound."""
LAYER = "kernels"
UNIT = "%"
MOVES = "train_tokens_per_s_chip"


def compute(run):
    from chipbench import costs, costs_components, scope_reduce
    red = scope_reduce.of_run(run)
    if not red or not red["has_op_names"] or not run.get("peaks"):
        return None
    spent = scope_reduce.group_s(red, "mlp")     # per device, all directions
    if spent <= 0:
        return None
    flops, byts = costs_components.mlp_train(
        run["config"], run["batch_size"], run["seq_len"])
    # a sharded step splits the work over the chips; `spent` is one
    # device's share of the time
    calls = run["config"]["num_hidden_layers"] * run["steps_traced"] / run["chips"]
    least, bound = costs.roofline_s(flops * calls, byts * calls,
                                    run["peaks"])
    return 100.0 * least / spent, (
        f"bound={bound} least_s={least:.6f} device_s={spent:.6f} per device "
        f"over {run['steps_traced']} steps (recomputation in the time, not "
        f"in the work)")
