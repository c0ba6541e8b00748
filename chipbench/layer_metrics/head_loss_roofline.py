"""Share of its roofline the output head and the cross-entropy reach in
training: the least time the chip could take for the work the traced
steps REQUIRE (costs_components.head_loss_train) over all device time of
components `head` and `loss`. Says which bound."""
LAYER = "kernels"
UNIT = "%"
MOVES = "train_tokens_per_s_chip"


def compute(run):
    from chipbench import costs, costs_components, scope_reduce
    red = scope_reduce.of_run(run)
    if not red or not red["has_op_names"] or not run.get("peaks"):
        return None
    spent = scope_reduce.group_s(red, "head_loss")     # per device, all directions
    if spent <= 0:
        return None
    flops, byts = costs_components.head_loss_train(
        run["config"], run["batch_size"], run["seq_len"])
    # a sharded step splits the work over the chips; `spent` is one
    # device's share of the time
    calls = 1 * run["steps_traced"] / run["chips"]
    least, bound = costs.roofline_s(flops * calls, byts * calls,
                                    run["peaks"])
    return 100.0 * least / spent, (
        f"bound={bound} least_s={least:.6f} device_s={spent:.6f} per device "
        f"over {run['steps_traced']} steps (recomputation in the time, not "
        f"in the work)")
