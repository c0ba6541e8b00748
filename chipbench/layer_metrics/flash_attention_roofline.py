"""Share of its roofline the flash-attention kernels reach in training:
the least time the chip could take for the attention the traced steps
REQUIRE (costs.flash_attention_train: forward + backward once a layer a
step, no recomputation) over the device time of every flash-attention
kernel event in the trace. Says which bound."""
LAYER = "kernels"
UNIT = "%"
MOVES = "train_tokens_per_s_chip"
MARKS = ("flash_attention", "splash", "flash")


def compute(run):
    red = (run.get("trace") or {}).get("reduced")
    if run["kind"] != "train" or not red or not run.get("peaks"):
        return None
    from chipbench import costs
    spent = sum(s for name, s in red["op_self_s"].items()
                if any(m in name.lower() for m in MARKS))
    if spent <= 0:
        return None
    cfg = run["config"]
    # a sharded step splits heads and batch over the chips: each device
    # does 1/chips of a layer's attention; op_self_s sums the devices
    flops, byts = costs.flash_attention_train(
        cfg, run["batch_size"], run["seq_len"])
    calls = cfg["num_hidden_layers"] * run["steps_traced"]
    least, bound = costs.roofline_s(flops * calls, byts * calls,
                                    run["peaks"])
    return 100.0 * least / spent, (
        f"bound={bound} least_s={least:.6f} kernel_s={spent:.6f} "
        f"over {run['steps_traced']} steps")
