"""Share of its roofline the ragged paged attention kernel reaches in
serving: the least time the chip could take for the attention of the
traced ticks (costs.ragged_attention_call over each tick's sequences,
once a layer) over the device time of the kernel's events in the trace.
Says which bound."""
LAYER = "kernels"
UNIT = "%"
MOVES = "tpot_p95_ms"
MARKS = ("ragged_paged_attention", "ragged", "paged_attention")


def compute(run):
    tr = run.get("trace") or {}
    red = tr.get("reduced")
    if run["kind"] != "serve" or not red or not run.get("peaks") \
            or not tr.get("ticks"):
        return None
    from chipbench import costs
    spent = sum(s for name, s in red["op_self_s"].items()
                if any(m in name.lower() for m in MARKS))
    if spent <= 0:
        return None
    cfg = run["config"]
    flops = byts = 0
    for seqs in tr["ticks"]:
        f, b = costs.ragged_attention_call(cfg, seqs)
        flops, byts = flops + f, byts + b
    layers = cfg["num_hidden_layers"]
    least, bound = costs.roofline_s(flops * layers, byts * layers,
                                    run["peaks"])
    return 100.0 * least / spent, (
        f"bound={bound} least_s={least:.6f} kernel_s={spent:.6f} over "
        f"{len(tr['ticks'])} ticks")
