"""Share of its roofline the full layers' selected core reaches in
training: the least time the chip could take for attention over the
SELECTED pairs (costs_dots3_note.dsa_core_train: forward + backward once
a full layer a step) over ALL device time of component
`attn/core/selected`. A dense-masked core visits every causal tile, so
the share reads low by the ratio of selected to causal pairs: that is
the yardstick, not a fault of the reader. Says which bound."""
LAYER = "kernels"
UNIT = "%"
MOVES = "train_tokens_per_s_chip"


def compute(run):
    from chipbench import costs_dots3_note as cd
    from chipbench import scope_tables
    cfg = run.get("config") or {}
    if cfg.get("model_type") != "dots3_note":
        return None
    flops, byts = cd.dsa_core_train(cfg, run["batch_size"], run["seq_len"])
    calls = cd.sizes(cfg)["full"] * run["steps_traced"]
    return scope_tables.roofline(
        run, "components_dots3_note.json", "dsa_core", flops * calls,
        byts * calls, "selected pairs in the work, every causal tile the "
        "kernels visit in the time")
