"""Share of its roofline the sliding layers' core reaches in training: the
least time the chip could take for attention over the band of
`sliding_window_size` positions (costs_dots3_note.window_attn_train:
forward + backward once a sliding layer a step) over ALL device time of
component `attn/core/window` (the splash kernels under a LocalMask, the
concatenation of the rope key to every head's keys, recomputation). Says
which bound."""
LAYER = "kernels"
UNIT = "%"
MOVES = "train_tokens_per_s_chip"


def compute(run):
    from chipbench import costs_dots3_note as cd
    from chipbench import scope_tables
    cfg = run.get("config") or {}
    if cfg.get("model_type") != "dots3_note":
        return None
    flops, byts = cd.window_attn_train(cfg, run["batch_size"],
                                       run["seq_len"])
    calls = cd.sizes(cfg)["sliding"] * run["steps_traced"]
    return scope_tables.roofline(
        run, "components_dots3_note.json", "window_attn", flops * calls,
        byts * calls, "the band in the work; tiles the blocks cover "
        "beyond it and recomputation in the time")
