"""Share of its roofline the gated short convolution reaches in training:
the least time the chip could take for the operations and bytes of
`short_conv_train` (the run's own `costs_<model_type>.py`: one layer's
`bcx` read and y written forward, `bcx` and dy read and d`bcx` written
backward), times the conv layers of a step (`sizes(cfg)["conv"]`), over
ALL device time of component `conv/core`, the forward made again in the
backward included. It reads the SCOPE, not an instruction's name, so it
reads the same work whether a kernel or the compiler's fusions do it.
Says which bound. None for a configuration without `conv_L_cache`."""
LAYER = "kernels"
UNIT = "%"
MOVES = "train_tokens_per_s_chip"
FIRST = "components_lfm2_moe.json"


def compute(run):
    from chipbench import scope_tables
    cfg = run.get("config") or {}
    cs = scope_tables.costs_of(run, "short_conv_train")
    if "conv_L_cache" not in cfg or cs is None:
        return None
    flops, byts = cs.short_conv_train(cfg, run["batch_size"], run["seq_len"])
    layers = cs.sizes(cfg)["conv"]
    calls = layers * run["steps_traced"]
    return scope_tables.roofline(
        run, scope_tables.table_of(run, "short_conv", FIRST), "short_conv",
        flops * calls, byts * calls,
        f"{layers} conv layers a step, {byts / 1e6:.1f} MB a layer forward "
        f"+ backward; recomputation (the forward again in the backward) in "
        f"the time, not in the work")
