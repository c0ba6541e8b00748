"""Share of its roofline the state-space layer's short convolution +
bias + SiLU reaches in training: the least time the chip could take for
the operations and bytes it REQUIRES (costs_granitemoehybrid.ssm_conv_train:
forward + backward once a state-space layer a step) over ALL device time
of component `ssm/conv`, recomputation included. Says which bound."""
LAYER = "kernels"
UNIT = "%"
MOVES = "train_tokens_per_s_chip"


def compute(run):
    from chipbench import costs_granitemoehybrid as cg
    from chipbench import scope_tables
    cfg = run.get("config") or {}
    if cfg.get("model_type") != "granitemoehybrid":
        return None
    flops, byts = cg.ssm_conv_train(cfg, run["batch_size"], run["seq_len"])
    calls = cg.sizes(cfg)["mamba"] * run["steps_traced"]
    return scope_tables.roofline(
        run, "components_granitemoehybrid.json", "ssm_conv", flops * calls,
        byts * calls, "recomputation in the time, not in the work")
