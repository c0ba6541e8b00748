"""Share of its roofline the state-space layer's short convolution +
bias + SiLU reaches in training: the least time the chip could take for
the operations and bytes it REQUIRES (`ssm_conv_train` of the run's own
`costs_<model_type>.py`, which also says how many state-space layers
there are, `sizes(cfg)["mamba"]`; costs_granitemoehybrid's for that
architecture: forward + backward once a state-space layer a step) over
ALL device time of component `ssm/conv`, recomputation included. Says
which bound."""
LAYER = "kernels"
UNIT = "%"
MOVES = "train_tokens_per_s_chip"
FIRST = "components_granitemoehybrid.json"


def compute(run):
    from chipbench import scope_tables
    cfg = run.get("config") or {}
    cg = scope_tables.costs_of(run, "ssm_conv_train")
    if cg is None:
        return None          # an architecture that brings no count of it
    flops, byts = cg.ssm_conv_train(cfg, run["batch_size"], run["seq_len"])
    calls = cg.sizes(cfg)["mamba"] * run["steps_traced"]
    return scope_tables.roofline(
        run, scope_tables.table_of(run, "ssm_conv", FIRST), "ssm_conv",
        flops * calls, byts * calls,
        "recomputation in the time, not in the work")
