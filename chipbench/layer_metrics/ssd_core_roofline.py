"""Share of its roofline the chunked state-space (SSD) operator reaches
in training: the least time the chip could take for the operations and
bytes the chunked algorithm REQUIRES (costs_granitemoehybrid.ssd_core_train:
forward + backward once a state-space layer a step) over ALL device time
of component `ssm/core`, recomputation included. Says which bound."""
LAYER = "kernels"
UNIT = "%"
MOVES = "train_tokens_per_s_chip"


def compute(run):
    from chipbench import costs_granitemoehybrid as cg
    from chipbench import scope_tables
    cfg = run.get("config") or {}
    if cfg.get("model_type") != "granitemoehybrid":
        return None
    flops, byts = cg.ssd_core_train(cfg, run["batch_size"], run["seq_len"])
    calls = cg.sizes(cfg)["mamba"] * run["steps_traced"]
    return scope_tables.roofline(
        run, "components_granitemoehybrid.json", "ssd_core", flops * calls,
        byts * calls, "recomputation in the time, not in the work")
