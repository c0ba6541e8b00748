"""Share of its roofline the chunked gated delta-rule operator reaches
in training: the least time the chip could take for the operations and
bytes the chunked algorithm REQUIRES (costs_solar_open2.kda_core_train:
forward + backward once a linear-attention layer a step) over ALL device
time of component `kda/core`, recomputation included. Says which bound."""
LAYER = "kernels"
UNIT = "%"
MOVES = "train_tokens_per_s_chip"


def compute(run):
    from chipbench import costs_solar_open2 as cs
    from chipbench import scope_tables
    cfg = run.get("config") or {}
    if cfg.get("model_type") != "solar_open2":
        return None
    flops, byts = cs.kda_core_train(cfg, run["batch_size"], run["seq_len"])
    calls = cs.sizes(cfg)["kda"] * run["steps_traced"]
    return scope_tables.roofline(
        run, "components_solar_open2.json", "kda_core", flops * calls,
        byts * calls, "recomputation in the time, not in the work")
