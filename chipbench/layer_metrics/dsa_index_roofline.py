"""Share of its roofline the learned selection's indexer reaches in
training: the least time the chip could take for the index scores over
the CAUSAL pairs (costs_dots3_note.dsa_index_train: forward + backward
once a full layer a step, at the bf16 peak) over ALL device time of
component `attn/index`: the float32 projections, the three-pass scores,
the heads' summed probabilities and the loss's backward are all in the
time, none in the work. Says which bound."""
LAYER = "kernels"
UNIT = "%"
MOVES = "train_tokens_per_s_chip"


def compute(run):
    from chipbench import costs_dots3_note as cd
    from chipbench import scope_tables
    cfg = run.get("config") or {}
    if cfg.get("model_type") != "dots3_note":
        return None
    flops, byts = cd.dsa_index_train(cfg, run["batch_size"], run["seq_len"])
    calls = cd.sizes(cfg)["full"] * run["steps_traced"]
    return scope_tables.roofline(
        run, "components_dots3_note.json", "dsa_index", flops * calls,
        byts * calls, "causal pairs in the work; precision passes, the "
        "target and recomputation in the time")
