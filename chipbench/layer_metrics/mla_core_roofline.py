"""Share of its roofline the dense-causal latent-attention core reaches in
training: the least time the chip could take for causal attention over the
triangle of the sequence at the configuration's heads and widths
(costs_glm4_moe_lite.mla_core_train: forward + backward once a call, a call
a layer and one for the prediction module's block) over ALL device time of
component `attn/core/causal`, trunk and module alike (the splash kernels
under a CausalMask, the concatenation of the rope key to every head's keys,
recomputation). Says which bound."""
LAYER = "kernels"
UNIT = "%"
MOVES = "train_tokens_per_s_chip"


def compute(run):
    from chipbench import costs_glm4_moe_lite as cg
    from chipbench import scope_tables
    cfg = run.get("config") or {}
    if cfg.get("model_type") != "glm4_moe_lite":
        return None
    flops, byts = cg.mla_core_train(cfg, run["batch_size"], run["seq_len"])
    calls = cg.mla_core_calls(cfg) * run["steps_traced"]
    return scope_tables.roofline(
        run, "components_glm4_moe_lite.json", "mla_core", flops * calls,
        byts * calls, f"{cg.mla_core_calls(cfg)} calls a step, the causal "
        f"triangle in the work; the tiles' masked halves on the diagonal and "
        f"recomputation in the time")
