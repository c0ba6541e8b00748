"""Device milliseconds a step spends in latent attention's projections
and rotary, per device: self time of every operation whose op_name
resolves to `attn/qkv` (down- and up-projections with the latent norms),
`attn/rope` or `attn/out`, in the trunk's layers and the prediction
module's block, forward, backward and recomputed, read through
`components_glm4_moe_lite.json`."""
LAYER = "kernels"
UNIT = "ms"
MOVES = "train_tokens_per_s_chip"


def compute(run):
    from chipbench import scope_tables
    if (run.get("config") or {}).get("model_type") != "glm4_moe_lite":
        return None
    return scope_tables.ms_per_step(run, "components_glm4_moe_lite.json",
                                    "mla_proj")
