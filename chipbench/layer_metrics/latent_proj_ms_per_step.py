"""Device milliseconds a step spends in latent attention's projections,
per device: self time of every operation whose op_name resolves to
`attn/qkv` (down- and up-projections with the latent norms), `attn/gate`
(the heads' gates) or `attn/out`, both layer kinds, forward, backward and
recomputed, read through `components_dots3_note.json`."""
LAYER = "kernels"
UNIT = "ms"
MOVES = "train_tokens_per_s_chip"


def compute(run):
    from chipbench import scope_tables
    if (run.get("config") or {}).get("model_type") != "dots3_note":
        return None
    return scope_tables.ms_per_step(run, "components_dots3_note.json",
                                    "latent_proj")
