"""Device milliseconds a step spends in the learned sparse selection of the
full layers, per device: self time of every operation whose op_name (or
kernel) resolves to `attn/index` (the indexer's projections, its scores,
the heads' summed probabilities and its loss's backward), `attn/select`
(the exact top-k) or `attn/core/selected` (the heads' attention over the
selected keys), forward, backward and recomputed, read through
`components_dots3_note.json`."""
LAYER = "kernels"
UNIT = "ms"
MOVES = "train_tokens_per_s_chip"


def compute(run):
    from chipbench import scope_tables
    if (run.get("config") or {}).get("model_type") != "dots3_note":
        return None
    return scope_tables.ms_per_step(run, "components_dots3_note.json",
                                    "sparse_attn")
