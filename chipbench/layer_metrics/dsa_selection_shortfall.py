"""|pairs due - pairs attended| summed over the full layers, last step:
the `attended_pairs` buffers the compiled step writes (the ones of the
selection's mask) against batch x sum over t of min(t + 1, index_topk).
0 while the selection is exact: every query keeps exactly index_topk
causal keys (all of them while it has fewer)."""
LAYER = "kernels"
UNIT = "count"
MOVES = "train_tokens_per_s_chip"


def compute(run):
    counters = run.get("counters")
    cfg = run.get("config") or {}
    if not counters or "attended_pairs" not in counters \
            or cfg.get("model_type") != "dots3_note":
        return None
    from chipbench import costs_dots3_note as cd
    due = run["batch_size"] * cd.selected_pairs(cfg, run["seq_len"])
    got = counters["attended_pairs"]
    return sum(abs(due - n) for n in got), (
        f"due {due} a full layer, attended {got}")
