"""Share of its roofline the hyper-connection's mixing reaches in training:
the least time the chip could take for the operations and bytes of
`hc_mix_train` (the run's own `costs_<model_type>.py`: one half-layer's map
+ pre + post, forward + backward, X read and written as seldom as the
arithmetic allows), times the half-layers of a step, over ALL device time
of components `hc/map`, `hc/pre` and `hc/post`, recomputation included. It
reads the same work whether the compiler's fusions or a kernel do it. Says
which bound. None for a configuration without `hc_mult`."""
LAYER = "kernels"
UNIT = "%"
MOVES = "train_tokens_per_s_chip"
FIRST = "components_xing4_0.json"


def compute(run):
    from chipbench import scope_tables
    cfg = run.get("config") or {}
    cs = scope_tables.costs_of(run, "hc_mix_train")
    if "hc_mult" not in cfg or cs is None:
        return None
    flops, byts = cs.hc_mix_train(cfg, run["batch_size"], run["seq_len"])
    halves = cs.hc_halves(cfg)
    calls = halves * run["steps_traced"]
    return scope_tables.roofline(
        run, scope_tables.table_of(run, "hc_mix", FIRST), "hc_mix",
        flops * calls, byts * calls,
        f"{halves} half-layers a step, {byts / 1e6:.1f} MB and "
        f"{flops / 1e9:.2f} GFLOP a half forward + backward; recomputation "
        f"(a feed-forward half makes map and pre again) in the time")
