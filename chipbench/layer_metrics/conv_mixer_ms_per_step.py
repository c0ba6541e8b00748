"""Device milliseconds a step spends in the gated short-convolution
layers' mixers, per device: self time of every operation whose op_name
resolves to `conv/proj` (the [H, 3H] product into B | C | X), `conv/core`
(B * X, the taps, C *: the two Mosaic calls of kernels/short_conv.py's
third form by their names too) or `conv/out` (the output projection and
the residual add), forward, backward and recomputed, read through the
group `conv` of the run's own `components_<model_type>.json`. None for a
program that names no such scope."""
LAYER = "kernels"
UNIT = "ms"
MOVES = "train_tokens_per_s_chip"
FIRST = "components_lfm2_moe.json"


def compute(run):
    from chipbench import scope_tables
    if "conv_L_cache" not in (run.get("config") or {}):
        return None
    return scope_tables.ms_per_step(
        run, scope_tables.table_of(run, "conv", FIRST), "conv")
